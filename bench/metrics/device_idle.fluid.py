"""Share of the traced window in which no operation runs on the chip:
1 - busy / window, in percent."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
