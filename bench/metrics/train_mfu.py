"""Model FLOP utilization of the whole training step: the forward and
backward operations per token (`bench/flops.py`, no recompute) times
the traced window's tokens per second, over chips x the chip's bf16
peak (`bench/peaks.json`)."""

from bench import flops


def read(ctx):
    per_token = flops.train_flops_per_token(ctx["config"],
                                            ctx["traffic"]["seq_len"])
    peak = flops.peak(ctx["device_kind"])["bf16_flops_per_s"]
    tokens_per_s = ctx["work"] / ctx["trace"].window_s
    return 100.0 * per_token * tokens_per_s / (ctx["chips"] * peak)
