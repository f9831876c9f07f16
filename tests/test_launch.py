"""The launch layer: the train/serve entry points `chip_smoke.py` drives,
the compile-cache placement, the Auto-axis mesh, and the partial-manual
rotor pod trainer (`train/trainer.py`) on the native `jax.shard_map`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import auto_mesh, pctx_for_mesh


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    """Send the entry points' compile cache to a temporary directory and
    restore JAX's global cache setting afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


class TestCompileCache:
    def test_env_dir_wins(self, cache_dir):
        assert compile_cache.enable_compile_cache() == str(cache_dir)
        assert jax.config.jax_compilation_cache_dir == str(cache_dir)

    def test_fixed_in_checkout_default(self, cache_dir, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert compile_cache.REPO_CACHE_DIR.parent.joinpath("src").is_dir()


class TestEntryPoints:
    @pytest.mark.parametrize("trainer", ["opera-dp", "gspmd"])
    def test_train_main_reduced(self, cache_dir, monkeypatch, trainer):
        """Three steps, finite losses, and one compile of the step: the
        state goes in with the shardings the step gives back."""
        from repro.launch import train

        jitted = []
        real_jit = jax.jit

        def spy_jit(*a, **kw):
            jitted.append(real_jit(*a, **kw))
            return jitted[-1]

        monkeypatch.setattr(train.jax, "jit", spy_jit)
        out = train.main(["--arch", "smollm-360m", "--reduced", "--steps",
                          "3", "--batch", "4", "--seq", "16", "--trainer",
                          trainer, "--log-every", "1"])
        assert len(out["losses"]) == 3
        assert np.all(np.isfinite(out["losses"]))
        assert out["param_devices"] == len(jax.devices())
        assert jitted[-1]._cache_size() == 1

    def test_train_main_losses_are_the_step_losses(self, cache_dir,
                                                   monkeypatch):
        """The losses come back as Python floats, one a step, equal to
        the step's own `loss` output, with only the first and last steps
        logged (so the rest are read after the loop)."""
        from repro.launch import train

        seen = []
        real_jit = jax.jit

        def spy_jit(*a, **kw):
            step = real_jit(*a, **kw)

            def call(*args):
                state, metrics = step(*args)
                seen.append(metrics["loss"])
                return state, metrics
            return call

        monkeypatch.setattr(train.jax, "jit", spy_jit)
        out = train.main(["--arch", "smollm-360m", "--reduced", "--steps",
                          "4", "--batch", "4", "--seq", "16",
                          "--log-every", "10"])
        assert len(out["losses"]) == len(seen) == 4
        assert all(type(x) is float for x in out["losses"])
        assert out["losses"] == [float(x) for x in seen]

    def test_serve_main_returns_every_token(self, cache_dir):
        from repro.launch import serve

        done = serve.main(["--arch", "smollm-360m", "--reduced",
                           "--requests", "3", "--slots", "2", "--max-new",
                           "5", "--max-seq", "64"])
        assert sorted(r.rid for r in done) == [0, 1, 2]
        assert all(len(r.out_tokens) == 5 for r in done)

    def test_serve_defaults_to_published_widths(self, monkeypatch):
        """`--reduced` is off unless asked for (it used to be stuck on)."""
        from repro.launch import serve

        seen = {}

        class Stop(Exception):
            pass

        def fake_init(cfg, key):
            seen["d_model"] = cfg.d_model
            raise Stop

        monkeypatch.setattr(serve, "init_params", fake_init)
        monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
        with pytest.raises(Stop):
            serve.main(["--arch", "smollm-360m"])
        assert seen["d_model"] == 960


class TestAutoMesh:
    def test_axes_are_auto(self):
        m = auto_mesh((1, 1), ("data", "model"))
        assert m.axis_names == ("data", "model")
        assert dict(m.shape) == {"data": 1, "model": 1}
        assert set(m.axis_types) == {jax.sharding.AxisType.Auto}


class TestRotorPodTrainer:
    def test_rotor_pod_sync_matches_xla_update(self):
        """grad_sync='rotor' runs the partial-manual pod region (pod
        manual, data/model auto); on a one-device pod mesh its update
        must equal the GSPMD (grad_sync='xla') update."""
        from repro.configs import get_config
        from repro.configs.base import reduced_config
        from repro.data.pipeline import SyntheticLM
        from repro.models import init_params
        from repro.optim.adamw import AdamWConfig
        from repro.train.trainer import init_train_state, make_train_step

        base = reduced_config(get_config("smollm-360m")).replace(
            num_layers=1, vocab_size=64)
        params = init_params(base, jax.random.key(0))
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=4)
        batch = jax.tree.map(jnp.asarray,
                             SyntheticLM(base.vocab_size, 8, 4, seed=0)
                             .batch_at(0))
        mesh = auto_mesh((1, 1, 1), ("pod", "data", "model"))
        pctx = pctx_for_mesh(mesh)
        assert pctx.pod_axis == "pod"
        outs = {}
        for sync in ("xla", "rotor"):
            cfg = base.replace(grad_sync=sync)
            with jax.set_mesh(mesh):
                step = jax.jit(make_train_step(cfg, pctx, opt))
                outs[sync] = step(init_train_state(cfg, params), batch)
        (s1, m1), (s2, m2) = outs["xla"], outs["rotor"]
        assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-6)
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
