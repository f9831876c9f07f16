"""Integration: training learns, checkpoint-restart is exact, serving runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduced_config
from repro.data.pipeline import SyntheticLM, device_batches
from repro.launch.mesh import auto_mesh
from repro.models import init_params
from repro.models.parallel import single_device_ctx
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import Request, ServeEngine
from repro.train.checkpoint import Checkpointer
from repro.train.opera_dp import init_opera_dp_state, make_opera_dp_train_step
from repro.train.trainer import init_train_state, make_train_step


def _mesh11():
    return auto_mesh((1, 1), ("data", "model"))


def _tiny():
    cfg = reduced_config(get_config("smollm-360m")).replace(
        num_layers=2, vocab_size=64
    )
    return cfg


class TestTrainerLearns:
    def test_loss_decreases_gspmd(self):
        cfg = _tiny()
        params = init_params(cfg, jax.random.key(0))
        opt = AdamWConfig(lr=2e-3, warmup_steps=5, total_steps=60)
        pctx = single_device_ctx()
        step = jax.jit(make_train_step(cfg, pctx, opt))
        state = init_train_state(cfg, params)
        src = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
        losses = []
        for i in range(60):
            state, m = step(state, jax.tree.map(jnp.asarray, src.batch_at(i)))
            losses.append(float(m["loss"]))
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        assert last < first - 0.5, f"not learning: {first:.3f} -> {last:.3f}"
        assert last < np.log(cfg.vocab_size)  # beats uniform

    def test_opera_dp_equals_gspmd_on_one_device(self):
        """The explicit rotor DP trainer must produce the same update as
        the jit trainer when the mesh is 1x1 (all collectives degenerate)."""
        cfg = _tiny()
        params = init_params(cfg, jax.random.key(1))
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        src = SyntheticLM(cfg.vocab_size, 16, 4, seed=1)
        batch = jax.tree.map(jnp.asarray, src.batch_at(0))

        mesh = _mesh11()
        from repro.launch.mesh import pctx_for_mesh

        pctx = pctx_for_mesh(mesh)
        with jax.set_mesh(mesh):
            s1 = init_train_state(cfg, params)
            s1, m1 = jax.jit(make_train_step(cfg, pctx, opt))(s1, batch)
            s2 = init_opera_dp_state(params)
            s2, m2 = jax.jit(make_opera_dp_train_step(cfg, pctx, opt))(s2, batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
        a = jax.tree.leaves(s1["params"])
        b = jax.tree.leaves(s2["params"])
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32),
                                       atol=1e-5, rtol=1e-5)

    def test_compressed_grad_sync_still_learns(self):
        cfg = _tiny()
        params = init_params(cfg, jax.random.key(2))
        opt = AdamWConfig(lr=2e-3, warmup_steps=5, total_steps=40)
        mesh = _mesh11()
        from repro.launch.mesh import pctx_for_mesh

        pctx = pctx_for_mesh(mesh)
        src = SyntheticLM(cfg.vocab_size, 32, 8, seed=2)
        with jax.set_mesh(mesh):
            step = jax.jit(
                make_opera_dp_train_step(cfg, pctx, opt, compress=True)
            )
            state = init_opera_dp_state(params, compress=True)
            losses = []
            for i in range(40):
                state, m = step(state, jax.tree.map(jnp.asarray, src.batch_at(i)))
                losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


class TestCheckpointRestart:
    def test_restart_is_bit_exact(self, tmp_path):
        """Kill-and-restore: steps 0..9 straight vs 0..4 + restore + 5..9."""
        cfg = _tiny()
        params = init_params(cfg, jax.random.key(3))
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        pctx = single_device_ctx()
        step = jax.jit(make_train_step(cfg, pctx, opt))
        src = SyntheticLM(cfg.vocab_size, 16, 4, seed=3)

        sA = init_train_state(cfg, params)
        for i in range(10):
            sA, _ = step(sA, jax.tree.map(jnp.asarray, src.batch_at(i)))

        sB = init_train_state(cfg, params)
        ck = Checkpointer(str(tmp_path))
        for i in range(5):
            sB, _ = step(sB, jax.tree.map(jnp.asarray, src.batch_at(i)))
        ck.save(5, sB, blocking=True)
        sB2, start = ck.restore(sB)  # simulated crash + restart
        assert start == 5
        for i in range(start, 10):
            sB2, _ = step(sB2, jax.tree.map(jnp.asarray, src.batch_at(i)))

        for x, y in zip(jax.tree.leaves(sA["params"]),
                        jax.tree.leaves(sB2["params"])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestServeEngine:
    @pytest.mark.parametrize("arch", ["smollm-360m", "recurrentgemma-2b",
                                      "falcon-mamba-7b"])
    def test_continuous_batching(self, arch):
        cfg = reduced_config(get_config(arch))
        params = init_params(cfg, jax.random.key(0))
        eng = ServeEngine(cfg, params, single_device_ctx(), slots=2,
                          max_seq=32)
        rng = np.random.default_rng(0)
        for rid in range(4):  # more requests than slots
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                max_new_tokens=4,
            ))
        done = eng.run_to_completion(max_ticks=64)
        assert len(done) == 4
        for r in done:
            assert len(r.out_tokens) >= 2
            assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)

    def test_greedy_decode_consistent_with_forward(self):
        """Engine's first decoded token == argmax of a fresh prefill."""
        from repro.models.model import forward_prefill

        cfg = reduced_config(get_config("smollm-360m"))
        params = init_params(cfg, jax.random.key(0))
        prompt = np.arange(1, 7, dtype=np.int32)
        eng = ServeEngine(cfg, params, single_device_ctx(), slots=1,
                          max_seq=32)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
        eng.step()
        logits, _ = forward_prefill(
            params, {"tokens": jnp.asarray(prompt[None])}, cfg,
            single_device_ctx(),
        )
        want = int(jnp.argmax(logits[0]))
        got = eng.finished[0].out_tokens[0] if eng.finished else \
            [r for r in eng.active if r][0].out_tokens[0]
        assert got == want
