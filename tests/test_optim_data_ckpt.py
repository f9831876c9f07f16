"""Optimizer, data pipeline, checkpoint/elastic-restore tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import auto_mesh
from repro.optim.adamw import (
    AdamWConfig,
    adamw_update,
    global_norm,
    init_opt_state,
    lr_at,
)
from repro.train.checkpoint import Checkpointer


class TestAdamW:
    def _params(self):
        return {
            "w": jnp.ones((4, 4)) * 0.5,
            "ln": {"scale": jnp.ones((4,))},
        }

    def test_quadratic_converges(self):
        c = AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                        weight_decay=0.0)
        params = {"w": jnp.array([3.0, -2.0])}
        st_ = init_opt_state(params)
        for _ in range(150):
            grads = {"w": 2 * params["w"]}
            params, st_, _ = adamw_update(c, params, grads, st_)
        assert float(jnp.abs(params["w"]).max()) < 0.15

    def test_clipping(self):
        c = AdamWConfig(clip_norm=1.0, warmup_steps=1)
        params = self._params()
        st_ = init_opt_state(params)
        grads = jax.tree.map(lambda p: 1e6 * jnp.ones_like(p), params)
        _, _, m = adamw_update(c, params, grads, st_)
        assert float(m["grad_norm"]) > 1e5  # reported pre-clip

    def test_no_decay_on_norm_scales(self):
        c = AdamWConfig(lr=0.1, weight_decay=1.0, warmup_steps=1)
        params = self._params()
        st_ = init_opt_state(params)
        zero_g = jax.tree.map(jnp.zeros_like, params)
        new, _, _ = adamw_update(c, params, zero_g, st_)
        # zero grads: decayed params shrink, norm scales must not
        assert float(new["w"].mean()) < 0.5
        np.testing.assert_allclose(new["ln"]["scale"], params["ln"]["scale"])

    @pytest.mark.parametrize(
        "step", [0, 1, 50, 99, 100, 101, 500, 5000, 9999, 10_000, 13_337,
                 20_000]
    )
    def test_lr_schedule_bounds(self, step):
        c = AdamWConfig(lr=3e-4, warmup_steps=100, total_steps=10_000)
        lr = float(lr_at(c, jnp.asarray(step)))
        # lr_at computes in float32: allow one ulp of representation slack
        assert 0.0 < lr <= c.lr * (1 + 1e-6)
        if step >= c.total_steps:
            assert lr == pytest.approx(c.lr * c.min_lr_frac, rel=1e-3)


class TestPipeline:
    def test_deterministic_and_resumable(self):
        src = SyntheticLM(128, 16, 4, seed=7)
        a = src.batch_at(13)
        b = SyntheticLM(128, 16, 4, seed=7).batch_at(13)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_targets_are_next_tokens(self):
        src = SyntheticLM(128, 16, 4, seed=7)
        b = src.batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])

    def test_learnable_structure(self):
        src = SyntheticLM(64, 512, 8, seed=0)
        b = src.batch_at(0)
        # successors constrained: conditional entropy well below ln(V)
        assert src.conditional_entropy() < 0.7 * np.log(64)


class TestCheckpoint:
    def _state(self, scale=1.0):
        return {
            "params": {"w": jnp.arange(12.0).reshape(3, 4) * scale,
                       "b": jnp.ones((4,)) * scale},
            "opt": {"step": jnp.asarray(5, jnp.int32)},
        }

    def test_roundtrip(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        state = self._state()
        ck.save(100, state, blocking=True)
        restored, step = ck.restore(state)
        assert step == 100
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b), state, restored
        )

    def test_keep_last_k(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, self._state(s), blocking=True)
        assert ck.steps() == [3, 4]

    def test_elastic_restore_onto_mesh(self, tmp_path):
        """Save unsharded, restore onto an explicit (1,1) mesh sharding —
        the elastic-resize path (mesh-shape-independent checkpoint)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        ck = Checkpointer(str(tmp_path))
        state = self._state()
        ck.save(7, state, blocking=True)
        mesh = auto_mesh((1, 1), ("data", "model"))
        shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), state
        )
        restored, step = ck.restore(state, shardings=shardings)
        assert step == 7
        assert restored["params"]["w"].sharding.mesh.shape == {"data": 1,
                                                               "model": 1}

    def test_async_save_then_wait(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(3, self._state(), blocking=False)
        ck.wait()
        assert ck.latest_step() == 3


class TestGlobalNorm:
    @pytest.mark.parametrize("s", [0.1, 0.5, 1.0, 2.0, 3.7, 25.0, 100.0])
    def test_scaling_property(self, s):
        t = {"a": jnp.ones((3,)), "b": jnp.full((2, 2), 2.0)}
        n1 = float(global_norm(t))
        n2 = float(global_norm(jax.tree.map(lambda x: x * s, t)))
        assert n2 == pytest.approx(n1 * s, rel=1e-4)
