"""The §Perf levers must preserve semantics: chunked CE == standard CE,
bf16 normalize ~= fp32 normalize, layouts don't change the math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduced_config
from repro.launch.mesh import auto_mesh
from repro.models import init_params, loss_fn
from repro.models.layers import apply_norm, init_norm
from repro.models.model import softmax_xent, softmax_xent_chunked
from repro.models.parallel import single_device_ctx

RNG = np.random.default_rng(0)


class TestChunkedCE:
    @pytest.mark.parametrize("V,chunk", [(64, 16), (96, 32), (50, 50), (50, 7)])
    def test_matches_full_loss(self, V, chunk):
        B, S, D = 2, 8, 16
        x = jnp.asarray(RNG.normal(size=(B, S, D)), jnp.float32)
        head = jnp.asarray(RNG.normal(size=(D, V)) * 0.2, jnp.float32)
        tgt = jnp.asarray(RNG.integers(0, V, (B, S)), jnp.int32)
        full, ce_f = softmax_xent(x @ head, tgt)
        chk, ce_c = softmax_xent_chunked(x, head, tgt, chunk)
        assert float(ce_f) == pytest.approx(float(ce_c), rel=1e-5)
        assert float(full) == pytest.approx(float(chk), rel=1e-5)

    def test_gradients_match(self):
        B, S, D, V = 1, 4, 8, 32
        x = jnp.asarray(RNG.normal(size=(B, S, D)), jnp.float32)
        head = jnp.asarray(RNG.normal(size=(D, V)) * 0.2, jnp.float32)
        tgt = jnp.asarray(RNG.integers(0, V, (B, S)), jnp.int32)
        g1 = jax.grad(lambda h: softmax_xent(x @ h, tgt)[0])(head)
        g2 = jax.grad(lambda h: softmax_xent_chunked(x, h, tgt, 8)[0])(head)
        np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-5)

    def test_loss_fn_chunked_config_matches(self):
        cfg = reduced_config(get_config("smollm-360m")).replace(num_layers=2)
        params = init_params(cfg, jax.random.key(0))
        batch = {
            "tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)),
                                  jnp.int32),
            "targets": jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)),
                                   jnp.int32),
        }
        t1, _ = loss_fn(params, batch, cfg, single_device_ctx())
        t2, _ = loss_fn(params, batch, cfg.replace(loss_chunk_vocab=64),
                        single_device_ctx())
        assert float(t1) == pytest.approx(float(t2), rel=1e-4)


class TestNormDowncast:
    @pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
    def test_bf16_normalize_close(self, kind):
        p = init_norm(kind, 64, jnp.float32)
        x = jnp.asarray(RNG.normal(size=(4, 16, 64)), jnp.bfloat16)
        a = apply_norm(kind, p, x, upcast=True)
        b = apply_norm(kind, p, x, upcast=False)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_model_trains_with_downcast_norm(self):
        cfg = reduced_config(get_config("yi-9b")).replace(
            num_layers=2, norm_upcast=False
        )
        params = init_params(cfg, jax.random.key(0))
        batch = {
            "tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)),
                                  jnp.int32),
            "targets": jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)),
                                   jnp.int32),
        }
        (total, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, single_device_ctx()),
            has_aux=True,
        )(params)
        assert jnp.isfinite(total)


class TestLayouts:
    def test_dp_only_pctx_math_unchanged(self):
        """dp_only must be a layout change only: same loss on 1 device."""
        from repro.launch.mesh import pctx_for_mesh

        cfg = reduced_config(get_config("smollm-360m")).replace(num_layers=2)
        params = init_params(cfg, jax.random.key(0))
        batch = {
            "tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)),
                                  jnp.int32),
            "targets": jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)),
                                   jnp.int32),
        }
        mesh = auto_mesh((1, 1), ("data", "model"))
        with jax.set_mesh(mesh):
            t1, _ = loss_fn(params, batch, cfg, pctx_for_mesh(mesh))
            t2, _ = loss_fn(params, batch, cfg,
                            pctx_for_mesh(mesh, layout="dp_only"))
            t3, _ = loss_fn(params, batch, cfg,
                            pctx_for_mesh(mesh, layout="tp_only"))
        assert float(t1) == pytest.approx(float(t2), rel=1e-5)
        assert float(t1) == pytest.approx(float(t3), rel=1e-5)
