"""Loop-aware HLO cost model vs unrolled ground truth."""
import jax

from jax.sharding import PartitionSpec as P

import jax.numpy as jnp
import pytest
from jax import lax

from repro.analysis.hlo import collective_bytes
from repro.analysis.hlo_cost import analyze
from repro.launch.mesh import auto_mesh


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile().as_text()


X = jax.ShapeDtypeStruct((128, 128), jnp.float32)


class TestLoopAwareness:
    def test_scan_matches_unroll(self):
        def f_scan(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            y, _ = lax.scan(body, x, None, length=10)
            return y.sum()

        def f_unroll(x, w):
            for _ in range(10):
                x = jnp.tanh(x @ w)
            return x.sum()

        a = analyze(_compile(f_scan, X, X))
        b = analyze(_compile(f_unroll, X, X))
        assert a["flops"] == pytest.approx(b["flops"], rel=0.02)

    def test_nested_scan(self):
        def g(x, w):
            def outer(c, _):
                def inner(d, _):
                    return d @ w, None
                d, _ = lax.scan(inner, c, None, length=5)
                return d, None
            y, _ = lax.scan(outer, x, None, length=4)
            return y.sum()

        a = analyze(_compile(g, X, X))
        expect = 20 * 2 * 128**3
        assert a["flops"] == pytest.approx(expect, rel=0.02)

    def test_dot_flops_exact(self):
        def f(x, w):
            return (x @ w).sum()

        a = analyze(_compile(f, X, X))
        assert a["flops"] == pytest.approx(2 * 128**3, rel=0.02)

    def test_batch_dot(self):
        B = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
        W = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)

        def f(a, b):
            return jnp.einsum("bij,bjk->bik", a, b).sum()

        a = analyze(_compile(f, B, W))
        assert a["flops"] == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.05)

    def test_bytes_positive_and_bounded(self):
        def f(x, w):
            return (x @ w).sum()

        a = analyze(_compile(f, X, X))
        lo = 3 * 128 * 128 * 4          # operands + output once
        assert a["bytes"] >= lo
        assert a["bytes"] <= 20 * lo     # fusion slack


class TestCollectiveAccounting:
    def test_psum_inside_scan_multiplied(self):
        """Naive text grep counts loop collectives once; analyze() must
        multiply by trip count."""
        mesh = auto_mesh((1,), ("d",))

        def f(x):
            def per(a):
                def body(c, _):
                    return lax.psum(c, "d") * 0.5, None
                y, _ = lax.scan(body, a, None, length=7)
                return y
            return jax.shard_map(
                per, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
                check_vma=False,
            )(x)

        spec = jax.ShapeDtypeStruct((4, 8), jnp.float32)
        hlo = jax.jit(f).lower(spec).compile().as_text()
        la = analyze(hlo)
        naive = collective_bytes(hlo)
        if naive["count_total"] > 0:  # CPU may elide 1-device collectives
            assert la["coll_count_total"] >= 7 * naive["count_total"] * 0.9
