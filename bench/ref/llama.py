"""Plain reference of a dense decoder (SmolLM / Llama layout) and its
training step, in jax.numpy float32.

Per layer: RMSNorm, grouped-query attention with rotary positions
(rotate-half convention, base `rope_theta`) and a causal softmax, a
residual add; RMSNorm, a SiLU-gated MLP, a residual add.  A final
RMSNorm and the tied embedding give the logits.  The objective is the
mean token cross-entropy plus `z_loss` x mean(logsumexp^2).  The step is
AdamW with global-norm clipping, linear warm-up and decoupled weight
decay on every matrix.

`precision` chooses how every matrix product is taken: `exact` at
HIGHEST (float32), or `fp8`, with both operands rounded to 3 mantissa
bits first (the control: one precision step below the configuration's
bfloat16).  Gradients are summed over blocks of rows and over chips.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
              "w_down")
NO_DECAY = ("ln1", "ln2", "final_norm")


def shapes(cfg: dict) -> Dict[str, tuple]:
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return dict(embed=(V, d), final_norm=(d,), ln1=(L, d), ln2=(L, d),
                wq=(L, d, q), wk=(L, d, kv), wv=(L, d, kv), wo=(L, q, d),
                w_gate=(L, d, f), w_up=(L, d, f), w_down=(L, f, d))


def make_weights(cfg: dict, seed: int, sharding=None) -> Dict:
    """Random weights from the seed, made on the device in one call:
    fan-in scaled normals, unit norm scales, float32."""
    shp = shapes(cfg)

    def build(key):
        keys = dict(zip(sorted(shp), jax.random.split(key, len(shp))))
        out = {}
        for name, s in shp.items():
            if name in NO_DECAY:
                out[name] = jnp.ones(s, jnp.float32)
            else:
                fan_in = s[-1] if name == "embed" else s[-2]
                out[name] = (jax.random.normal(keys[name], s, jnp.float32)
                             * fan_in ** -0.5)
        return out

    return jax.jit(build, out_shardings=sharding)(
        jax.random.key(seed % 2**63))


def _round(x, mantissa_bits):
    return jax.lax.reduce_precision(x, exponent_bits=8,
                                    mantissa_bits=mantissa_bits)


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _round(a, 3), _round(b, 3)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (b, h, s, hd); rotate-half rotary embedding at positions 0..s-1."""
    hd, s = x.shape[-1], x.shape[-2]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_sums(w: Dict, tokens, targets, cfg: dict, precision: str):
    """(sum of token cross-entropies, sum of logsumexp^2) over a block of
    rows; tokens and targets (b, s) int32."""
    b, s = tokens.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(_mm, precision=precision)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = mm("bsd,dk->bsk", h, lw["wq"]).reshape(b, s, hq, hd)
        k = mm("bsd,dk->bsk", h, lw["wk"]).reshape(b, s, hkv, hd)
        v = mm("bsd,dk->bsk", h, lw["wv"]).reshape(b, s, hkv, hd)
        q = _rope(q.transpose(0, 2, 1, 3), cfg["rope_theta"])
        k = _rope(k.transpose(0, 2, 1, 3), cfg["rope_theta"])
        v = v.transpose(0, 2, 1, 3)
        k = jnp.repeat(k, hq // hkv, axis=1)      # query head h reads h // G
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = mm("bhqd,bhkd->bhqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm("bhqk,bhkd->bhqd", p, v).transpose(0, 2, 1, 3)
        x = x + mm("bsk,kd->bsd", o.reshape(b, s, hq * hd), lw["wo"])
        h = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, lw["w_gate"]))
        u = mm("bsd,df->bsf", h, lw["w_up"])
        return x + mm("bsf,fd->bsd", g * u, lw["w_down"]), None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x,
                        {k: w[k] for k in LAYER_KEYS})
    x = _rms(x, w["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, w["embed"])
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (lse - gold).sum(), (lse * lse).sum()


def make_grad_fn(cfg: dict, job: dict, mesh, axis: str, precision: str,
                 rows_per_chip: int, exchange: bool = True):
    """(weights, tokens, targets) -> (mean cross-entropy, gradient of the
    objective), the batch's rows split over `axis` of `mesh` as the
    program splits them, each chip summing its rows `block_rows` at a
    time, the chips' sums added with one psum.  Two faults can be
    planted: `rows_per_chip` below each chip's share leaves rows out, and
    `exchange=False` leaves each chip with its own rows' gradient."""
    from jax.sharding import PartitionSpec as P

    blk = job["block_rows"]
    n_chips = mesh.shape[axis]

    def objective(w, tok, tgt):
        ce, z = block_sums(w, tok, tgt, cfg, precision)
        return ce + job["z_loss"] * z, ce

    def per_chip(w, tok, tgt):
        tok, tgt = tok[:rows_per_chip], tgt[:rows_per_chip]
        nb = rows_per_chip // blk
        tok = tok.reshape(nb, blk, -1)
        tgt = tgt.reshape(nb, blk, -1)
        n_tokens = rows_per_chip * tok.shape[-1] * (n_chips if exchange
                                                    else 1)

        def body(acc, xs):
            (_, ce), g = jax.value_and_grad(objective, has_aux=True)(w, *xs)
            return jax.tree.map(jnp.add, acc, (ce, g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
        (ce, g), _ = jax.lax.scan(body, zero, (tok, tgt))
        if exchange:
            ce, g = jax.lax.psum((ce, g), axis)
        return ce / n_tokens, jax.tree.map(lambda x: x / n_tokens, g)

    return jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False))


def adamw(job: dict, w, g, m, v, step: int):
    """One AdamW step (step counts from 0); returns (w, m, v, the clipped
    gradient the moments took)."""
    b1, b2 = job["beta1"], job["beta2"]
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, job["clip_norm"] / jnp.maximum(gnorm, 1e-9)), g)
    lr = job["lr"] * min(1.0, (step + 1) / max(job["warmup_steps"], 1))
    t = step + 1
    out_w, out_m, out_v = {}, {}, {}
    for k in w:
        mk = b1 * m[k] + (1 - b1) * g[k]
        vk = b2 * v[k] + (1 - b2) * g[k] * g[k]
        delta = (mk / (1 - b1 ** t)) / (jnp.sqrt(vk / (1 - b2 ** t))
                                        + job["eps"])
        if k not in NO_DECAY:
            delta = delta + job["weight_decay"] * w[k]
        out_w[k], out_m[k], out_v[k] = w[k] - lr * delta, mk, vk
    return out_w, out_m, out_v, g


def leaf_norms(tree: Dict, minus: Dict = None) -> Dict[str, np.ndarray]:
    """Norm of every leaf (of `tree - minus`, fused, where given), a
    stacked leaf counting one leaf per layer."""
    def norm(name, x):
        x = x.astype(jnp.float32)
        if name in LAYER_KEYS:
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))[None]

    def norms(t, m):
        return {k: norm(k, x if m is None else x - m[k])
                for k, x in t.items()}

    out = jax.jit(norms)(tree, minus)
    return {k: np.asarray(x, np.float64) for k, x in out.items()}
