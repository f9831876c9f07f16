"""Public model API: init, shapes, forwards (train / prefill / decode), loss.

All entry points are pure functions of (params, batch) suitable for
jax.jit with NamedSharding in/out specs, or for eval_shape-based dry-runs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import transformer as T
from repro.models.kvcache import cache_specs, init_cache
from repro.models.layers import apply_norm, dense_init, embed_init, init_norm
from repro.models.parallel import ParallelContext


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    plan = T.stack_plan(cfg)
    p: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "stack": T.init_stack(ks[1], cfg, plan),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dt)
    if cfg.family == "encdec":
        p["encoder"] = T.init_stack(ks[3], cfg, T.encoder_plan(cfg))
        p["enc_norm"] = init_norm(cfg.norm, cfg.d_model, dt)
    return p


def param_shapes(cfg: ModelConfig) -> Dict:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0)
    )


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = param_shapes(cfg)
    total = 0
    m = cfg.moe

    def visit(path, leaf):
        nonlocal total
        n = int(np.prod(leaf.shape))
        if active_only and m is not None:
            keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            if any("moe" == k for k in keys) and any(
                k in ("w_gate", "w_up", "w_down") for k in keys
            ):
                if m.num_experts in leaf.shape:
                    n = int(n * m.top_k / m.num_experts)
        total += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    return total


# --------------------------------------------------------------------------
# forwards
# --------------------------------------------------------------------------


def _embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    return x.astype(jnp.dtype(cfg.compute_dtype))


def _logits(params, x, cfg: ModelConfig):
    x = apply_norm(cfg.norm, params["final_norm"], x, upcast=cfg.norm_upcast)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    return x.astype(jnp.float32) @ head.astype(jnp.float32)


def _encode(params, encoder_embeds, cfg, pctx):
    S = encoder_embeds.shape[1]
    ctx = T.LayerCtx(
        positions=jnp.arange(S, dtype=jnp.int32), mode="train"
    )
    x, _, _ = T.apply_stack(
        params["encoder"], encoder_embeds, cfg, pctx, ctx, T.encoder_plan(cfg)
    )
    return apply_norm(cfg.norm, params["enc_norm"], x, upcast=cfg.norm_upcast)


def forward_train(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    pctx: ParallelContext,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V) fp32, aux_loss)."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    cross_src = None
    if cfg.family == "encdec":
        cross_src = _encode(params, batch["encoder_embeds"], cfg, pctx)
    elif cfg.family == "vlm":
        cross_src = batch["image_embeds"]
    x = _embed(params, tokens, cfg)
    ctx = T.LayerCtx(
        positions=jnp.arange(S, dtype=jnp.int32),
        cross_src=cross_src,
        mode="train",
    )
    x, aux, _ = T.apply_stack(
        params["stack"], x, cfg, pctx, ctx, T.stack_plan(cfg)
    )
    return _logits(params, x, cfg), aux


def forward_prefill(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    pctx: ParallelContext,
    cache_len: Optional[int] = None,
):
    """Returns (last-token logits (B,V), decode caches).

    With cache_len, self-attention K/V caches are padded to that length so
    decode steps have slots to write into (ring-buffer window caches are
    already sized to their window and are left alone).
    """
    tokens = batch["tokens"]
    S = tokens.shape[1]
    cross_src = None
    if cfg.family == "encdec":
        cross_src = _encode(params, batch["encoder_embeds"], cfg, pctx)
    elif cfg.family == "vlm":
        cross_src = batch["image_embeds"]
    x = _embed(params, tokens, cfg)
    ctx = T.LayerCtx(
        positions=jnp.arange(S, dtype=jnp.int32),
        cross_src=cross_src,
        mode="prefill",
    )
    x, _, caches = T.apply_stack(
        params["stack"], x, cfg, pctx, ctx, T.stack_plan(cfg)
    )
    if cache_len is not None and cache_len > S:
        window = cfg.hybrid.local_window if cfg.hybrid else 0

        def pad(path, leaf):
            name = str(getattr(path[-1], "key", ""))
            if name not in ("k", "v") or leaf.ndim < 4:
                return leaf
            sdim = leaf.ndim - 2
            cur = leaf.shape[sdim]
            if cur != S:
                return leaf  # ring cache already at its window size
            tgt = min(cache_len, window) if window else cache_len
            if tgt <= cur:
                return leaf
            pads = [(0, 0)] * leaf.ndim
            pads[sdim] = (0, tgt - cur)
            return jnp.pad(leaf, pads)

        caches = jax.tree_util.tree_map_with_path(pad, caches)
    return _logits(params, x[:, -1:], cfg)[:, 0], caches


def forward_decode(
    params: Dict,
    tokens: jnp.ndarray,        # (B, 1)
    positions: jnp.ndarray,     # (B,)
    caches,
    cfg: ModelConfig,
    pctx: ParallelContext,
):
    """One decode step.  Returns (logits (B,V), new caches)."""
    x = _embed(params, tokens, cfg)
    ctx = T.LayerCtx(pos=positions, mode="decode")
    x, _, new_caches = T.apply_stack(
        params["stack"], x, cfg, pctx, ctx, T.stack_plan(cfg), caches=caches
    )
    return _logits(params, x, cfg)[:, 0], new_caches


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------


def _pick_gold(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """``logits[..., targets]`` as a one-hot select-sum, not a gather.

    Exact (one nonzero term per row), and elementwise over the vocab dim,
    so a vocab-sharded logits tensor reduces with one all-reduce.  XLA's
    gather partitioner aborts on that layout inside a partial-manual
    `shard_map` region (the rotor pod trainer, train/trainer.py).
    """
    vocab = jnp.arange(logits.shape[-1], dtype=targets.dtype)
    return jnp.where(vocab == targets[..., None], logits, 0.0).sum(-1)


def softmax_xent(logits: jnp.ndarray, targets: jnp.ndarray, z_weight=1e-4):
    """Mean token cross-entropy (+ z-loss) in fp32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = _pick_gold(logits, targets)
    ce = (lse - gold).mean()
    z = (lse**2).mean() * z_weight
    return ce + z, ce


def _pick_chunk(v: int, target: int) -> int:
    c = min(target, v)
    while v % c:
        c -= 1
    return max(c, 1)


def softmax_xent_chunked(
    x: jnp.ndarray,        # (B, S, D) final normed hidden
    head: jnp.ndarray,     # (D, V)
    targets: jnp.ndarray,  # (B, S)
    chunk: int,
    z_weight=1e-4,
):
    """Vocab-chunked CE: the (B, S, V) logits are never materialized.

    Online logsumexp over vocab chunks inside a rematerialized scan — the
    classic memory-roofline optimization for large-vocab losses (§Perf).
    """
    D, V = head.shape
    c = _pick_chunk(V, chunk)
    nc = V // c
    x32 = x.astype(jnp.float32)
    hc = head.astype(jnp.float32).reshape(D, nc, c).transpose(1, 0, 2)
    los = jnp.arange(nc) * c

    @jax.checkpoint
    def body(carry, xs):
        m, s, gold = carry
        h, lo = xs
        logits = x32 @ h                                    # (B, S, c)
        m_new = jnp.maximum(m, logits.max(-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[..., None]
        ).sum(-1)
        t_loc = targets - lo
        in_chunk = (t_loc >= 0) & (t_loc < c)
        gold = gold + jnp.where(in_chunk, _pick_gold(logits, t_loc), 0.0)
        return (m_new, s, gold), None

    B, S = targets.shape
    m0 = jnp.full((B, S), -1e30, jnp.float32)
    s0 = jnp.zeros((B, S), jnp.float32)
    g0 = jnp.zeros((B, S), jnp.float32)
    (m, s, gold), _ = jax.lax.scan(body, (m0, s0, g0), (hc, los))
    lse = m + jnp.log(jnp.maximum(s, 1e-30))
    ce = (lse - gold).mean()
    z = (lse**2).mean() * z_weight
    return ce + z, ce


def forward_train_hidden(params, batch, cfg: ModelConfig, pctx):
    """Like forward_train but stops before the LM head (chunked loss)."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    cross_src = None
    if cfg.family == "encdec":
        cross_src = _encode(params, batch["encoder_embeds"], cfg, pctx)
    elif cfg.family == "vlm":
        cross_src = batch["image_embeds"]
    x = _embed(params, tokens, cfg)
    ctx = T.LayerCtx(
        positions=jnp.arange(S, dtype=jnp.int32),
        cross_src=cross_src,
        mode="train",
    )
    x, aux, _ = T.apply_stack(
        params["stack"], x, cfg, pctx, ctx, T.stack_plan(cfg)
    )
    return apply_norm(cfg.norm, params["final_norm"], x,
                      upcast=cfg.norm_upcast), aux


def loss_fn(
    params: Dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    pctx: ParallelContext,
):
    if cfg.loss_chunk_vocab:
        x, aux = forward_train_hidden(params, batch, cfg, pctx)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        total, ce = softmax_xent_chunked(
            x, head, batch["targets"], cfg.loss_chunk_vocab
        )
    else:
        logits, aux = forward_train(params, batch, cfg, pctx)
        total, ce = softmax_xent(logits, batch["targets"])
    if cfg.moe is not None:
        total = total + cfg.moe.router_aux_weight * aux
    return total, {"loss": ce, "aux": aux, "total": total}
