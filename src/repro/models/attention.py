"""GQA/MQA/MHA attention: training (causal), prefill, and cached decode.

The einsum formulation below is the XLA path used for lowering/dry-run; the
Pallas flash-attention kernel (repro.kernels.flash_attention) is an optional
drop-in for the training path on real TPUs (cfg-level switch in the bundle).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.axes import logical_constraint, weight_constraint
from repro.models.layers import apply_rotary, rotary_embedding
from repro.models.params import P


def wo_matrix(p: Dict[str, jax.Array]) -> jax.Array:
    """Output projection with FSDP gather-at-use applied."""
    return weight_constraint(p["wo"], "q_features", "embed")

NEG_INF = -1e30


def attention_specs(cfg: ArchConfig, d_in: Optional[int] = None
                    ) -> Dict[str, P]:
    """q/k/v read ``d_in`` features (default d_model); wo writes d_model."""
    d, h = cfg.d_model, cfg.resolved_head_dim()
    d_in = d_in or d
    return {
        "wq": P((d_in, cfg.n_heads * h), ("embed", "q_features")),
        "wk": P((d_in, cfg.n_kv_heads * h), ("embed", "kv_features")),
        "wv": P((d_in, cfg.n_kv_heads * h), ("embed", "kv_features")),
        "wo": P((cfg.n_heads * h, d), ("q_features", "embed")),
    }


def qkv(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
        positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> q (B,S,nq,h), k/v (B,S,nkv,h), rotary applied."""
    B, S, _ = x.shape
    h = cfg.resolved_head_dim()
    wq = weight_constraint(p["wq"], "embed", "q_features")
    wk = weight_constraint(p["wk"], "embed", "kv_features")
    wv = weight_constraint(p["wv"], "embed", "kv_features")
    q = (x @ wq).reshape(B, S, cfg.n_heads, h)
    k = (x @ wk).reshape(B, S, cfg.n_kv_heads, h)
    v = (x @ wv).reshape(B, S, cfg.n_kv_heads, h)
    cos, sin = rotary_embedding(positions, h, cfg.rope_theta, x.dtype)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    q = logical_constraint(q, "batch", "seq", "heads", None)
    k = logical_constraint(k, "batch", "kv_seq", "kv_heads", None)
    v = logical_constraint(v, "batch", "kv_seq", "kv_heads", None)
    return q, k, v


def gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array,
               mask: Optional[jax.Array], *, softmax_scale: float) -> jax.Array:
    """Grouped-query attention core.

    q: (B, Sq, nq, h);  k, v: (B, Sk, nkv, h);  mask: broadcastable to
    (B, nkv, g, Sq, Sk) or None.  Returns (B, Sq, nq, h).
    """
    B, Sq, nq, h = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qg = q.reshape(B, Sq, nkv, g, h)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * softmax_scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, Sq, nq, h)


def causal_mask(Sq: int, Sk: int, offset: int = 0) -> jax.Array:
    """(1, 1, 1, Sq, Sk) causal mask; offset = #cached tokens before q."""
    qpos = jnp.arange(Sq)[:, None] + offset
    kpos = jnp.arange(Sk)[None, :]
    return (kpos <= qpos)[None, None, None]


CHUNKED_ATTN_THRESHOLD = 2048     # switch to O(S·BQ) attention above this


def chunked_gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, softmax_scale: float,
                       q_chunk: int = 512) -> jax.Array:
    """Memory-efficient attention: lax.scan over query blocks.

    The plain einsum path materializes (B, nkv, g, Sq, Sk) scores —
    quadratic; at 32 k context that is PBs.  Scanning query blocks keeps
    only (B, nkv, g, BQ, Sk) live (the XLA analogue of flash attention's
    outer loop; the Pallas kernel additionally blocks the k axis in VMEM).

    Numerics match gqa_attend exactly (f32 softmax over the full key
    axis).  §Perf iterations 4/5/5b tried q_chunk=1024, bf16
    probabilities, and hand-staged softmax (pre-scaled q, post-PV
    normalization) — all REFUTED on the lowered-IR byte accounting:
    XLA's recognized softmax pattern fuses better than hand staging, and
    bf16 probabilities just add converts under CPU legalization.  The
    reduced-precision-probability trade lives where it belongs, in the
    Pallas flash kernel (repro.kernels.flash_attention).
    """
    B, Sq, nq, h = q.shape
    nkv, Sk = k.shape[2], k.shape[1]
    g = nq // nkv
    BQ = min(q_chunk, Sq)
    while Sq % BQ:
        BQ -= 1
    nQ = Sq // BQ
    qg = q.reshape(B, nQ, BQ, nkv, g, h)
    kf, vf = k, v

    def chunk(qi, blk):                               # blk: (B,BQ,nkv,g,h)
        scores = jnp.einsum("bskgh,btkh->bkgst", blk, kf,
                            preferred_element_type=jnp.float32) * softmax_scale
        if causal:
            rows = qi * BQ + jnp.arange(BQ)[:, None]
            cols = jnp.arange(Sk)[None, :]
            scores = jnp.where((cols <= rows)[None, None, None],
                               scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgst,btkh->bskgh", w, vf)

    # inner remat: without it the scan's backward saves softmax(scores) for
    # every chunk — re-materializing the full quadratic matrix it exists to
    # avoid.  Recomputing scores per chunk in backward is the flash-
    # attention trade (+1 matmul) and keeps peak memory O(S·BQ).
    chunk = jax.checkpoint(chunk,
                           policy=jax.checkpoint_policies.nothing_saveable)

    def body(_, args):
        qi, blk = args
        return None, chunk(qi, blk)

    _, outs = jax.lax.scan(body, None,
                           (jnp.arange(nQ), jnp.moveaxis(qg, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1)                    # (B,nQ,BQ,nkv,g,h)
    return out.reshape(B, Sq, nq, h)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
           softmax_scale: float) -> jax.Array:
    """Quadratic einsum path below the threshold, chunked scan above."""
    Sq, Sk = q.shape[1], k.shape[1]
    if max(Sq, Sk) > CHUNKED_ATTN_THRESHOLD:
        return chunked_gqa_attend(q, k, v, causal=causal,
                                  softmax_scale=softmax_scale)
    mask = causal_mask(Sq, Sk) if causal else None
    return gqa_attend(q, k, v, mask, softmax_scale=softmax_scale)


def attention_train(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
                    *, causal: bool = True,
                    softmax_scale: Optional[float] = None) -> jax.Array:
    """x may be wider than d_model (see :func:`attention_specs`); the
    softmax scale defaults to head_dim ** -0.5."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = qkv(cfg, p, x, positions)
    scale = softmax_scale or cfg.resolved_head_dim() ** -0.5
    if cfg.use_kernels:
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=causal, softmax_scale=scale)
    else:
        out = attend(q, k, v, causal=causal, softmax_scale=scale)
    out = logical_constraint(out, "batch", "seq", "heads", None)
    return out.reshape(B, S, -1) @ wo_matrix(p)


def cross_attention_train(cfg: ArchConfig, p: Dict[str, jax.Array],
                          x: jax.Array, kv_src: jax.Array) -> jax.Array:
    """Decoder cross-attention over encoder output (no rotary, no mask)."""
    B, S, _ = x.shape
    T = kv_src.shape[1]
    h = cfg.resolved_head_dim()
    wq = weight_constraint(p["wq"], "embed", "q_features")
    wk = weight_constraint(p["wk"], "embed", "kv_features")
    wv = weight_constraint(p["wv"], "embed", "kv_features")
    q = (x @ wq).reshape(B, S, cfg.n_heads, h)
    k = (kv_src @ wk).reshape(B, T, cfg.n_kv_heads, h)
    v = (kv_src @ wv).reshape(B, T, cfg.n_kv_heads, h)
    out = gqa_attend(q, k, v, None, softmax_scale=h ** -0.5)
    return out.reshape(B, S, -1) @ wo_matrix(p)


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # (B, S_max, n_kv, h)
    v: jax.Array          # (B, S_max, n_kv, h)
    length: jax.Array     # (B,) int32 — tokens already cached


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, n_layers: int,
                  dtype) -> KVCache:
    h = cfg.resolved_head_dim()
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, h)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((batch,), jnp.int32))


def kv_cache_specs(cfg: ArchConfig, batch: int, max_len: int, n_layers: int,
                   dtype) -> KVCache:
    """Abstract cache (dry-run serve_step input)."""
    h = cfg.resolved_head_dim()
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, h)
    return KVCache(jax.ShapeDtypeStruct(shape, dtype),
                   jax.ShapeDtypeStruct(shape, dtype),
                   jax.ShapeDtypeStruct((batch,), jnp.int32))


def attention_decode(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *,
                     softmax_scale: Optional[float] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: (B, 1, D_in); caches (B, S_max, n_kv, h).

    Returns (out (B,1,D), new_k_cache, new_v_cache).
    """
    B, one, _ = x.shape
    S_max = k_cache.shape[1]
    positions = lengths[:, None]                                    # (B, 1)
    q, k, v = qkv(cfg, p, x, positions)
    # scatter the new kv at position `lengths` per batch row
    onehot = jax.nn.one_hot(lengths, S_max, dtype=k.dtype)          # (B, S_max)
    k_cache = k_cache * (1 - onehot)[..., None, None] + onehot[..., None, None] * k
    v_cache = v_cache * (1 - onehot)[..., None, None] + onehot[..., None, None] * v
    k_cache = logical_constraint(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = logical_constraint(v_cache, "batch", "kv_seq", "kv_heads", None)
    valid = (jnp.arange(S_max)[None, :] <= lengths[:, None])        # (B, S_max)
    mask = valid[:, None, None, None, :]                            # b k g s t
    out = gqa_attend(q, k_cache, v_cache, mask,
                     softmax_scale=softmax_scale
                     or cfg.resolved_head_dim() ** -0.5)
    out = out.reshape(B, one, -1) @ wo_matrix(p)
    return out, k_cache, v_cache
