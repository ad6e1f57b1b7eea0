"""Zamba2 hybrid: a Mamba2 backbone with shared attention+MLP blocks.

After Zyphra's Zamba2 (arXiv:2411.15242).  Layer ``i`` is a hybrid layer
when ``i % attn_every == attn_every - 1``; the j-th hybrid layer calls
shared block ``j % n_shared_blocks`` (A, B, A, ...).  A shared block reads
the residual stream ``h`` beside the token embedding ``e0``::

    x = RMSNorm(concat(h, e0))               width 2d
    a = RMSNorm(Attention(x))                q/k/v 2d -> heads x 2d/heads,
                                             rotary, scale (hd/2)**-0.5,
                                             o 2d -> d
    m = W_down (gelu(a W_gate + a A_j G_j) * (a W_up + a A_j U_j))
    T = m L_j

``A_j, G_j, U_j`` (rank ``adapter_rank``) and the d -> d linear ``L_j``
belong to the hybrid layer, not to the block.  ``T`` feeds that layer's
Mamba2 block only; the residual keeps ``h``::

    h' = h + Mamba2(RMSNorm(h + T))

Inside the layer scan ``lax.switch`` picks no block or one of them, so the
ScalAna PSG holds a Branch with ``n_shared_blocks + 1`` arms nested in the
layer Loop, the control structure the paper's backtracking walks through.
Every op of a shared-block call is traced under the named scope
``hybrid.shared_block`` (``repro.core.spans.SCOPES``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.spans import scope
from repro.distributed.axes import logical_constraint, weight_constraint
from repro.models import attention as attn
from repro.models import mamba2
from repro.models.layers import (
    chunked_cross_entropy,
    embed_specs,
    embed_tokens,
    logits_for,
    mlp_specs,
    rms_norm,
)
from repro.models.params import P, Specs
from repro.models.transformer import stack_specs


def hybrid_specs(cfg: ArchConfig) -> Specs:
    d, r = cfg.d_model, cfg.adapter_rank
    mamba_layer = {
        "norm": P((d,), ("embed",), init="zeros"),
        "ssd": mamba2.ssd_block_specs(cfg),
    }
    shared = {
        "attn_norm": P((2 * d,), ("embed",), init="zeros"),
        "attn": attn.attention_specs(cfg, d_in=2 * d),
        "mlp_norm": P((d,), ("embed",), init="zeros"),
        "mlp": mlp_specs(cfg),
    }
    per_hybrid = {
        "adapter_in": P((d, r), ("embed", None)),
        "adapter_gate": P((r, cfg.d_ff), (None, "mlp")),
        "adapter_up": P((r, cfg.d_ff), (None, "mlp")),
        "linear": P((d, d), ("embed", None)),
    }
    return {
        "embed": embed_specs(cfg),
        "layers": stack_specs(mamba_layer, cfg.n_layers),
        "shared": stack_specs(shared, cfg.n_shared_blocks),
        "hybrid": stack_specs(per_hybrid, cfg.n_hybrid_layers),
        "final_norm": P((d,), ("embed",), init="zeros"),
    }


def softmax_scale(cfg: ArchConfig) -> float:
    """Zamba2 scales scores by (head_dim / 2) ** -0.5."""
    return (cfg.resolved_head_dim() / 2) ** -0.5


def _select(cfg: ArchConfig, idx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(switch arm, hybrid index) of layer ``idx``: arm 0 runs no block,
    arm 1 + b shared block b."""
    k = cfg.attn_every
    j = idx // k
    arm = jnp.where(idx % k == k - 1, 1 + j % cfg.n_shared_blocks, 0)
    return arm, j


def _take(tree: Dict[str, Any], i) -> Dict[str, Any]:
    """Entry ``i`` (static or traced) of a stacked parameter tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _mlp(cfg: ArchConfig, p: Dict[str, jax.Array], hyb: Dict[str, jax.Array],
         a: jax.Array) -> jax.Array:
    """Gated GELU MLP with the hybrid layer's adapter on gate and up."""
    low = a @ weight_constraint(hyb["adapter_in"], "embed", None)
    gate = a @ weight_constraint(p["w_gate"], "embed", "mlp") \
        + low @ weight_constraint(hyb["adapter_gate"], None, "mlp")
    up = a @ weight_constraint(p["w_up"], "embed", "mlp") \
        + low @ weight_constraint(hyb["adapter_up"], None, "mlp")
    h = jax.nn.gelu(gate, approximate=False) * up
    h = logical_constraint(h, "batch", "seq", "mlp")
    return h @ weight_constraint(p["w_down"], "mlp", "embed")


def _shared_block(cfg: ArchConfig, blk: Dict[str, Any],
                  hyb: Dict[str, jax.Array], h: jax.Array, e0: jax.Array,
                  attend: Callable) -> Tuple[jax.Array, Any]:
    """T(h, e0) of one hybrid layer; ``attend(attn_params, x)`` returns
    the attention output and whatever the caller keeps (a cache)."""
    with scope("hybrid.shared_block"):
        x = rms_norm(jnp.concatenate([h, e0], axis=-1), blk["attn_norm"],
                     cfg.norm_eps)
        a, kept = attend(blk["attn"], x)
        a = rms_norm(a, blk["mlp_norm"], cfg.norm_eps)
        m = _mlp(cfg, blk["mlp"], hyb, a)
        return m @ weight_constraint(hyb["linear"], "embed", None), kept


def backbone_train(cfg: ArchConfig, params: Dict[str, Any],
                   e0: jax.Array) -> jax.Array:
    shared, hybrid = params["shared"], params["hybrid"]
    scale = softmax_scale(cfg)

    def attend(p, x):
        return attn.attention_train(cfg, p, x, softmax_scale=scale), None

    def arm(b):
        def run(x, j):
            t, _ = _shared_block(cfg, _take(shared, b), _take(hybrid, j),
                                 x, e0, attend)
            return x + t
        # remat inside the layer's remat: its backward then holds the
        # shared block's intermediates or the Mamba2 block's, not both
        # (one period, two rows of 4096: 3.26 GB of temporaries, not
        # 7.18, for a recompute of the block)
        return jax.checkpoint(run) if cfg.remat else run

    arms = [lambda x, j: x] + [arm(b) for b in range(cfg.n_shared_blocks)]

    def block(x, layer_params, idx):
        a, j = _select(cfg, idx)
        x_in = jax.lax.switch(a, arms, x, j)
        y = mamba2.ssd_block_train(cfg, layer_params["ssd"],
                                   rms_norm(x_in, layer_params["norm"],
                                            cfg.norm_eps))
        return logical_constraint(x + y, "batch", "res_seq", "embed")

    blk = jax.checkpoint(block) if cfg.remat else block

    def body(carry, xs):
        layer_params, idx = xs
        return blk(carry, layer_params, idx), None

    idxs = jnp.arange(cfg.n_layers)
    h, _ = jax.lax.scan(body, e0, (params["layers"], idxs))
    return rms_norm(h, params["final_norm"], cfg.norm_eps)


def train_loss(cfg: ArchConfig, params: Dict[str, Any],
               batch: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    e0 = embed_tokens(params["embed"], inputs)
    h = backbone_train(cfg, params, e0)
    mask = (labels >= 0).astype(jnp.float32)
    loss_sum, count = chunked_cross_entropy(
        params["embed"], h, jnp.maximum(labels, 0), mask, cfg.loss_chunk)
    loss = loss_sum / jnp.maximum(count, 1.0)
    return loss, {"ce_loss": loss, "loss": loss, "tokens": count}


# ---------------------------------------------------------------------------
# Serving: per-hybrid-layer KV caches beside the stacked SSM state
# ---------------------------------------------------------------------------

class HybridCache(NamedTuple):
    ssm: mamba2.SSMState          # stacked (L, ...)
    k: jax.Array                  # (hybrid layers, B, S_max, n_kv, h)
    v: jax.Array
    length: jax.Array             # (B,)


def _kv_shape(cfg: ArchConfig, batch: int, max_len: int):
    return (cfg.n_hybrid_layers, batch, max_len, cfg.n_kv_heads,
            cfg.resolved_head_dim())


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> HybridCache:
    kv_shape = _kv_shape(cfg, batch, max_len)
    return HybridCache(
        mamba2.init_ssm_state(cfg, batch, cfg.n_layers, dtype),
        jnp.zeros(kv_shape, dtype), jnp.zeros(kv_shape, dtype),
        jnp.zeros((batch,), jnp.int32),
    )


def cache_specs(cfg: ArchConfig, batch: int, max_len: int, dtype) -> HybridCache:
    kv_shape = _kv_shape(cfg, batch, max_len)
    return HybridCache(
        mamba2.ssm_state_specs(cfg, batch, cfg.n_layers, dtype),
        jax.ShapeDtypeStruct(kv_shape, dtype),
        jax.ShapeDtypeStruct(kv_shape, dtype),
        jax.ShapeDtypeStruct((batch,), jnp.int32),
    )


def _kv_arms(cfg: ArchConfig, params: Dict[str, Any], e0: jax.Array,
             attend: Callable) -> list:
    """Switch arms over (x, kbuf, vbuf, j): arm 0 passes x through, arm
    1 + b runs shared block b and writes hybrid layer j's cache entry."""
    shared, hybrid = params["shared"], params["hybrid"]

    def arm(b):
        def run(x, kbuf, vbuf, j):
            kv = (jax.lax.dynamic_index_in_dim(kbuf, j, 0, keepdims=False),
                  jax.lax.dynamic_index_in_dim(vbuf, j, 0, keepdims=False))
            t, (k, v) = _shared_block(
                cfg, _take(shared, b), _take(hybrid, j), x, e0,
                lambda p, xn: attend(p, xn, kv))
            kbuf = jax.lax.dynamic_update_index_in_dim(kbuf, k, j, 0)
            vbuf = jax.lax.dynamic_update_index_in_dim(vbuf, v, j, 0)
            return x + t, kbuf, vbuf
        return run

    return [lambda x, kbuf, vbuf, j: (x, kbuf, vbuf)] + [
        arm(b) for b in range(cfg.n_shared_blocks)]


def decode_step(cfg: ArchConfig, params: Dict[str, Any], cache: HybridCache,
                tokens: jax.Array) -> Tuple[jax.Array, HybridCache]:
    e0 = embed_tokens(params["embed"], tokens)
    scale = softmax_scale(cfg)

    def attend(p, xn, kv):
        out, k, v = attn.attention_decode(cfg, p, xn, kv[0], kv[1],
                                          cache.length, softmax_scale=scale)
        return out, (k, v)

    arms = _kv_arms(cfg, params, e0, attend)

    def body(carry, xs):
        x, kc, vc = carry
        layer_params, conv_s, ssm_h, idx = xs
        a, j = _select(cfg, idx)
        x_in, kc, vc = jax.lax.switch(a, arms, x, kc, vc, j)
        y, (conv_s, ssm_h) = mamba2.ssd_block_decode(
            cfg, layer_params["ssd"],
            rms_norm(x_in, layer_params["norm"], cfg.norm_eps),
            (conv_s, ssm_h))
        return (x + y, kc, vc), (conv_s, ssm_h)

    idxs = jnp.arange(cfg.n_layers)
    (h, kc, vc), (conv_s, ssm_h) = jax.lax.scan(
        body, (e0, cache.k, cache.v),
        (params["layers"], cache.ssm.conv, cache.ssm.h, idxs))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_for(params["embed"], h)
    new_cache = HybridCache(mamba2.SSMState(conv_s, ssm_h), kc, vc,
                            cache.length + 1)
    return logits, new_cache


def prefill(cfg: ArchConfig, params: Dict[str, Any],
            batch: Dict[str, jax.Array], max_len: int
            ) -> Tuple[jax.Array, HybridCache]:
    """Chunked prefill: SSD chunk scan per layer, hybrid layers' KV kept."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    e0 = embed_tokens(params["embed"], tokens)
    scale = softmax_scale(cfg)
    positions = jnp.arange(S)[None, :]
    pad = ((0, 0), (0, max_len - S), (0, 0), (0, 0))

    def attend(p, xn, _kv):
        q, k, v = attn.qkv(cfg, p, xn, positions)
        o = attn.attend(q, k, v, causal=True, softmax_scale=scale)
        out = o.reshape(B, S, -1) @ attn.wo_matrix(p)
        return out, (jnp.pad(k, pad), jnp.pad(v, pad))

    arms = _kv_arms(cfg, params, e0, attend)
    kbuf = jnp.zeros(_kv_shape(cfg, B, max_len), e0.dtype)
    vbuf = jnp.zeros_like(kbuf)

    def body(carry, xs):
        x, kbuf, vbuf = carry
        layer_params, idx = xs
        a, j = _select(cfg, idx)
        x_in, kbuf, vbuf = jax.lax.switch(a, arms, x, kbuf, vbuf, j)
        y, (conv_s, ssm_h) = mamba2.ssd_block_train(
            cfg, layer_params["ssd"],
            rms_norm(x_in, layer_params["norm"], cfg.norm_eps),
            return_state=True)
        return (x + y, kbuf, vbuf), (conv_s, ssm_h)

    idxs = jnp.arange(cfg.n_layers)
    (hx, kbuf, vbuf), (conv_s, ssm_h) = jax.lax.scan(
        body, (e0, kbuf, vbuf), (params["layers"], idxs))
    hx = rms_norm(hx, params["final_norm"], cfg.norm_eps)
    logits = logits_for(params["embed"], hx[:, -1:, :])
    lengths = jnp.full((B,), S, jnp.int32)
    cache = HybridCache(mamba2.SSMState(conv_s, ssm_h), kbuf, vbuf, lengths)
    return logits, cache
