"""Mamba2 / SSD (state-space duality) block — chunked scan + one-step decode.

Selective state space:  h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t (x) x_t,
y_t = C_t . h_t + D * x_t, with per-head scalar A (Mamba2's SSD restriction).

Training uses the chunked SSD algorithm (arXiv:2405.21060 §6): intra-chunk
"attention-like" term + inter-chunk state recurrence via associative scan —
sub-quadratic in sequence length and SP-friendly.  Decode carries
(conv_state, ssm_state) and is O(1) per token regardless of history length,
which is why the long_500k shape is assigned to the SSM/hybrid families.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.axes import logical_constraint, weight_constraint
from repro.models.layers import rms_norm
from repro.models.params import P


def ssd_block_specs(cfg: ArchConfig) -> Dict[str, P]:
    """Split, layout-native projections (§Perf mamba2 iteration 1).

    A packed in_proj (d, 2di+2N+H) sharded on 'model' forced GSPMD to
    halo-exchange every shard-misaligned slice (z/x/B/C/dt split, head
    reshape): 1128 collective-permutes + 24 AGs per prefill on the 16x16
    mesh.  Separate per-stream weights — with the x streams as 3-D
    (d, H, P) tensors — produce every activation directly in its sharded
    layout: no slicing or reshaping of sharded dims at all."""
    d, n, hh, pd = cfg.d_model, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    cw = cfg.conv_width
    return {
        "w_z": P((d, hh, pd), ("embed", "ssm_heads", "ssm_pdim")),
        "w_x": P((d, hh, pd), ("embed", "ssm_heads", "ssm_pdim")),
        "w_B": P((d, n), ("embed", "state")),
        "w_C": P((d, n), ("embed", "state")),
        "w_dt": P((d, hh), ("embed", "ssm_heads")),
        "conv_x_w": P((cw, hh, pd), ("conv", "ssm_heads", "ssm_pdim"),
                      scale=0.5),
        "conv_x_b": P((hh, pd), ("ssm_heads", "ssm_pdim"), init="zeros"),
        "conv_B_w": P((cw, n), ("conv", "state"), scale=0.5),
        "conv_B_b": P((n,), ("state",), init="zeros"),
        "conv_C_w": P((cw, n), ("conv", "state"), scale=0.5),
        "conv_C_b": P((n,), ("state",), init="zeros"),
        "dt_bias": P((hh,), ("ssm_heads",), init="zeros"),
        "A_log": P((hh,), ("ssm_heads",), init="zeros"),
        "D": P((hh,), ("ssm_heads",), init="ones"),
        "gate_norm": P((hh, pd), ("ssm_heads", "ssm_pdim"), init="zeros"),
        "out_proj": P((hh, pd, d), ("ssm_heads", "ssm_pdim", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along seq. x: (B,S,...C), w: (W,...C)."""
    W = w.shape[0]
    S = x.shape[1]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    out = jnp.zeros_like(x)
    for i in range(W):                      # W is tiny (4): unrolled taps
        out = out + pad[:, i:i + S] * w[i]
    return jax.nn.silu(out + b)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, chunk: int, return_final: bool = False):
    """Chunked SSD core (the pure-jnp oracle for the Pallas kernel).

    x: (B,S,H,P)  dt: (B,S,H) (already softplus'ed)  A: (H,) negative
    Bm, Cm: (B,S,N) (single group, broadcast over heads)
    Returns y: (B,S,H,P).
    """
    Bsz, S, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    S0 = S
    if S % Q:                       # pad with dt=0 steps: state-neutral
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q

    xc = x.reshape(Bsz, nc, Q, H, Pd)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    dA = dtc * A                                             # (B,nc,Q,H)
    s = jnp.cumsum(dA, axis=2)                               # inclusive cumsum
    # intra-chunk: Y[i] = sum_{j<=i} exp(s_i - s_j) dt_j (C_i.B_j) x_j
    CB = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)               # (B,nc,Q,Q)
    L = s[:, :, :, None, :] - s[:, :, None, :, :]            # s_i - s_j (B,nc,Q,Q,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # mask the exponent, not its value: above the diagonal s_i - s_j > 0
    # grows with the chunk's decay, and exp of it overflows float32 (a 0
    # times inf in the gradient) at Mamba-2's published A and dt draws
    L = jnp.exp(jnp.where(causal[None, None, :, :, None], L, -jnp.inf))
    M = CB[..., None] * L * dtc[:, :, None, :, :]            # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", M, xc)

    # chunk states: St_c = sum_j exp(s_Q - s_j) dt_j B_j (x) x_j  -> (B,nc,H,N,P)
    # s is non-increasing along the chunk (dt >= 0, A < 0): this and the
    # other exponents below are <= 0 and cannot overflow
    decay_to_end = jnp.exp(s[:, :, -1:, :] - s)              # (B,nc,Q,H)
    st = jnp.einsum("bcqh,bcqn,bcqhp->bchnp",
                    decay_to_end * dtc, Bc, xc)

    # inter-chunk recurrence over nc: h_c = a_c h_{c-1} + st_c
    a = jnp.exp(s[:, :, -1, :])                              # (B,nc,H)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, b1 * a2[..., None, None] + b2

    a_sc, h_sc = jax.lax.associative_scan(combine, (a, st), axis=1)
    # state entering chunk c = h_{c-1} (zeros for c=0)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h_sc[:, :1]), h_sc[:, :-1]], axis=1)  # (B,nc,H,N,P)

    # inter-chunk output: Y[i] += C_i . (exp(s_i) h_prev)
    y_inter = jnp.einsum("bcqn,bcqh,bchnp->bcqhp",
                         Cc, jnp.exp(s), h_prev)
    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)[:, :S0]
    if return_final:
        return y, h_sc[:, -1]                                # (B,H,N,P)
    return y


class SSMState(NamedTuple):
    conv: jax.Array    # (B, conv_width-1, d_inner + 2N) rolling conv input
    h: jax.Array       # (B, H, N, P) ssm state


def init_ssm_state(cfg: ArchConfig, batch: int, n_layers: int, dtype) -> SSMState:
    di, n = cfg.d_inner, cfg.ssm_state
    return SSMState(
        jnp.zeros((n_layers, batch, cfg.conv_width - 1, di + 2 * n), dtype),
        jnp.zeros((n_layers, batch, cfg.ssm_heads, n, cfg.ssm_head_dim),
                  jnp.float32),
    )


def ssm_state_specs(cfg: ArchConfig, batch: int, n_layers: int, dtype) -> SSMState:
    di, n = cfg.d_inner, cfg.ssm_state
    return SSMState(
        jax.ShapeDtypeStruct((n_layers, batch, cfg.conv_width - 1, di + 2 * n),
                             dtype),
        jax.ShapeDtypeStruct((n_layers, batch, cfg.ssm_heads, n,
                              cfg.ssm_head_dim), jnp.float32),
    )


def _rms_norm_hp(y: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMS norm over the flattened (H, P) feature dims. y: (B,S,H,P)."""
    dt = y.dtype
    y32 = y.astype(jnp.float32)
    ms = jnp.mean(jnp.square(y32), axis=(-2, -1), keepdims=True)
    y32 = y32 * jax.lax.rsqrt(ms + eps)
    return (y32 * (1.0 + w.astype(jnp.float32))).astype(dt)


def ssd_block_train(cfg: ArchConfig, p: Dict[str, jax.Array],
                    x: jax.Array, return_state: bool = False):
    """Full Mamba2 block, training/prefill path. x: (B,S,D) -> (B,S,D).

    With ``return_state`` also returns (conv_state, ssm_state) at sequence
    end so prefill can hand off to O(1) decode.  All streams are computed
    in their final sharded layout (see ssd_block_specs).
    """
    B, S, _ = x.shape
    di, n, hh, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    w_z = weight_constraint(p["w_z"], "embed", "ssm_heads", "ssm_pdim")
    w_x = weight_constraint(p["w_x"], "embed", "ssm_heads", "ssm_pdim")
    z = jnp.einsum("bsd,dhp->bshp", x, w_z)
    x_raw = jnp.einsum("bsd,dhp->bshp", x, w_x)              # (B,S,H,P)
    B_raw = x @ weight_constraint(p["w_B"], "embed", "state")
    C_raw = x @ weight_constraint(p["w_C"], "embed", "state")
    dt = x @ weight_constraint(p["w_dt"], "embed", "ssm_heads")
    x_raw = logical_constraint(x_raw, "batch", "seq", "ssm_heads",
                               "ssm_pdim")
    xh = _causal_conv(x_raw, p["conv_x_w"], p["conv_x_b"])
    Bm = _causal_conv(B_raw, p["conv_B_w"], p["conv_B_b"])
    Cm = _causal_conv(C_raw, p["conv_C_w"], p["conv_C_b"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if cfg.use_kernels:
        from repro.kernels.ssd_scan.ops import ssd_scan
        y, h_final = ssd_scan(xh.astype(jnp.float32), dt, A,
                              Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                              chunk=cfg.ssm_chunk, return_final=True)
    else:
        y, h_final = ssd_chunked(xh.astype(jnp.float32), dt, A,
                                 Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                                 cfg.ssm_chunk, return_final=True)
    y = y + p["D"][None, None, :, None].astype(jnp.float32) \
        * xh.astype(jnp.float32)
    y = y.astype(x.dtype)
    y = _rms_norm_hp(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    w_out = weight_constraint(p["out_proj"], "ssm_heads", "ssm_pdim", "embed")
    out = jnp.einsum("bshp,hpd->bsd", y, w_out)
    if return_state:
        W = cfg.conv_width
        # decode conv state stays packed [x | B | C] for a stable cache
        # layout (splitting it at decode touches only (B, W-1, C) scraps)
        conv_state = jnp.concatenate(
            [x_raw[:, S - (W - 1):].reshape(B, W - 1, di),
             B_raw[:, S - (W - 1):], C_raw[:, S - (W - 1):]], axis=-1)
        return out, (conv_state, h_final)
    return out


def ssd_block_decode(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array,
                     state: Tuple[jax.Array, jax.Array]
                     ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode. x: (B,1,D); state = (conv (B,W-1,C), h (B,H,N,P)).

    The packed conv state keeps the cache layout stable; the split here
    touches only (B, W-1, C)-sized scraps (negligible at decode)."""
    conv_state, h = state
    B = x.shape[0]
    di, n, hh, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    x0 = x[:, 0]
    z = jnp.einsum("bd,dhp->bhp", x0, p["w_z"])
    x_new = jnp.einsum("bd,dhp->bhp", x0, p["w_x"]).reshape(B, di)
    B_new = x0 @ p["w_B"]
    C_new = x0 @ p["w_C"]
    dt = x0 @ p["w_dt"]
    packed_new = jnp.concatenate([x_new, B_new, C_new], axis=-1)
    # rolling conv window: state holds previous W-1 packed inputs
    window = jnp.concatenate([conv_state, packed_new[:, None, :]],
                             axis=1)                          # (B,W,C)
    new_conv_state = window[:, 1:]
    xw = window[..., :di].reshape(B, -1, hh, pd)              # (B,W,H,P)
    conv_x = jnp.einsum("bwhp,whp->bhp", xw, p["conv_x_w"]) + p["conv_x_b"]
    conv_B = jnp.einsum("bwn,wn->bn", window[..., di:di + n],
                        p["conv_B_w"]) + p["conv_B_b"]
    conv_C = jnp.einsum("bwn,wn->bn", window[..., di + n:],
                        p["conv_C_w"]) + p["conv_C_b"]
    xh = jax.nn.silu(conv_x).astype(jnp.float32)              # (B,H,P)
    Bm = jax.nn.silu(conv_B).astype(jnp.float32)
    Cm = jax.nn.silu(conv_C).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A)                                             # (B,H)
    dBx = jnp.einsum("bh,bn,bhp->bhnp", dt, Bm, xh)
    h = h * dA[..., None, None] + dBx
    y = jnp.einsum("bn,bhnp->bhp", Cm, h) + p["D"][None, :, None] * xh
    y = y.astype(x.dtype)
    y = _rms_norm_hp((y * jax.nn.silu(z))[:, None], p["gate_norm"],
                     cfg.norm_eps)[:, 0]
    out = jnp.einsum("bhp,hpd->bd", y, p["out_proj"])
    return out[:, None, :], (new_conv_state, h)
