"""Plain reference of mamba2-130m's training step, in jax.numpy.

Written from the Mamba-2 paper (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060, sections 6-7) and imports nothing of the program under
test.  It is straightforward and slow on purpose: one sequence at a time,
float32, ``jax.default_matmul_precision("highest")``, and the SSD layer
as its block decomposition (quadratic inside a chunk, a sequential scan
of states across chunks).

Parameters are the nested dict the program's train state carries, layers
stacked on a leading axis (L layers, d model width, H heads of P
channels, N state size, W conv width, V vocabulary)::

    embed/embedding (V, d)              input embedding, tied output head
    layers/norm (L, d)                  pre-norm, RMSNorm scale 1 + w
    layers/ssd/w_z, w_x (L, d, H, P)    gate and input projections
    layers/ssd/w_B, w_C (L, d, N)       input-dependent B and C (one group)
    layers/ssd/w_dt (L, d, H)           step size
    layers/ssd/conv_{x,B,C}_{w,b}       depthwise causal conv on x, B, C
    layers/ssd/dt_bias, A_log, D (L, H)
    layers/ssd/gate_norm (L, H, P)      gated RMSNorm over the H*P channels
    layers/ssd/out_proj (L, H, P, d)
    final_norm (d,)

Training is next-token cross-entropy averaged over every position, then
AdamW with global-norm clipping and a warmup-cosine learning rate; the
optimizer's constants come from the traffic file.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REFERENCE_CHUNK = 128          # SSD block length of the reference
LOSS_CHUNK = 1024              # positions per cross-entropy block


# -- weights -----------------------------------------------------------

def _leaf_name(path) -> List[str]:
    return [str(getattr(k, "name", getattr(k, "key", k))) for k in path]


DT_BIAS = -4.0                 # step size softplus(-4) = 0.018 at z = 0


def _init_leaf(names: Sequence[str], shape, dtype, key) -> jax.Array:
    """Initialisation by parameter name: fan-in scaled normal projections,
    embedding 0.02 normal, conv taps 0.5 normal, zero-centred norms and
    biases, D = 1, A = 1 and a step-size bias of ``DT_BIAS`` (A and dt
    at the low end of Mamba-2's published A in [1, 16] and dt in
    [1e-3, 1e-1]).

    Larger decays make the program's gradients NaN: its SSD layer
    exponentiates the masked, positive half of the intra-chunk decay
    before masking it, which overflows float32 once a chunk's decay
    passes about 88 (PERF.md, Open questions).  Mamba-2's published
    draws do that, and so does the repository's own A = 1 with a zero
    bias (dt about 0.7) on some seeds; these weights keep every chunk's
    decay far below it."""
    name = names[-1]
    if name == "embedding":
        return (0.02 * jax.random.normal(key, shape)).astype(dtype)
    if name == "dt_bias":
        return jnp.full(shape, DT_BIAS, dtype)
    if name in ("norm", "final_norm", "gate_norm", "A_log") \
            or name.endswith("_b"):
        return jnp.zeros(shape, dtype)
    if name == "D":
        return jnp.ones(shape, dtype)
    if name.startswith("conv_"):
        return (0.5 * jax.random.normal(key, shape)).astype(dtype)
    if name == "out_proj":                       # (L, H, P, d)
        fan_in = shape[1] * shape[2]
    else:                                        # (L, d, ...) projections
        fan_in = shape[1]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def make_state(abstract_state, seed: int):
    """The train state, made on the device in one jitted call from
    ``seed``: parameters by name as above, every other leaf (optimizer
    moments, step counters) zero.  ``abstract_state`` gives the layout."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for (path, leaf), k in zip(flat, keys):
            names = _leaf_name(path)
            if names[0] == "params":
                leaves.append(_init_leaf(names, leaf.shape, leaf.dtype, k))
            else:
                leaves.append(jnp.zeros(leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(jax.random.key(seed))


# -- forward -----------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def causal_conv(x, w, b):
    """Depthwise causal convolution along positions, then SiLU.
    x: (S, ...C), w: (W, ...C)."""
    width, seq = w.shape[0], x.shape[0]
    pad = jnp.pad(x, [(width - 1, 0)] + [(0, 0)] * (x.ndim - 1))
    out = sum(pad[i:i + seq] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def mm(spec: str, *operands, q=None):
    """A matrix product (einsum) in float32; with ``q`` a dtype, each
    operand is first rounded to it (the control's lower precision)."""
    if q is not None:
        operands = [o.astype(q).astype(jnp.float32) for o in operands]
    return jnp.einsum(spec, *operands)


def ssd(x, dt, A, B, C, chunk: int, q=None):
    """y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s.

    x: (S, H, P), dt: (S, H), A: (H,), B, C: (S, N)."""
    S, H, P = x.shape
    Q = min(chunk, S)
    if S % Q:
        raise ValueError(f"sequence {S} is not a multiple of chunk {Q}")
    nc = S // Q
    xc, dtc = x.reshape(nc, Q, H, P), dt.reshape(nc, Q, H)
    Bc, Cc = B.reshape(nc, Q, -1), C.reshape(nc, Q, -1)
    cs = jnp.cumsum(dtc * A, axis=1)                      # (nc, Q, H)
    # inside a chunk: decay from s to t is exp(cs_t - cs_s), t >= s
    seg = cs[:, :, None, :] - cs[:, None, :, :]           # (nc, t, s, H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = mm("ctn,csn->cts", Cc, Bc, q=q)
    y = mm("cts,ctsh,csh,cshp->cthp", scores, decay, dtc, xc, q=q)
    # each chunk's final state, then carried across chunks in order
    to_end = jnp.exp(cs[:, -1:, :] - cs)                  # (nc, Q, H)
    states = mm("csn,csh,csh,cshp->chnp", Bc, to_end, dtc, xc, q=q)
    chunk_decay = jnp.exp(cs[:, -1, :])                   # (nc, H)

    def carry(h, inp):
        st, dec = inp
        return h * dec[:, None, None] + st, h

    h0 = jnp.zeros(states.shape[1:], states.dtype)
    _, h_in = jax.lax.scan(carry, h0, (states, chunk_decay))
    y = y + mm("ctn,cth,chnp->cthp", Cc, jnp.exp(cs), h_in, q=q)
    return y.reshape(S, H, P)


def block(arch: Dict[str, Any], p, x, q=None):
    """One residual Mamba-2 layer on one sequence, x: (S, d)."""
    eps = arch["norm_eps"]
    s = p["ssd"]
    h = rms_norm(x, p["norm"], eps)
    z = mm("sd,dhp->shp", h, s["w_z"], q=q)
    xr = mm("sd,dhp->shp", h, s["w_x"], q=q)
    xh = causal_conv(xr, s["conv_x_w"], s["conv_x_b"])
    Bm = causal_conv(mm("sd,dn->sn", h, s["w_B"], q=q), s["conv_B_w"],
                     s["conv_B_b"])
    Cm = causal_conv(mm("sd,dn->sn", h, s["w_C"], q=q), s["conv_C_w"],
                     s["conv_C_b"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, s["w_dt"], q=q) + s["dt_bias"])
    A = -jnp.exp(s["A_log"])
    y = ssd(xh, dt, A, Bm, Cm, REFERENCE_CHUNK, q) + s["D"][:, None] * xh
    y = y * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=(-2, -1), keepdims=True)
                          + eps) * (1.0 + s["gate_norm"])
    return x + mm("shp,hpd->sd", y, s["out_proj"], q=q)


def row_loss_sum(arch: Dict[str, Any], p, row, q=None):
    """Summed next-token cross-entropy of one sequence (S + 1 tokens);
    ``q`` rounds every matrix product's operands (see :func:`mm`)."""
    inputs, labels = row[:-1], row[1:]
    emb = p["embed"]["embedding"]
    x = emb[inputs]

    def layer(x, lp):
        return jax.checkpoint(lambda x, lp: block(arch, lp, x, q))(x, lp), \
            None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = rms_norm(x, p["final_norm"], arch["norm_eps"])
    S, d = x.shape
    c = min(LOSS_CHUNK, S)

    def ce(total, xs):
        hc, yc = xs
        logits = mm("cd,vd->cv", hc, emb, q=q)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(jax.checkpoint(ce), jnp.float32(0.0),
                            (x.reshape(S // c, c, d),
                             labels.reshape(S // c, c)))
    return total


# -- training ----------------------------------------------------------

def lr_at(step: int, opt: Dict[str, Any]) -> float:
    """Warmup-cosine learning rate at 0-based ``step``."""
    warm = opt["warmup_steps"]
    if step < warm:
        return opt["learning_rate"] * step / max(warm, 1)
    frac = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0),
               1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    f = opt["final_lr_frac"]
    return opt["learning_rate"] * (f + (1.0 - f) * cos)


def train_steps(arch: Dict[str, Any], opt: Dict[str, Any], params0,
                batches: Sequence[np.ndarray], matmul_dtype=None,
                rows: int = 0):
    """AdamW steps from ``params0`` (host arrays) over ``batches`` of
    token rows, in float32 at the highest matmul precision; with
    ``matmul_dtype``, every matrix product's operands are first rounded
    to it.  ``rows`` > 0 keeps only the first ``rows`` rows of every
    batch.

    Returns (losses, the first step's clipped gradient, the parameters
    after the last step), the last two as host arrays."""
    with jax.default_matmul_precision("highest"):
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, r: row_loss_sum(arch, p, r, matmul_dtype)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        update = jax.jit(_adamw, static_argnames=("opt",))
        params = jax.device_put(params0)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, g_first = [], None
        for step, batch in enumerate(batches):
            batch = batch[:rows] if rows else batch
            total, grads = 0.0, None
            for row in batch:
                loss, g = grad_row(params, jnp.asarray(row))
                total += float(loss)
                grads = g if grads is None else add(grads, g)
            count = batch.shape[0] * (batch.shape[1] - 1)
            losses.append(total / count)
            params, mu, nu, clipped = update(
                params, grads, mu, nu, jnp.float32(1.0 / count),
                jnp.float32(lr_at(step, opt)), jnp.int32(step + 1),
                opt=_frozen(opt))
            if g_first is None:
                g_first = jax.device_get(clipped)
        return losses, g_first, jax.device_get(params)


def _frozen(opt: Dict[str, Any]) -> Tuple:
    return tuple(sorted((k, v) for k, v in opt.items()
                        if isinstance(v, (int, float))))


def _adamw(params, grad_sums, mu, nu, inv_count, lr, t, *, opt):
    o = dict(opt)
    grads = jax.tree.map(lambda g: g * inv_count, grad_sums)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["max_grad_norm"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = o["b1"], o["b2"]
    tf = t.astype(jnp.float32)
    bc1, bc2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf
    mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
                                  + o["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, grads


# -- model FLOPs ---------------------------------------------------------

def model_flops_per_token(arch: Dict[str, Any]) -> float:
    """Operations per token of one training step: the forward pass's
    projections, convolution, state-space recurrence (decay, input and
    readout over H*N*P state) and output head, times three for the
    forward and backward passes.  Recomputation does not count."""
    d, N, L = arch["d_model"], arch["ssm_state"], arch["n_layers"]
    di = arch["ssm_expand"] * d
    H = di // arch["ssm_head_dim"]
    proj = 2 * d * (2 * di + 2 * N + H) + 2 * di * d
    conv = 2 * arch["conv_width"] * (di + 2 * N)
    ssm = 6 * di * N
    head = 2 * d * arch["vocab_size"]
    return 3.0 * (L * (proj + conv + ssm) + head)
