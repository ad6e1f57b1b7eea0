"""Plain reference of zamba2-2.7b's training step, in jax.numpy.

Written from the Zamba2 technical report (Glorioso et al., arXiv:2411.15242)
and the published Zamba2 configuration (hybrid layer every sixth, two
shared blocks used in turn, adapter rank 128, attention over the
concatenated embedding), with the Mamba-2 layer from Dao and Gu
(arXiv:2405.21060).  It imports nothing of the program under test.  It is
straightforward and slow on purpose: one sequence at a time, float32,
``jax.default_matmul_precision("highest")``, a Python loop over the
layers, attention one block of queries at a time, and the SSD layer as
its block decomposition (quadratic inside a chunk, a sequential scan of
states across chunks).

One layer ``i`` on one sequence (``h`` the residual stream, ``e0`` the
token embedding, k = ``attn_every``)::

    x_in = h                                   if i % k != k - 1
    x_in = h + T_j(h, e0)  (j = i // k)        otherwise, block j % n_blocks
    h    = h + Mamba2(RMSNorm(x_in))

    T_j(h, e0) = (W_down (gelu(a Wg + a A_j G_j) * (a Wu + a A_j U_j))) L_j
    a = RMSNorm(Attn(RMSNorm(concat(h, e0))))

Attention: 2d -> heads x (2d / heads) for q, k and v, rotary on the whole
head (theta 10000), causal, scores scaled by (head_dim / 2) ** -0.5, o
2d -> d.  GELU is the exact (erf) form.

Parameters are the nested dict the program's train state carries (L
layers, B shared blocks, J hybrid layers)::

    embed/embedding (V, d)                  input embedding, tied output head
    layers/norm (L, d), layers/ssd/...      Mamba-2 layers, as mamba2-130m's
    shared/attn_norm (B, 2d), shared/mlp_norm (B, d)
    shared/attn/wq, wk, wv (B, 2d, 2d), wo (B, 2d, d)
    shared/mlp/w_gate, w_up (B, d, f), w_down (B, f, d)
    hybrid/adapter_in (J, d, r), adapter_gate, adapter_up (J, r, f)
    hybrid/linear (J, d, d)
    final_norm (d,)

Departures from the published model, each shared with the program: every
RMSNorm scale is stored as ``w`` and applied as ``1 + w``; the gate and up
halves of the published ``gate_up_proj`` (and of its adapter's second
factor) are two matrices; one B/C group in the Mamba-2 layers; no dt
clamp; random weights from the seed (below).

Training is next-token cross-entropy averaged over every position, then
AdamW with global-norm clipping and a warmup-cosine learning rate; the
optimizer's constants come from the traffic file.
"""
from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REFERENCE_CHUNK = 128          # SSD block length of the reference
LOSS_CHUNK = 1024              # positions per cross-entropy block
QUERY_BLOCK = 512              # queries per attention block

A_RANGE = (1.0, 16.0)          # Mamba-2's draw of A
DT_RANGE = (1e-3, 1e-1)        # Mamba-2's log-uniform draw of dt
DT_FLOOR = 1e-4


# -- weights -----------------------------------------------------------

def _leaf_name(path) -> List[str]:
    return [str(getattr(k, "name", getattr(k, "key", k))) for k in path]


def _init_leaf(names: Sequence[str], shape, dtype, key) -> jax.Array:
    """Initialisation by parameter name: Mamba-2's published draws of A
    (uniform in [1, 16]) and dt (log-uniform in [1e-3, 1e-1], floor 1e-4,
    stored as its inverse softplus), D = 1, fan-in scaled normal
    projections and adapters, embedding 0.02 normal, conv taps 0.5
    normal, zero-centred norms and biases."""
    name = names[-1]
    if name == "embedding":
        return (0.02 * jax.random.normal(key, shape)).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=A_RANGE[0],
                                          maxval=A_RANGE[1])).astype(dtype)
    if name == "dt_bias":
        lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, minval=lo,
                                                    maxval=hi)), DT_FLOOR)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name in ("norm", "final_norm", "gate_norm", "attn_norm",
                "mlp_norm") or name.endswith("_b"):
        return jnp.zeros(shape, dtype)
    if name == "D":
        return jnp.ones(shape, dtype)
    if name.startswith("conv_"):
        return (0.5 * jax.random.normal(key, shape)).astype(dtype)
    if name == "out_proj":                       # (L, H, P, d)
        fan_in = shape[1] * shape[2]
    else:                                        # (stack, fan_in, ...)
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
    seg = cs[:, :, None, :] - cs[:, None, :, :]           # (nc, t, s, H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = mm("ctn,csn->cts", Cc, Bc, q=q)
    y = mm("cts,ctsh,csh,cshp->cthp", scores, decay, dtc, xc, q=q)
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


def mamba(arch: Dict[str, Any], s, h, q=None):
    """The Mamba-2 mixer on a normalised sequence h: (S, d)."""
    eps = arch["norm_eps"]
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
    return mm("shp,hpd->sd", y, s["out_proj"], q=q)


def rotary(x, theta: float):
    """Rotary embedding on the whole head, halves rotated. x: (S, n, h)."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(arch: Dict[str, Any], p, x, q=None):
    """Causal attention of one sequence x: (S, 2d) -> (S, d), one block
    of queries at a time."""
    S = x.shape[0]
    n, hd = arch["n_heads"], arch["head_dim"]
    scale = (hd / 2) ** -0.5
    theta = arch["rope_theta"]
    qh = rotary(mm("se,ef->sf", x, p["wq"], q=q).reshape(S, n, hd), theta)
    kh = rotary(mm("se,ef->sf", x, p["wk"], q=q).reshape(S, n, hd), theta)
    vh = mm("se,ef->sf", x, p["wv"], q=q).reshape(S, n, hd)
    bq = min(QUERY_BLOCK, S)

    def block(_, xs):
        start, qb = xs
        scores = mm("qnh,knh->nqk", qb, kh, q=q) * scale
        rows = start + jnp.arange(bq)[:, None]
        scores = jnp.where(jnp.arange(S)[None, :] <= rows, scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        return None, mm("nqk,knh->qnh", w, vh, q=q)

    starts = jnp.arange(0, S, bq)
    _, out = jax.lax.scan(jax.checkpoint(block), None,
                          (starts, qh.reshape(S // bq, bq, n, hd)))
    return mm("sf,fd->sd", out.reshape(S, n * hd), p["wo"], q=q)


def shared_block(arch: Dict[str, Any], blk, hyb, h, e0, q=None):
    """T_j(h, e0): shared block ``blk`` with hybrid layer j's adapter and
    linear ``hyb``."""
    eps = arch["norm_eps"]
    x = rms_norm(jnp.concatenate([h, e0], axis=-1), blk["attn_norm"], eps)
    a = rms_norm(attention(arch, blk["attn"], x, q), blk["mlp_norm"], eps)
    low = mm("sd,dr->sr", a, hyb["adapter_in"], q=q)
    mlp = blk["mlp"]
    gate = mm("sd,df->sf", a, mlp["w_gate"], q=q) \
        + mm("sr,rf->sf", low, hyb["adapter_gate"], q=q)
    up = mm("sd,df->sf", a, mlp["w_up"], q=q) \
        + mm("sr,rf->sf", low, hyb["adapter_up"], q=q)
    m = mm("sf,fd->sd", jax.nn.gelu(gate, approximate=False) * up,
           mlp["w_down"], q=q)
    return mm("sd,de->se", m, hyb["linear"], q=q)


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def row_loss_sum(arch: Dict[str, Any], p, row, q=None):
    """Summed next-token cross-entropy of one sequence (S + 1 tokens);
    ``q`` rounds every matrix product's operands (see :func:`mm`)."""
    inputs, labels = row[:-1], row[1:]
    emb = p["embed"]["embedding"]
    e0 = emb[inputs]
    k, n_blocks = arch["attn_every"], arch["n_shared_blocks"]
    eps = arch["norm_eps"]

    def layer(h, lp, i):
        x_in = h
        if i % k == k - 1:
            j = i // k
            x_in = h + shared_block(arch, _at(p["shared"], j % n_blocks),
                                    _at(p["hybrid"], j), h, e0, q)
        return h + mamba(arch, lp["ssd"], rms_norm(x_in, lp["norm"], eps), q)

    h = e0
    for i in range(arch["n_layers"]):
        h = jax.checkpoint(lambda h, lp, i=i: layer(h, lp, i))(
            h, _at(p["layers"], i))
    h = rms_norm(h, p["final_norm"], eps)
    S, d = h.shape
    c = min(LOSS_CHUNK, S)

    def ce(total, xs):
        hc, yc = xs
        logits = mm("cd,vd->cv", hc, emb, q=q)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(jax.checkpoint(ce), jnp.float32(0.0),
                            (h.reshape(S // c, c, d),
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
        # parameters, gradients and both moments are 2 GB each at the
        # cell's size: the update reuses their buffers, or it would not
        # fit one chip beside them
        update = jax.jit(_adamw, static_argnames=("opt",),
                         donate_argnums=(0, 1, 2, 3))
        params = jax.tree.map(lambda a: jnp.array(a, copy=True), params0)
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

def _seq_len() -> int:
    """The cell's positions per sequence, from the configuration file
    beside this one (attention's operations grow with it)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "config.json")) as f:
        return int(json.load(f)["shape"]["seq_len"])


def model_flops_per_token(arch: Dict[str, Any]) -> float:
    """Operations per token of one training step at the cell's sequence
    length: each Mamba-2 layer's projections, convolution and state-space
    recurrence (decay, input and readout over H*N*P state); each hybrid
    layer's attention projections (2d in), causal scores and weighted
    values (on average (S + 1) / 2 positions), MLP with its adapter and
    linear; the output head; times three for the forward and backward
    passes.  Recomputation does not count."""
    d, N, L = arch["d_model"], arch["ssm_state"], arch["n_layers"]
    di = arch["ssm_expand"] * d
    H = di // arch["ssm_head_dim"]
    f, r = arch["d_ff"], arch["adapter_rank"]
    inner = arch["n_heads"] * arch["head_dim"]
    mamba_layer = 2 * d * (2 * di + 2 * N + H) + 2 * di * d \
        + 2 * arch["conv_width"] * (di + 2 * N) + 6 * di * N
    attn = 2 * (2 * d) * 3 * inner + 2 * inner * d \
        + 4 * inner * (_seq_len() + 1) / 2
    mlp = 3 * 2 * d * f + 2 * d * r + 2 * 2 * r * f + 2 * d * d
    hybrid = L // arch["attn_every"]
    head = 2 * d * arch["vocab_size"]
    return 3.0 * (L * mamba_layer + hybrid * (attn + mlp) + head)
