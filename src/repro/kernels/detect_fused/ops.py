"""Public fused-detection ops: dispatch, jnp fast path, launch counting.

Three ops cover the detection tail, each ONE logical launch:

* :func:`fused_non_scalable` — stacked (S, P, V) merge + slope + flag.
* :func:`fused_non_scalable_live` — the steady-state variant: merge only
  the LIVE scale's blocks, splice in the device-cached historical (4, H,
  V) merged columns, then slope + flag.  This is what makes incremental
  detect O(live scale), not O(all scales).
* :func:`fused_abnormal` — step time + masked median + flags + stable
  top-k over the (P, V) matrix (blockwise and degraded-fleet variants).

Dispatch (``interpret`` argument, resolved by :func:`kernel_mode`):

* ``None``  — compiled Pallas on TPU, else the fused-jnp fast path (one
  ``jax.jit`` executable per op; Pallas interpret mode is far slower
  than plain XLA on CPU, so it is never the default).
* ``True``  — Pallas in interpret mode (the CI parity path).
* ``False`` — compiled Pallas, forced.

The jnp fast path exists because the op chain it replaces was dispatch-
bound on CPU (~10 device calls per detect); it leans on two tricks
shared with the Pallas kernels via :mod:`.kernel`'s integer-key bridge:
XLA's single-operand *integer* sort (~13x faster than a float sort on
CPU) yields the exact masked median as two middle order statistics, and
a block tournament extracts the top-k without the 45ms stable argsort —
while reproducing the reference ranking bit-for-bit (descending score,
ties by ascending vid-major flat index).

Every op bumps ``launch_counts`` once per logical kernel launch, so
tests and benches can ASSERT "steady-state detect = 1 non-scalable + 1
abnormal launch" instead of inferring it from timings.  On the Pallas
paths an eager concatenation of several row blocks (a caller's own; a
``DeviceShardView`` hands one resident buffer) is the span
``detect.concat`` (:mod:`repro.core.spans`), its stat ``operands``.
"""
from __future__ import annotations

import collections
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.detect_fused.kernel import (
    _COL_TILE, _ROW_TILE, _STEP_EPS, ab_fused_kernel, abnormal_flags,
    from_key, key_floor, merge_all_stack, merge_blocks, ns_fused_kernel,
    slope_share_flag, to_key)
from repro.core.detect import JIT_STRATEGIES
from repro.core.spans import span

_IMAX = JIT_STRATEGIES.index("max")

# -- launch counting seam ----------------------------------------------
# One logical launch == one fused op call; ``launch_counts`` accumulates
# per-op totals.
launch_counts: collections.Counter = collections.Counter()


def _note_launch(name: str) -> None:
    launch_counts[name] += 1


def reset_launch_counts() -> None:
    launch_counts.clear()


def kernel_mode(interpret: Optional[bool] = None) -> str:
    """How the fused ops run: "pallas" (compiled), "interpret" or "jnp"."""
    if interpret is None:
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return "interpret" if interpret else "pallas"


# -- fused jnp fast path ------------------------------------------------

def _topk_tournament(score: jax.Array, k: int):
    """Exact replacement for ``argsort(-flat, stable=True)[:k]`` over the
    vid-major flattening: block maxima + k extraction rounds on integer
    keys.  Ties rank by ascending flat index (argmax returns the FIRST
    max), and extracted and padding entries sit at the key floor —
    strictly below every real score key, -inf included, so the -inf
    tail fills in ascending index order exactly like the stable
    argsort."""
    flat = score.T.reshape(-1)
    n = flat.shape[0]
    keys = to_key(flat)
    floor = key_floor(score.dtype)
    B = 128
    nb = -(-n // B)
    kp = jnp.pad(keys, (0, nb * B - n),
                 constant_values=floor).reshape(nb, B)

    def body(i, st):
        kb, order, vals = st
        j = jnp.argmax(kb.max(axis=1))
        row = kb[j]
        i2 = jnp.argmax(row)
        gidx = j.astype(jnp.int32) * B + i2.astype(jnp.int32)
        kb = kb.at[j, i2].set(floor)
        return kb, order.at[i].set(gidx), vals.at[i].set(row[i2])

    order = jnp.zeros((k,), jnp.int32)
    vals = jnp.zeros((k,), keys.dtype)
    _, order, vals = jax.lax.fori_loop(0, k, body, (kp, order, vals))
    return order, from_key(vals, score.dtype)


@partial(jax.jit, static_argnames=("k", "use_step", "use_live",
                                   "use_valid"))
def _ab_jnp(ts, live, valid, top_idx, params, *, k, use_step, use_live,
            use_valid):
    t = ts[0] if len(ts) == 1 else jnp.concatenate(ts, axis=0)
    if use_live:
        t = t[live]
    P = t.shape[0]
    if use_valid:
        vcol = valid[:, None]
        n_live = jnp.maximum(valid.sum(), 1)
        tm = jnp.where(vcol, t, 0.0)
        lo_r, hi_r = (n_live - 1) // 2, n_live // 2
        keys = to_key(jnp.where(vcol, t, jnp.inf).T)
    else:
        tm = t
        lo_r, hi_r = (P - 1) // 2, P // 2
        keys = to_key(t.T)
    if use_step:
        step = params[2]
    else:
        srow = t[:, top_idx].sum(axis=1)
        if use_valid:
            srow = jnp.where(valid, srow, 0.0)
        step = srow.max()
        step = jnp.where(step > 0.0, step, _STEP_EPS)
    srt = jax.lax.sort(keys, dimension=1, is_stable=False)
    lo = from_key(jnp.take(srt, lo_r, axis=1), t.dtype)
    hi = from_key(jnp.take(srt, hi_r, axis=1), t.dtype)
    typical = 0.5 * (lo + hi)
    flags = abnormal_flags(tm, typical, params[0], params[1], step)
    if use_valid:
        flags = flags & vcol
    score = jnp.where(flags, tm - typical, -jnp.inf)
    order, svals = _topk_tournament(score, k)
    return order, svals, flags.sum(), typical


@partial(jax.jit, static_argnames=("use_total",))
def _ns_jnp(t, var, logp, present, top_idx, params, *, use_total):
    M = merge_all_stack(t, var)
    total = params[3] if use_total else M[_IMAX, -1, top_idx].sum()
    slope, share, flagged = slope_share_flag(
        M, logp, present, total, params[0], params[1], params[2])
    return M, slope, share, flagged


@jax.jit
def _ns_live_jnp(ts, vs, hist, logp, present, top_idx, params):
    col = merge_blocks(ts, vs)
    M = jnp.concatenate([hist, col[:, None, :]], axis=1)
    total = M[_IMAX, -1, top_idx].sum()
    slope, share, flagged = slope_share_flag(
        M, logp, present, total, params[0], params[1], params[2])
    return M, slope, share, flagged


# -- padding helpers for the Pallas path -------------------------------

def _lanes(V: int) -> int:
    """V rounded up to whole lane tiles (the ab kernel's column width)."""
    return -(-V // _COL_TILE) * _COL_TILE


def _pad_cols(a: jax.Array, V: int) -> jax.Array:
    Vp = _lanes(V)
    if Vp == V:
        return a
    pad = [(0, 0)] * (a.ndim - 1) + [(0, Vp - V)]
    return jnp.pad(a, pad)


def _pad_rows(a: jax.Array, P: int, axis: int) -> jax.Array:
    TP = P if P <= _ROW_TILE else _ROW_TILE
    Pp = -(-P // TP) * TP
    if Pp == P:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, Pp - P)
    return jnp.pad(a, pad)                             # zero rows = dead


def _rows_of(blocks: Tuple[jax.Array, ...]) -> jax.Array:
    """The blocks' rows as one (P, V) device array: the block itself
    where there is one (a device view's resident buffer), an eager
    concatenation where there are several."""
    if len(blocks) == 1:
        return blocks[0]
    with span("detect.concat", operands=len(blocks)):
        return jnp.concatenate(blocks, axis=0)


def _top_mask(top_idx, V: int, dtype) -> jax.Array:
    m = jnp.zeros((1, _lanes(V)), dtype)
    if top_idx is not None and top_idx.shape[0]:
        m = m.at[0, top_idx].set(1.0)
    return m


# -- public ops ---------------------------------------------------------

def fused_abnormal(ts: Sequence[jax.Array], top_idx: Optional[jax.Array],
                   abnorm_thd: float, min_share: float, k: int, *,
                   step_time: Optional[float] = None,
                   live: Optional[jax.Array] = None,
                   valid: Optional[jax.Array] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-launch abnormal detection over device time blocks.

    ``ts``: tuple of (n_local, V) blocks in global proc order (a single
    block for the host-stacked path).  ``top_idx``: int32 step-time
    column indices (unused when ``step_time`` is given).  ``live`` /
    ``valid``: padded live-row gather indices + real-row mask for
    degraded fleets (fixed shapes — one executable per fleet size, not
    per live count).  Returns ``(order, scores, count, typical)`` device
    arrays: flat vid-major indices and scores of the top ``k`` entries
    (reference ranking: descending ``time - typical``, stable ascending-
    index ties, -inf tail), the total flagged count, and the (V,)
    typical vector."""
    ts = tuple(ts)
    V = ts[0].shape[1]
    P = live.shape[0] if live is not None else sum(b.shape[0] for b in ts)
    dtype = ts[0].dtype
    k_eff = max(min(int(k), P * V), 0)
    if k_eff == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), dtype),
                jnp.zeros((), jnp.int32), jnp.zeros((V,), dtype))
    mode = kernel_mode(interpret)
    _note_launch("abnormal")
    use_step = step_time is not None
    if mode == "jnp":
        params = jnp.asarray(
            [abnorm_thd, min_share, step_time if use_step else 0.0, 0.0],
            dtype)
        return _ab_jnp(
            ts,
            live if live is not None else jnp.zeros((0,), jnp.int32),
            valid if valid is not None else jnp.zeros((0,), bool),
            top_idx if top_idx is not None else jnp.zeros((0,), jnp.int32),
            params, k=k_eff, use_step=use_step, use_live=live is not None,
            use_valid=valid is not None)
    t = _rows_of(ts)
    if live is not None:
        t = t[live]
    t = _pad_cols(t, V)
    vcol = (valid.astype(dtype)[:, None] if valid is not None
            else jnp.ones((P, 1), dtype))
    params = jnp.asarray([[abnorm_thd, min_share,
                           step_time if use_step else 0.0,
                           1.0 if use_step else 0.0, 0.0, 0.0, 0.0, 0.0]],
                         dtype)
    order, scores, count, typical = ab_fused_kernel(
        t, vcol, _top_mask(top_idx, V, dtype), params, k=k_eff,
        interpret=(mode == "interpret"))
    return order[0], scores[0], count[0, 0], typical[0, :V]


def fused_non_scalable(t: jax.Array, var: jax.Array, logp: jax.Array,
                       present: jax.Array, *, ideal_slope: float,
                       slope_margin: float, min_share: float,
                       total_max: Optional[float] = None,
                       top_idx: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array]:
    """One-launch non-scalable detection over the stacked (S, P, V)
    time/variance matrices.  ``total_max`` (host-derived reference step
    time) wins over the in-kernel derivation from ``top_idx``.  Returns
    (M (4, S, V), slope (4, V), share (4, V), flagged (4, V) bool)."""
    mode = kernel_mode(interpret)
    _note_launch("non_scalable")
    dtype = t.dtype
    use_total = total_max is not None
    if mode == "jnp":
        params = jnp.asarray(
            [ideal_slope, slope_margin, min_share,
             total_max if use_total else 0.0], dtype)
        return _ns_jnp(
            t, var, logp, present,
            top_idx if top_idx is not None else jnp.zeros((0,), jnp.int32),
            params, use_total=use_total)
    S, P, V = t.shape
    tp = _pad_rows(t, P, axis=1)
    vp = _pad_rows(var, P, axis=1)
    params = jnp.asarray([[ideal_slope, slope_margin, min_share,
                           total_max if use_total else 0.0,
                           1.0 if use_total else 0.0, 0.0, 0.0, 0.0]],
                         dtype)
    M, slope, share, flagged = ns_fused_kernel(
        tp, vp, jnp.zeros((4, 1, V), dtype), logp[:, None],
        present.astype(dtype), _top_mask(top_idx, V, dtype)[:, :V],
        params, n_hist=0, interpret=(mode == "interpret"))
    return M, slope, share, flagged > 0.0


def fused_non_scalable_live(ts: Sequence[jax.Array],
                            vs: Sequence[jax.Array], hist: jax.Array,
                            logp: jax.Array, present: jax.Array,
                            top_idx: jax.Array, *, ideal_slope: float,
                            slope_margin: float, min_share: float,
                            interpret: Optional[bool] = None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array]:
    """Steady-state non-scalable detection: merge only the LIVE scale's
    (n_local, V) blocks, append the merged column to the device-cached
    historical (4, H, V) stack, and run the slope/share/flag tail — all
    one launch.  ``logp`` / ``present`` cover all H + 1 scales (live
    last).  Returns (M (4, H + 1, V), slope, share, flagged bool)."""
    mode = kernel_mode(interpret)
    _note_launch("non_scalable_live")
    ts, vs = tuple(ts), tuple(vs)
    dtype = ts[0].dtype
    if mode == "jnp":
        params = jnp.asarray([ideal_slope, slope_margin, min_share, 0.0],
                             dtype)
        return _ns_live_jnp(ts, vs, hist, logp, present, top_idx, params)
    V = ts[0].shape[1]
    t, v = _rows_of(ts), _rows_of(vs)
    P = t.shape[0]
    n_hist = int(hist.shape[1])
    t = _pad_rows(t, P, axis=0)[None]
    v = _pad_rows(v, P, axis=0)[None]
    hist_in = hist if n_hist else jnp.zeros((4, 1, V), dtype)
    params = jnp.asarray([[ideal_slope, slope_margin, min_share,
                           0.0, 0.0, 0.0, 0.0, 0.0]], dtype)
    M, slope, share, flagged = ns_fused_kernel(
        t, v, hist_in, logp[:, None], present.astype(dtype),
        _top_mask(top_idx, V, dtype)[:, :V], params, n_hist=n_hist,
        interpret=(mode == "interpret"))
    return M, slope, share, flagged > 0.0


def merge_scale_column(ts: Sequence[jax.Array], vs: Sequence[jax.Array]
                       ) -> jax.Array:
    """One scale's blocks -> its (4, V) merged column (one launch).

    The cache-fill op: historical scales run through this once, then
    their columns stay device-resident until the underlying blocks
    change (see ``DeviceShardView.merged_column``)."""
    _note_launch("merge_column")
    return _merge_blocks_kernel(tuple(ts), tuple(vs))


@jax.jit
def _merge_blocks_kernel(ts, vs):
    return merge_blocks(ts, vs)
