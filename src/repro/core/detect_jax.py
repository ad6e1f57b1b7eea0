"""Jitted detection kernels — the on-accelerator half of ``detect``.

The detection math (cross-process merges, log-log slope fits, abnormality
thresholding) is pure array arithmetic over :class:`PerfStore` matrices, so
it can run under ``jax.jit`` next to the training job instead of on the
host.  Three kernels cover the pipeline:

* ``_merge_all_kernel`` — ALL jittable merge strategies ("mean" / "max" /
  "p0" / variance-weighted "var") batched into one stacked (S, P, V)
  computation: one fused executable produces the (4, S, V) merged-time
  stack, so switching strategies costs an index, not a recompile.
* ``_non_scalable_kernel`` — the merge stack + batched least-squares
  log-log slopes + share/deviation flagging, fused under one ``jax.jit``.
* ``_abnormal_topk_kernel`` — cross-process median (``jnp.median``,
  bit-identical to numpy's in f64) + AbnormThd thresholding + stable
  top-k, all device-side; only the winners and the (V,) typical
  transfer.  (``_abnormal_kernel`` keeps the host-median parity entry.)

A second kernel family consumes DEVICE row blocks instead of one
host-stacked matrix (:class:`~repro.core.shard.DeviceShardView` inputs —
the online path, where only dirty rows re-upload per call, and the view
hands one resident (P, V) buffer):
``_merge_blocks_kernel`` computes each scale's merge column as
block-level reductions, ``_slope_flag_from_M_kernel`` derives the total
step time from the merged stack itself, and
``_abnormal_topk_blocks_kernel`` concatenates any blocks on the device.
``non_scalable_views`` / ``abnormal_topk_view`` are their entry points;
the stacked (P, V) matrix exists on neither host nor wire.

Since the fused-detection PR, every entry point dispatches to the
one-launch fused ops in ``repro.kernels.detect_fused`` by default
(Pallas on TPU; a fused-jnp fast path elsewhere — integer-key sort
median + tournament top-k, which is what fixed the ~10-dispatch CPU
overhead), with device-cached historical merge columns making
steady-state ``non_scalable_views`` O(live scale).  The kernels above
are retained verbatim as the unfused baseline: the parity suite pins
``fused == legacy == numpy``, the view entry points accept
``fused=False``, and the bench still times the legacy chain.

:func:`precision` picks the detection dtype for every entry point.  On a
TPU it is float32 (keyed through int32): the chip has no float64 or
64-bit integer vector type, and the results match the float64 numpy
reference to ~1e-4 relative.  Elsewhere it is float64 under a
thread-local ``jax.enable_x64`` (the rest of the process keeps jax's
float32 default), matching the numpy reference in ``repro.core.detect``
to reduction-order rounding (~1e-15 relative); ``SCALANA_DETECT_F32=1``
selects the float32 variant there too, which is how the CPU tests cover
it.  "median" and "cluster" merges are per-column sorts with
data-dependent cuts; they stay on the numpy path.

This module imports jax at module level and is therefore ONLY imported by
``detect``'s backend resolution — never from the lazy ``repro.core``
namespace — so the analysis layer stays importable without jax.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detect import JIT_STRATEGIES, VAR_EPS
from repro.core.spans import span
# The pure merge/slope/flag formulas live in
# ``repro.kernels.detect_fused.kernel`` — single source of truth shared
# by these legacy kernels (kept for parity tests and as the unfused
# baseline) and the fused one-launch paths the entry points dispatch to.
from repro.kernels.detect_fused import ops as _fused
from repro.kernels.detect_fused.kernel import (
    abnormal_flags as _abnormal_flags,
    merge_all_stack as _merge_all,
    merge_blocks as _merge_blocks,
    slope_share_flag as _slope_share_flag)


@jax.jit
def _merge_all_kernel(t, var):
    return _merge_all(t, var)


@jax.jit
def _non_scalable_kernel(t, var, logp, present, total_max,
                         ideal_slope, slope_margin, min_share):
    """Fused detect math: merge stack + slope fit + flagging.

    t, var: (S, P, V) stacked per-scale matrices (P padded to the max
    scale; padding rows are dead readings).  logp: (S,) log process
    counts.  present: (S, V) vertex-exists-at-scale mask.  Returns
    (M_all (4, S, V), slope (4, V), share (4, V), flagged (4, V))."""
    M = _merge_all(t, var)                             # (4, S, V)
    slope, share, flagged = _slope_share_flag(
        M, logp, present, total_max, ideal_slope, slope_margin,
        min_share)
    return M, slope, share, flagged


# -- device-block kernels (DeviceShardView inputs) ------------------
# One scale's row blocks -> its (4, V) merged column, as
# associative block-level reductions (row order = global proc order;
# the stacked host matrix never exists on either side).
_merge_blocks_kernel = jax.jit(_merge_blocks)


@jax.jit
def _slope_flag_from_M_kernel(M, logp, present, top_idx,
                              ideal_slope, slope_margin, min_share):
    """Slope/share/flag over a device-merged (4, S, V) stack.

    The reference scale's total step time is the "max"-merge row at
    the last scale summed over the root's children — exactly the
    host's per-column ``max(initial=0.0)`` sum, since the merge
    already clamps all-dead columns to 0 — so no extra reduction
    over the raw blocks is needed."""
    total_max = M[JIT_STRATEGIES.index("max"), -1, top_idx].sum()
    return _slope_share_flag(M, logp, present, total_max,
                             ideal_slope, slope_margin, min_share)


@jax.jit
def _abnormal_kernel(t, typical, abnorm_thd, min_share, step_time):
    return _abnormal_flags(t, typical, abnorm_thd, min_share, step_time)


@jax.jit
def _fit_slopes_kernel(logp, M, valid):
    """Batched masked least-squares slope per column — the jitted
    twin of ``detect._fit_slopes`` (same formulas, same <2-point
    clamp to 0.0)."""
    x = logp[:, None]                              # (S, 1)
    Y = jnp.where(valid, jnp.log(jnp.where(valid, M, 1.0)), 0.0)
    n = valid.sum(axis=0)
    Sx = (x * valid).sum(axis=0)
    Sy = Y.sum(axis=0)
    Sxx = (x * x * valid).sum(axis=0)
    Sxy = (x * Y).sum(axis=0)
    denom = n * Sxx - Sx ** 2
    num = n * Sxy - Sx * Sy
    safe = jnp.where(denom != 0, denom, 1.0)
    slope = jnp.where(denom != 0, num / safe, 0.0)
    return jnp.where(n >= 2, slope, 0.0)


def _median_flags_topk(t, abnorm_thd, min_share, step_time, k):
    """Fused median + flags + device-side top-k selection — the one
    ranking implementation both the host-fed and the device-block
    kernels trace, so they cannot diverge.

    The cross-process median (``typical``), the (P, V) flag matrix
    and the excess-over-typical scores never leave the device:
    flagged entries are ranked by a stable descending argsort over
    the vid-major flattening (matching the numpy path's
    ``argwhere(flags.T)`` enumeration plus stable sort, so ties rank
    identically) and only the best ``k`` flat indices, their scores,
    the flagged count, and the (V,) typical vector are transferred."""
    typical = jnp.median(t, axis=0)
    flags = _abnormal_flags(t, typical, abnorm_thd, min_share, step_time)
    score = jnp.where(flags, t - typical, -jnp.inf)
    flat = score.T.reshape(-1)                    # vid-major
    order = jnp.argsort(-flat, stable=True)[:k]
    return order, flat[order], flags.sum(), typical


@partial(jax.jit, static_argnums=(4,))
def _abnormal_topk_kernel(t, abnorm_thd, min_share, step_time, k):
    return _median_flags_topk(t, abnorm_thd, min_share, step_time, k)


@partial(jax.jit, static_argnums=(4,))
def _abnormal_topk_blocks_kernel(ts, top_idx, abnorm_thd, min_share, k):
    """Device-block abnormal detection, end to end on the device.

    ``ts``: tuple of (n_local, V) device blocks in global proc order.
    The blocks concatenate ON THE DEVICE (the host never stacks
    them); the step time, the cross-process median, the flag matrix
    and the ranking all happen there, and only <= k winners + the
    (V,) typical come home."""
    t = jnp.concatenate(ts, axis=0)               # device-side (P, V)
    step_time = t[:, top_idx].sum(axis=1).max()
    step_time = jnp.where(step_time > 0.0, step_time, 1e-12)
    return _median_flags_topk(t, abnorm_thd, min_share, step_time, k)


@partial(jax.jit, static_argnums=(6,))
def _abnormal_topk_blocks_live_kernel(ts, live, valid, top_idx,
                                      abnorm_thd, min_share, k):
    """Degraded-fleet variant: gather LIVE rows at a FIXED shape.

    ``live`` holds the live global row indices PADDED to the fleet
    size P (pad entries repeat row 0); ``valid`` marks the real ones.
    The padded gather keeps every traced shape a function of P alone,
    so a flapping host — a different live count every detect call —
    reuses one compiled executable instead of retracing per live-set
    size.  Semantics still match a store that never contained the
    dead rows: the median sorts dead rows to +inf and reads the two
    live middle order statistics (zeroing would poison the count),
    and dead rows are zeroed/mask-excluded everywhere magnitudes
    matter (step time, flags, scores)."""
    t = jnp.concatenate(ts, axis=0)[live]         # (P, V), P static
    vcol = valid[:, None]
    n_live = jnp.maximum(valid.sum(), 1)
    step_time = jnp.where(valid, t[:, top_idx].sum(axis=1), 0.0).max()
    step_time = jnp.where(step_time > 0.0, step_time, 1e-12)
    # masked median == numpy's over the live subset: dead rows sort
    # to the bottom, the middle pair indexes only live entries
    srt = jnp.sort(jnp.where(vcol, t, jnp.inf), axis=0)
    lo = jnp.take(srt, (n_live - 1) // 2, axis=0)
    hi = jnp.take(srt, n_live // 2, axis=0)
    typical = 0.5 * (lo + hi)
    tm = jnp.where(vcol, t, 0.0)
    flags = _abnormal_flags(tm, typical, abnorm_thd, min_share,
                            step_time) & vcol
    score = jnp.where(flags, tm - typical, -jnp.inf)
    flat = score.T.reshape(-1)                    # vid-major
    order = jnp.argsort(-flat, stable=True)[:k]
    return order, flat[order], flags.sum(), typical


def precision(dtype=None):
    """(dtype, context) for detection on the default jax backend.

    ``dtype=None`` picks it: float32 on a TPU, which has no float64 or
    64-bit integer vectors; float64 elsewhere unless
    ``SCALANA_DETECT_F32`` is set (truthy; read per call so tests can
    toggle it).  The context enables x64 for float64, thread-locally,
    and disables it for float32, so device buffers and kernels are
    created in exactly that dtype.  Every entry point here and
    :meth:`~repro.core.shard.DeviceShardView.refresh` run inside it."""
    if dtype is None:
        f32 = (jax.default_backend() == "tpu"
               or os.environ.get("SCALANA_DETECT_F32", "").lower()
               in ("1", "true", "on", "yes"))
        dtype = np.float32 if f32 else np.float64
    dtype = np.dtype(dtype)
    return dtype, jax.enable_x64(dtype == np.float64)


def merge_matrix(t: np.ndarray, strategy: str,
                 var: Optional[np.ndarray] = None) -> np.ndarray:
    """Jitted columnwise merge over one (n_procs, V) matrix -> (V,).

    All strategies are computed in one stacked kernel call; ``strategy``
    only selects the output row.  Reference-parity entry point for tests
    and small hosts; detection uses the fused kernels directly."""
    si = JIT_STRATEGIES.index(strategy)
    dtype, ctx = precision()
    with ctx:
        td = jnp.asarray(np.asarray(t, dtype)[None])
        vd = jnp.asarray(np.zeros_like(t, dtype)[None] if var is None
                         else np.asarray(var, dtype)[None])
        out = _merge_all_kernel(td, vd)
    return np.asarray(out)[si, 0]


def fit_slopes(scales: Sequence[int], M: np.ndarray,
               valid: np.ndarray) -> np.ndarray:
    """Jitted batched log-log slope fit: (S, V) merged times -> (V,).

    The jax side of ``detect.fit_slopes`` — the cross-run diff resolves
    between the two through ``detect._resolve_backend``."""
    dtype, ctx = precision()
    with ctx:
        out = _fit_slopes_kernel(
            jnp.asarray(np.log(np.asarray(scales, dtype))),
            jnp.asarray(np.asarray(M, dtype)),
            jnp.asarray(np.asarray(valid, bool)))
    return np.asarray(out)


def non_scalable_arrays(scales: Sequence[int], t: np.ndarray, var: np.ndarray,
                        present: np.ndarray, total_max: float,
                        ideal_slope: float, slope_margin: float,
                        min_share: float, strategy: str
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                   np.ndarray]:
    """Run the one-launch fused non-scalable op; returns the ``strategy``
    row of (M (S, V), slope (V,), share (V,), flagged (V,))."""
    si = JIT_STRATEGIES.index(strategy)
    dtype, ctx = precision()
    logp = np.log(np.asarray(scales, dtype))
    with ctx:
        M, slope, share, flagged = _fused.fused_non_scalable(
            jnp.asarray(np.asarray(t, dtype)),
            jnp.asarray(np.asarray(var, dtype)),
            jnp.asarray(logp), jnp.asarray(present),
            ideal_slope=float(ideal_slope),
            slope_margin=float(slope_margin),
            min_share=float(min_share), total_max=float(total_max))
    return (np.asarray(M)[si], np.asarray(slope)[si],
            np.asarray(share)[si], np.asarray(flagged)[si])


def abnormal_arrays(t: np.ndarray, abnorm_thd: float, min_share: float,
                    step_time: float) -> Tuple[np.ndarray, np.ndarray]:
    """Run the abnormal kernel; returns ((P, V) flags, (V,) typical).

    Materializes the full flag matrix on the host — parity/test entry
    point; detection itself uses :func:`abnormal_topk`, which keeps the
    flags device-resident."""
    dtype, ctx = precision()
    typical = np.median(np.asarray(t, dtype), axis=0)
    with ctx:
        flags = _abnormal_kernel(
            jnp.asarray(np.asarray(t, dtype)), jnp.asarray(typical),
            float(abnorm_thd), float(min_share), float(step_time))
    return np.asarray(flags), typical


def abnormal_topk(t: np.ndarray, abnorm_thd: float, min_share: float,
                  step_time: float, k: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Device-resident abnormal detection: only the winners come home.

    The cross-process median (``jnp.median`` — bit-identical to numpy's
    in f64; the order statistic no longer round-trips ``t`` through the
    host), the (P, V) flag matrix and the ranking scores stay on the
    device until report time; the host receives the (vid, proc) indices
    of the ``<= k`` highest-scoring flagged entries (ranked exactly like
    the numpy reference: descending ``time - typical``, ties in
    vid-major enumeration order), the (V,) typical vector, and the total
    flagged count.  Returns ``(vids, procs, typical, n_flagged)``."""
    dtype, ctx = precision()
    t_host = np.asarray(t, dtype)
    with ctx:
        order, _, count, typical = _fused.fused_abnormal(
            (jnp.asarray(t_host),), None, float(abnorm_thd),
            float(min_share), int(k), step_time=float(step_time))
        n_flagged = int(count)                 # report time: flags leave
        order = np.asarray(order[:min(int(k), n_flagged)])  # the device
        typical = np.asarray(typical)
    n_procs = t_host.shape[0]
    return order // n_procs, order % n_procs, typical, n_flagged


def col_tiles(n_vertices: int) -> int:
    """The fused abnormal kernel's column tiles over ``n_vertices``."""
    return _fused._lanes(n_vertices) // _fused._COL_TILE


def abnormal_topk_view(view, n_vertices: int, top: Sequence[int],
                       abnorm_thd: float, min_share: float, k: int,
                       live_rows: Optional[np.ndarray] = None,
                       fused: bool = True
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Abnormal detection fed straight from a
    :class:`~repro.core.shard.DeviceShardView` — the online entry point.

    ``view.refresh`` uploads only the rows written since the last call
    (O(dirty rows), not O(P·V)) into the view's resident (P, V) buffer,
    where the step time, median, flagging and top-k ranking all run.
    The host never materializes the stacked (P, V) matrix.
    ``top`` is the root's child vids (the step-time columns).  Returns
    ``(vids, procs, typical, n_flagged)`` like :func:`abnormal_topk`.

    ``live_rows``: optional live global row indices (degraded fleets).
    The gather runs on the device at a shape PADDED to the fleet size
    (pad rows masked out), so varying live counts — a flapping host —
    hit one compiled executable instead of retracing per live-set size.
    The returned ``procs`` index INTO ``live_rows`` (the caller maps
    back to global procs), matching the host path's row-subset
    semantics.

    ``fused=True`` (the default) routes through the one-launch fused op
    (``repro.kernels.detect_fused``); ``fused=False`` keeps the legacy
    multi-dispatch kernel chain — the unfused baseline the bench still
    times and the parity tests pin the fused path against."""
    dtype, ctx = precision()
    n_procs = view.n_procs
    with ctx:
        view.refresh(n_vertices, dtype)
        ts = tuple(view.time_blocks())
        top_d = jnp.asarray(np.asarray(top, np.int32))
        if live_rows is not None:
            live = np.zeros(n_procs, np.int32)
            valid = np.zeros(n_procs, bool)
            n_live = int(len(live_rows))
            live[:n_live] = np.asarray(live_rows, np.int32)
            valid[:n_live] = True
        if fused:
            if live_rows is None:
                order, _, count, typical = _fused.fused_abnormal(
                    ts, top_d, float(abnorm_thd), float(min_share),
                    int(k))
            else:
                order, _, count, typical = _fused.fused_abnormal(
                    ts, top_d, float(abnorm_thd), float(min_share),
                    int(k), live=jnp.asarray(live),
                    valid=jnp.asarray(valid))
        elif live_rows is None:
            order, _, count, typical = _abnormal_topk_blocks_kernel(
                ts, top_d, float(abnorm_thd), float(min_share), int(k))
        else:
            order, _, count, typical = _abnormal_topk_blocks_live_kernel(
                ts, jnp.asarray(live), jnp.asarray(valid), top_d,
                float(abnorm_thd), float(min_share), int(k))
        with span("detect.readback"):
            n_flagged = int(count)
            order = np.asarray(order[:min(int(k), n_flagged)])
            typical = np.asarray(typical)
    return order // n_procs, order % n_procs, typical, n_flagged


def non_scalable_views(scales: Sequence[int], views: Sequence,
                       n_vertices: int, present: np.ndarray,
                       top: Sequence[int], ideal_slope: float,
                       slope_margin: float, min_share: float, strategy: str,
                       fused: bool = True
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]:
    """Non-scalable detection fed from per-scale
    :class:`~repro.core.shard.DeviceShardView`\\ s.

    The fused path (default) exploits that historical scales are
    IMMUTABLE once their run completes: each completed view's (4, V)
    merged column is computed once (``merge_scale_column``) and cached
    on the view keyed by its upload revision
    (:meth:`~repro.core.shard.DeviceShardView.merged_column`), so a
    steady-state call merges only the LIVE scale's blocks and runs the
    slope/share/flag tail — one ``fused_non_scalable_live`` launch over
    the cached (4, S-1, V) stack.  Any write, re-pin, layout or dtype
    change bumps the revision and refills that scale's column.  The
    reference step time still derives from the merged "max" row at the
    final scale.  ``fused=False`` keeps the legacy per-scale merge +
    slope-kernel chain (the unfused baseline).  Returns the ``strategy``
    row of (M (S, V), slope (V,), share (V,), flagged (V,)) as host
    arrays — O(S·V), never O(P·V)."""
    si = JIT_STRATEGIES.index(strategy)
    dtype, ctx = precision()
    logp = np.log(np.asarray(scales, dtype))
    with ctx:
        for view in views:
            view.refresh(n_vertices, dtype)
        if fused:
            cols = []
            for v in views[:-1]:
                col = v.merged_column()
                if col is None:
                    col = _fused.merge_scale_column(
                        tuple(v.time_blocks()), tuple(v.var_blocks()))
                    v.cache_merged_column(col)
                cols.append(col)
            hist = (jnp.stack(cols, axis=1) if cols
                    else jnp.zeros((4, 0, int(n_vertices)), dtype))
            live = views[-1]
            M, slope, share, flagged = _fused.fused_non_scalable_live(
                tuple(live.time_blocks()), tuple(live.var_blocks()),
                hist, jnp.asarray(logp), jnp.asarray(present),
                jnp.asarray(np.asarray(top, np.int32)),
                ideal_slope=float(ideal_slope),
                slope_margin=float(slope_margin),
                min_share=float(min_share))
        else:
            M = jnp.stack(
                [_merge_blocks_kernel(tuple(v.time_blocks()),
                                      tuple(v.var_blocks())) for v in views],
                axis=1)                                    # (4, S, V)
            slope, share, flagged = _slope_flag_from_M_kernel(
                M, jnp.asarray(logp), jnp.asarray(present),
                jnp.asarray(np.asarray(top, np.int32)),
                float(ideal_slope), float(slope_margin), float(min_share))
        with span("detect.readback"):
            return (np.asarray(M)[si], np.asarray(slope)[si],
                    np.asarray(share)[si], np.asarray(flagged)[si])
