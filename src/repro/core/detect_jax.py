"""Jitted detection entry points — the on-accelerator half of ``detect``.

The detection math (cross-process merges, log-log slope fits, abnormality
thresholding, the cross-process median and the top-k ranking) is pure
array arithmetic over :class:`PerfStore` matrices, so it runs under
``jax.jit`` next to the training job instead of on the host.  Every entry
point here dispatches to the one-launch fused ops in
``repro.kernels.detect_fused`` (compiled Pallas on a TPU; a fused-jnp
executable elsewhere):

* ``non_scalable_views`` / ``abnormal_topk_view`` — the online path,
  fed from :class:`~repro.core.shard.DeviceShardView` objects.  Each view
  keeps one resident (P, V) buffer and re-uploads only the rows written
  since the last call; historical scales' merged columns stay cached on
  the device, so a steady-state cycle is one non-scalable and one
  abnormal launch.  The stacked (P, V) matrix exists on neither host nor
  wire.
* ``non_scalable_arrays`` / ``abnormal_topk`` — the same ops fed from
  host arrays (a plain ``PerfStore``).
* ``merge_matrix`` — one (P, V) matrix through the fused merge, the
  parity entry point the tests pin against numpy.
* ``fit_slopes`` — the jitted batched slope fit of the cross-run diff
  (``repro.runs.diff``).

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
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detect import JIT_STRATEGIES
from repro.core.spans import span
from repro.kernels.detect_fused import ops as _fused


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
    """Fused columnwise merge over one (n_procs, V) matrix -> (V,).

    One ``merge_scale_column`` launch computes every jittable strategy;
    ``strategy`` only selects the output row.  Reference-parity entry
    point for tests: it runs the merge the device-fed detectors run."""
    si = JIT_STRATEGIES.index(strategy)
    dtype, ctx = precision()
    with ctx:
        td = jnp.asarray(np.asarray(t, dtype))
        vd = jnp.asarray(np.zeros_like(t, dtype) if var is None
                         else np.asarray(var, dtype))
        out = _fused.merge_scale_column((td,), (vd,))
    return np.asarray(out)[si]


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
                       live_rows: Optional[np.ndarray] = None
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
    semantics."""
    dtype, ctx = precision()
    n_procs = view.n_procs
    with ctx:
        view.refresh(n_vertices, dtype)
        ts = tuple(view.time_blocks())
        top_d = jnp.asarray(np.asarray(top, np.int32))
        if live_rows is None:
            order, _, count, typical = _fused.fused_abnormal(
                ts, top_d, float(abnorm_thd), float(min_share), int(k))
        else:
            live = np.zeros(n_procs, np.int32)
            valid = np.zeros(n_procs, bool)
            n_live = int(len(live_rows))
            live[:n_live] = np.asarray(live_rows, np.int32)
            valid[:n_live] = True
            order, _, count, typical = _fused.fused_abnormal(
                ts, top_d, float(abnorm_thd), float(min_share), int(k),
                live=jnp.asarray(live), valid=jnp.asarray(valid))
        with span("detect.readback"):
            n_flagged = int(count)
            order = np.asarray(order[:min(int(k), n_flagged)])
            typical = np.asarray(typical)
    return order // n_procs, order % n_procs, typical, n_flagged


def non_scalable_views(scales: Sequence[int], views: Sequence,
                       n_vertices: int, present: np.ndarray,
                       top: Sequence[int], ideal_slope: float,
                       slope_margin: float, min_share: float, strategy: str
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]:
    """Non-scalable detection fed from per-scale
    :class:`~repro.core.shard.DeviceShardView`\\ s.

    Historical scales are IMMUTABLE once their run completes: each
    completed view's (4, V) merged column is computed once
    (``merge_scale_column``) and cached on the view keyed by its upload
    revision (:meth:`~repro.core.shard.DeviceShardView.merged_column`),
    so a steady-state call merges only the LIVE scale's blocks and runs
    the slope/share/flag tail — one ``fused_non_scalable_live`` launch
    over the cached (4, S-1, V) stack.  Any write, re-pin, layout or
    dtype change bumps the revision and refills that scale's column.
    The reference step time still derives from the merged "max" row at
    the final scale.  Returns the ``strategy`` row of (M (S, V), slope
    (V,), share (V,), flagged (V,)) as host arrays — O(S·V), never
    O(P·V)."""
    si = JIT_STRATEGIES.index(strategy)
    dtype, ctx = precision()
    logp = np.log(np.asarray(scales, dtype))
    with ctx:
        for view in views:
            view.refresh(n_vertices, dtype)
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
        with span("detect.readback"):
            return (np.asarray(M)[si], np.asarray(slope)[si],
                    np.asarray(share)[si], np.asarray(flagged)[si])
