"""Pure-numpy oracle for the fused detection kernels.

Independent re-derivation of the detection math from
``repro.core.detect``'s numpy path, in the exact shapes the fused
kernels consume, so the parity tests pin the fused kernels (both the
jnp fast path and the Pallas interpret mode) against this reference.

Everything here is host numpy and float64 unless the caller passes
other dtypes; nothing imports jax.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from repro.core.detect import JIT_STRATEGIES, VAR_EPS


def merge_all_ref(t: np.ndarray, var: np.ndarray) -> np.ndarray:
    """(S, P, V) times + variances -> (4, S, V) merged stack.

    Rows ordered as ``JIT_STRATEGIES``; non-positive readings are dead
    (excluded from every merge, exactly like the numpy detect path)."""
    pos = t > 0.0
    cnt = pos.sum(axis=1)
    any_pos = cnt > 0
    total = np.where(pos, t, 0.0).sum(axis=1)
    mean = np.where(any_pos, total / np.maximum(cnt, 1), 0.0)
    mx = np.where(any_pos, t.max(axis=1), 0.0)
    p0 = t[:, 0, :]
    p0 = np.where(p0 > 0.0, p0, mean)
    w = np.where(pos, 1.0 / (var + VAR_EPS), 0.0)
    wsum = w.sum(axis=1)
    varm = np.where(wsum > 0,
                    (w * t).sum(axis=1) / np.where(wsum > 0, wsum, 1.0),
                    0.0)
    return np.stack([mean, mx, p0, varm])


def slope_share_flag_ref(M: np.ndarray, logp: np.ndarray,
                         present: np.ndarray, total_max: float,
                         ideal_slope: float, slope_margin: float,
                         min_share: float
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(4, S, V) merged stack -> (slope, share, flagged), each (4, V).

    ``share`` is guarded: a non-positive ``total_max`` (all-dead final
    scale) yields share 0 and flags nothing."""
    valid = (M > 0.0) & present[None]
    x = logp[None, :, None]
    Y = np.where(valid, np.log(np.where(valid, M, 1.0)), 0.0)
    n = valid.sum(axis=1)
    Sx = (x * valid).sum(axis=1)
    Sy = Y.sum(axis=1)
    Sxx = (x * x * valid).sum(axis=1)
    Sxy = (x * Y).sum(axis=1)
    denom = n * Sxx - Sx ** 2
    num = n * Sxy - Sx * Sy
    slope = np.where((denom != 0) & (n >= 2),
                     num / np.where(denom != 0, denom, 1.0), 0.0)
    share = np.where(total_max > 0.0,
                     M[:, -1, :] / np.where(total_max > 0.0, total_max, 1.0),
                     0.0)
    flagged = ((M.sum(axis=1) > 0.0)
               & (slope - ideal_slope > slope_margin)
               & (share >= min_share))
    return slope, share, flagged


def non_scalable_ref(scales: Sequence[int], t: np.ndarray, var: np.ndarray,
                     present: np.ndarray, ideal_slope: float,
                     slope_margin: float, min_share: float,
                     total_max: Optional[float] = None,
                     top: Optional[Sequence[int]] = None
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Full fused non-scalable reference over a stacked (S, P, V) input.

    ``total_max`` defaults to the kernel-internal derivation: the "max"
    merge row at the final scale summed over the ``top`` columns."""
    M = merge_all_ref(t, var)
    if total_max is None:
        top = [] if top is None else list(top)
        total_max = float(M[JIT_STRATEGIES.index("max"), -1, top].sum())
    logp = np.log(np.asarray(scales, t.dtype))
    slope, share, flagged = slope_share_flag_ref(
        M, logp, present, total_max, ideal_slope, slope_margin, min_share)
    return M, slope, share, flagged


def abnormal_ref(t: np.ndarray, top: Sequence[int], abnorm_thd: float,
                 min_share: float, k: int,
                 valid: Optional[np.ndarray] = None,
                 step_time: Optional[float] = None
                 ) -> Tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """Abnormal-detection reference: (order, scores, count, typical).

    ``t`` is the (P, V) time matrix (already live-gathered and padded on
    the degraded path); ``valid`` marks real rows (None = all live).
    ``order`` are flat vid-major indices (``vid * P + proc``) of the top
    ``k`` scoring entries, ranked by descending ``time - typical`` with
    stable ascending-index ties — exactly the numpy detector's ranking.
    """
    P, V = t.shape
    if valid is None:
        valid = np.ones(P, bool)
    vcol = valid[:, None]
    n_live = max(int(valid.sum()), 1)
    tm = np.where(vcol, t, 0.0)
    if step_time is None:
        step_time = float(np.where(valid, tm[:, list(top)].sum(axis=1),
                                   0.0).max()) if P else 0.0
        step_time = step_time if step_time > 0.0 else 1e-12
    srt = np.sort(np.where(vcol, t, np.inf), axis=0)
    lo = srt[(n_live - 1) // 2]
    hi = srt[n_live // 2]
    typical = 0.5 * (lo + hi)
    active = tm.max(axis=0) > 0.0
    over = ((typical > 0.0) & (tm > abnorm_thd * typical)
            & ((tm - typical) / step_time >= min_share))
    dead_typical = (typical == 0.0) & (tm / step_time >= min_share)
    flags = (over | dead_typical) & active & vcol
    score = np.where(flags, tm - typical, -np.inf)
    flat = score.T.reshape(-1)
    order = np.argsort(-flat, kind="stable")[:k]
    return order, flat[order], int(flags.sum()), typical
