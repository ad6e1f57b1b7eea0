"""Plain numpy reference of ScalAna's two detectors (paper section IV-A).

Copied from the numpy path of the repository's ``core/detect.py`` (the
"mean" merge strategy, the default) so that later changes to the program
cannot move the yardstick.  It works on the benchmark's own time
matrices, never on the program's store.

- Non-scalable vertices: each vertex's time merged over processes (the
  mean of the positive readings) at every scale, a least-squares slope of
  log time on log processes, flagged when the slope exceeds the ideal by
  more than the margin and the vertex's share of the largest scale's step
  is significant.
- Abnormal vertices: at one scale, a process whose time exceeds
  ``abnorm_thd`` times the cross-process median (or any time where the
  median is zero) by at least ``min_share`` of the step time; the
  ``top_k`` largest excesses, ties in vertex-major order.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np


def merge_mean(t: np.ndarray) -> np.ndarray:
    """(P, V) -> (V,): the mean of each column's positive readings."""
    pos = t > 0.0
    cnt = pos.sum(axis=0)
    s = t.sum(axis=0, where=pos)
    return np.divide(s, cnt, out=np.zeros(t.shape[1]), where=cnt > 0)


def fit_slopes(scales: Sequence[int], M: np.ndarray,
               valid: np.ndarray) -> np.ndarray:
    x = np.log(np.asarray(scales, float))[:, None]
    Y = np.where(valid, np.log(np.where(valid, M, 1.0)), 0.0)
    n = valid.sum(axis=0)
    Sx, Sy = (x * valid).sum(axis=0), Y.sum(axis=0)
    Sxx, Sxy = (x * x * valid).sum(axis=0), (x * Y).sum(axis=0)
    denom = n * Sxx - Sx ** 2
    slope = np.divide(n * Sxy - Sx * Sy, denom, out=np.zeros(M.shape[1]),
                      where=denom != 0)
    return np.where(n >= 2, slope, 0.0)


def non_scalable(series: Mapping[int, np.ndarray], top: Sequence[int], *,
                 ideal_slope: float, slope_margin: float, min_share: float,
                 top_k: int) -> List[Dict]:
    """series: {processes: (P, V) time matrix}.  Returns the flagged
    vertices, highest score first: vid, slope, share, merged times."""
    scales = sorted(series)
    t_ref = series[scales[-1]]
    total = float(np.sum(t_ref[:, list(top)].max(axis=0, initial=0.0)))
    M = np.stack([merge_mean(series[p]) for p in scales])
    slope = fit_slopes(scales, M, M > 0.0)
    share = np.divide(M[-1], total, out=np.zeros(M.shape[1]),
                      where=total > 0)
    flagged = (M.sum(axis=0) > 0.0) & (slope - ideal_slope > slope_margin) \
        & (share >= min_share)
    out = [{"vid": int(v), "slope": float(slope[v]),
            "share": float(share[v]),
            "score": float((slope[v] - ideal_slope) * share[v]),
            "times": {p: float(M[i, v]) for i, p in enumerate(scales)}}
           for v in np.nonzero(flagged)[0]]
    out.sort(key=lambda d: -d["score"])
    return out[:top_k]


def abnormal(t: np.ndarray, top: Sequence[int], *, abnorm_thd: float,
             min_share: float, top_k: int) -> List[Dict]:
    """t: (P, V).  Returns vid, proc, time and typical (the median) of
    the ``top_k`` largest excesses over the median."""
    step_time = float(t[:, list(top)].sum(axis=1).max()) if len(top) else 0.0
    step_time = step_time or 1e-12
    typical = np.median(t, axis=0)
    active = t.max(axis=0) > 0.0
    over = (typical > 0.0) & (t > abnorm_thd * typical) \
        & ((t - typical) / step_time >= min_share)
    dead = (typical == 0.0) & (t / step_time >= min_share)
    idx = np.argwhere(((over | dead) & active).T)          # vid-major
    if not idx.size:
        return []
    score = t[idx[:, 1], idx[:, 0]] - typical[idx[:, 0]]
    picks = np.argsort(-score, kind="stable")[:top_k]
    return [{"vid": int(idx[j, 0]), "proc": int(idx[j, 1]),
             "time": float(t[idx[j, 1], idx[j, 0]]),
             "typical": float(typical[idx[j, 0]])} for j in picks]


def compare(prog_ns: List[Dict], prog_ab: List[Dict], ref_ns: List[Dict],
            ref_ab: List[Dict]) -> Tuple[bool, bool, float]:
    """(non-scalable set differs, abnormal set differs, widest gap).

    The gap is taken over the vertices both sides flagged: slopes by
    their absolute difference (they are of order 1 and a vertex that does
    not scale has slope 0), shares, merged times and typical times by
    their relative difference."""
    ns_differs = {d["vid"] for d in prog_ns} != {d["vid"] for d in ref_ns}
    ab_differs = ({(a["vid"], a["proc"]) for a in prog_ab}
                  != {(a["vid"], a["proc"]) for a in ref_ab})
    gap = 0.0
    ref_by = {d["vid"]: d for d in ref_ns}
    for d in prog_ns:
        r = ref_by.get(d["vid"])
        if r is None:
            continue
        gap = max(gap, abs(d["slope"] - r["slope"]) / max(abs(r["slope"]),
                                                          1.0),
                  _rel(d["share"], r["share"]))
        for p, t in r["times"].items():
            gap = max(gap, _rel(d["times"].get(p, 0.0), t))
    ref_ab_by = {(a["vid"], a["proc"]): a for a in ref_ab}
    for a in prog_ab:
        r = ref_ab_by.get((a["vid"], a["proc"]))
        if r is not None:
            gap = max(gap, _rel(a["typical"], r["typical"]),
                      _rel(a["time"], r["time"]))
    return ns_differs, ab_differs, gap


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)
