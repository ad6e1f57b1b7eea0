"""Problematic-vertex detection (§IV-A): unit + property tests."""
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (COMM, COMP, PSG, build_ppg, detect_abnormal,
                        detect_non_scalable, fit_loglog)
from repro.core.graph import PerfVector
from repro.core.inject import simulate_series


def _linear_psg(n_comp=6, with_comm=True):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    prev = None
    for i in range(n_comp):
        v = g.new_vertex(COMP, f"comp{i}", parent=root.vid,
                         source=f"model.py:{10 + i}")
        v.flops = 100.0
        if prev is not None:
            g.add_edge(prev, v.vid, "data")
        g.add_edge(root.vid, v.vid, "control")
        prev = v.vid
    if with_comm:
        c = g.new_vertex(COMM, "psum", parent=root.vid, source="step.py:42")
        c.comm_kind, c.comm_bytes = "all_reduce", 1e6
        g.add_edge(prev, c.vid, "data")
        g.add_edge(root.vid, c.vid, "control")
    return g


def test_fit_loglog_recovers_slope():
    scales = [4, 8, 16, 32, 64]
    for b in (-1.0, -0.5, 0.0, 0.7):
        times = [2.0 * p ** b for p in scales]
        a, slope = fit_loglog(scales, times)
        assert slope == pytest.approx(b, abs=1e-6)
        assert a == pytest.approx(2.0, rel=1e-6)


def test_non_scalable_detects_amdahl_vertex():
    psg = _linear_psg()
    bad = 3       # vertex with a serial fraction

    def time_at(p, vid, n):
        v = psg.vertices[vid]
        if v.kind == COMM:
            return 0.0
        if vid == bad:
            return 1.0 * (0.6 + 0.4 / n)     # Amdahl
        return 1.0 / n

    series = simulate_series(psg, [4, 8, 16, 32], time_at)
    found = detect_non_scalable(series)
    assert found, "must detect the serial-fraction vertex"
    vids = [d.vid for d in found]
    assert bad in vids
    top = found[0]
    assert top.vid in (bad,) or top.kind == "Comm"
    assert top.source


def test_non_scalable_clean_program_no_flags():
    psg = _linear_psg(with_comm=False)

    def time_at(p, vid, n):
        return 1.0 / n                        # perfect strong scaling

    series = simulate_series(psg, [4, 8, 16, 32], time_at)
    found = detect_non_scalable(series)
    assert not found


@pytest.mark.parametrize("strategy", ["mean", "median", "max", "cluster"])
def test_merge_strategies_all_work(strategy):
    psg = _linear_psg()

    def time_at(p, vid, n):
        v = psg.vertices[vid]
        if v.kind == COMM:
            return 0.0
        base = 1.0 / n
        return base * (2.0 if (p == 0 and vid == 2) else 1.0)

    series = simulate_series(psg, [4, 8, 16], time_at)
    # just exercise every merge strategy end-to-end
    detect_non_scalable(series, strategy=strategy)


def test_abnormal_detects_straggler_process():
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(8)}
    perf[5][2] = PerfVector(time=0.5)          # straggler: proc 5, vertex 2
    ppg = build_ppg(psg, 8, perf)
    found = detect_abnormal(ppg, abnorm_thd=1.3)
    assert found
    assert (found[0].proc, found[0].vid) == (5, 2)
    assert found[0].ratio == pytest.approx(5.0)


def test_abnormal_threshold_respected():
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(8)}
    perf[5][2] = PerfVector(time=0.12)         # only 1.2x: below 1.3 thd
    ppg = build_ppg(psg, 8, perf)
    assert not detect_abnormal(ppg, abnorm_thd=1.3)
    assert detect_abnormal(ppg, abnorm_thd=1.1)


@settings(max_examples=25, deadline=None)
@given(
    straggler=st.integers(0, 7),
    vid=st.integers(1, 6),
    ratio=st.floats(1.5, 20.0),
)
def test_abnormal_property_injected_always_found(straggler, vid, ratio):
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(8)}
    perf[straggler][vid] = PerfVector(time=0.1 * ratio)
    ppg = build_ppg(psg, 8, perf)
    found = detect_abnormal(ppg, abnorm_thd=1.3)
    assert any((a.proc, a.vid) == (straggler, vid) for a in found)


# ---------------------------------------------------------------------------
# backend validation + degraded-fleet row masks
# ---------------------------------------------------------------------------

def test_unknown_backend_raises_with_valid_values_listed():
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(4)}
    ppg = build_ppg(psg, 4, perf)
    with pytest.raises(ValueError, match=r"'numpy', 'jax', 'auto'"):
        detect_abnormal(ppg, backend="torch")
    # case/whitespace are forgiven, not errors
    assert detect_abnormal(ppg, backend="  NumPy ") == \
        detect_abnormal(ppg, backend="numpy")


def test_env_backend_validated_and_attributed():
    """An unknown ``backend=`` raises naming the value; ``None`` means
    "auto" and resolves exactly like it."""
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(4)}
    ppg = build_ppg(psg, 4, perf)
    with pytest.raises(ValueError,
                       match=r"unknown detect backend: 'cuda'"):
        detect_abnormal(ppg, backend="cuda")
    assert detect_abnormal(ppg, backend=None) == \
        detect_abnormal(ppg, backend="auto")


def test_auto_backend_prefers_numpy_on_cpu_only_jax():
    """Merely having jax importable must no longer flip auto onto the
    jitted path: on CPU-only jax with host-side stores the dispatch
    overhead makes it ~10x slower than numpy.  auto picks jax only when
    the data is device-resident (device_live) or a real accelerator is
    the default backend; an explicit 'jax' still forces it."""
    jax = pytest.importorskip("jax")
    from repro.core.detect import _resolve_backend

    if jax.default_backend() != "cpu":
        pytest.skip("accelerator present; auto legitimately routes to jax")
    assert "jax" in sys.modules
    assert _resolve_backend("auto") is None
    assert _resolve_backend(None) is None
    # a live DeviceShardView opts auto back into the jitted path
    assert _resolve_backend("auto", device_live=True) is not None
    assert _resolve_backend(None, device_live=True) is not None
    # explicit request always wins over the CPU heuristic
    assert _resolve_backend("jax") is not None


def test_proc_mask_excludes_rows_exactly():
    """Masked detection == one-shot on a store that never held the dead
    rows (exclusion, not zero-pollution: zeros would shift the median)."""
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(8)}
    perf[5][2] = PerfVector(time=0.5)          # straggler on a DEAD proc
    perf[2][3] = PerfVector(time=0.4)          # straggler on a live proc
    ppg = build_ppg(psg, 8, perf)
    mask = np.ones(8, bool)
    mask[4:6] = False
    live = np.nonzero(mask)[0]
    sub = build_ppg(psg, 6, {i: perf[int(p)] for i, p in enumerate(live)})
    got = detect_abnormal(ppg, proc_mask=mask, backend="numpy")
    want = detect_abnormal(sub, backend="numpy")
    assert got, "live straggler must still be found"
    assert [(a.vid, a.proc, a.time, a.typical, a.ratio) for a in got] == \
        [(a.vid, int(live[a.proc]), a.time, a.typical, a.ratio)
         for a in want]
    assert all(a.proc != 5 for a in got)       # dead straggler is silent


def test_proc_mask_shape_mismatch_raises():
    psg = _linear_psg()
    perf = {p: {v.vid: PerfVector(time=0.1) for v in psg.vertices
                if v.kind == COMP} for p in range(4)}
    ppg = build_ppg(psg, 4, perf)
    with pytest.raises(ValueError, match="proc_mask"):
        detect_abnormal(ppg, proc_mask=np.ones(7, bool))


def test_non_scalable_proc_mask_subsets_reference_scale():
    series = simulate_series(_linear_psg(), [4, 8, 16],
                             lambda p, vid, n: 0.05 * n + 0.01 * vid)
    mask = np.ones(16, bool)                   # reference = largest scale
    mask[3:7] = False
    out = detect_non_scalable(series, proc_mask=mask)
    all_live = detect_non_scalable(series, proc_mask=np.ones_like(mask))
    ref = detect_non_scalable(series)
    assert [(d.vid, d.slope, d.share) for d in all_live] == \
        [(d.vid, d.slope, d.share) for d in ref]
    # empty live set: nothing to diagnose, never a crash
    assert detect_non_scalable(series, proc_mask=np.zeros_like(mask)) == []
    assert isinstance(out, list)
