"""Indexed graph core: indexes vs brute force, dense store vs dict API,
sparse counter columns vs a dense reference, implicit comm groups vs
materialized edges, detect/backtrack equivalence, and jitted detection vs
the numpy reference (including the all-jax-absent fallback path).

The brute-force references are verbatim ports of the pre-index scalar
implementations, so these properties pin the refactor to the old
semantics."""
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (COMM, COMP, LOOP, PSG, backtrack, build_ppg, contract,
                        detect_abnormal, detect_non_scalable, root_causes)
from repro.core.backtrack import WAIT_COUNTER, _anomaly_score
from repro.core.detect import _merge, _merge_matrix
from repro.core.graph import PerfStore, PerfVector
from repro.core.inject import simulate, simulate_series


# ---------------------------------------------------------------------------
# random graph strategy
# ---------------------------------------------------------------------------

@st.composite
def random_indexed_psg(draw):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    frontier = [root.vid]
    n = draw(st.integers(4, 30))
    for _ in range(n):
        parent = draw(st.sampled_from(frontier))
        kind = draw(st.sampled_from([COMP, COMP, LOOP, COMM]))
        v = g.new_vertex(kind, kind.lower(), parent=parent,
                         depth=g.vertices[parent].depth + 1)
        if kind == COMM:
            v.comm_kind, v.comm_bytes = "all_reduce", 1e4
        if kind == LOOP:
            frontier.append(v.vid)
    for parent in {v.parent for v in g.vertices if v.parent >= 0}:
        kids = g.children(parent)
        for a, b in zip(kids, kids[1:]):
            g.add_edge(a, b, "data")
        for k in kids:
            g.add_edge(parent, k, "control")
    # a few extra cross edges
    extra = draw(st.integers(0, 5))
    for _ in range(extra):
        a = draw(st.integers(1, len(g.vertices) - 1))
        b = draw(st.integers(1, len(g.vertices) - 1))
        kind = draw(st.sampled_from(["data", "control"]))
        g.add_edge(a, b, kind)
    return g


# ---------------------------------------------------------------------------
# PSG index vs brute force
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(psg=random_indexed_psg())
def test_indexes_match_brute_force(psg):
    edges = set(psg.edges)
    for v in psg.vertices:
        vid = v.vid
        assert sorted(psg.children(vid)) == sorted(
            u.vid for u in psg.vertices if u.parent == vid)
        for kind in (None, "data", "control"):
            assert sorted(psg.preds(vid, kind)) == sorted(
                s for (s, d, k) in edges
                if d == vid and (kind is None or k == kind))
            assert sorted(psg.succs(vid, kind)) == sorted(
                d for (s, d, k) in edges
                if s == vid and (kind is None or k == kind))
    for kind in ("Root", COMP, LOOP, COMM):
        assert [u.vid for u in psg.by_kind(kind)] == \
            [u.vid for u in psg.vertices if u.kind == kind]


@settings(max_examples=20, deadline=None)
@given(psg=random_indexed_psg())
def test_index_survives_contraction_and_roundtrip(psg):
    cpsg, _ = contract(psg, max_loop_depth=2)
    for v in cpsg.vertices:
        assert sorted(cpsg.children(v.vid)) == sorted(
            u.vid for u in cpsg.vertices if u.parent == v.vid)
    clone = PSG.from_json(cpsg.to_json())
    assert clone.edges == cpsg.edges
    assert clone.stats() == cpsg.stats()
    for v in clone.vertices:
        assert sorted(clone.children(v.vid)) == sorted(
            u.vid for u in clone.vertices if u.parent == v.vid)


def test_filter_does_not_alias_source_vertices():
    """Regression: contraction._filter shared prims/p2p_pairs/meta lists
    with the source PSG, so mutating the filtered graph corrupted it."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    a = g.new_vertex(COMP, "a", parent=root.vid)
    a.prims = ["dot"]
    a.flops = 5.0
    c = g.new_vertex(COMM, "ppermute", parent=root.vid)
    c.p2p_pairs = [(0, 1)]
    c.meta["replica_groups"] = [[0, 1]]
    zero = g.new_vertex(COMP, "zero", parent=root.vid)   # dropped by filter
    for v in (a, c, zero):
        g.add_edge(root.vid, v.vid, "control")
    cpsg, mapping = contract(g, min_comp_flops=1.0)
    nv = cpsg.vertices[mapping[a.vid]]
    nv.prims.append("mutated")
    cpsg.vertices[mapping[c.vid]].p2p_pairs.append((9, 9))
    cpsg.vertices[mapping[c.vid]].meta["x"] = 1
    assert g.vertices[a.vid].prims == ["dot"]
    assert g.vertices[c.vid].p2p_pairs == [(0, 1)]
    assert "x" not in g.vertices[c.vid].meta


# ---------------------------------------------------------------------------
# PerfStore mapping compatibility
# ---------------------------------------------------------------------------

def test_perfstore_mapping_api():
    s = PerfStore(4, 3)
    s[(1, 2)] = PerfVector(time=0.5, samples=2, counters={"wait_s": 0.1})
    s[(0, 0)] = PerfVector(time=0.25)
    assert len(s) == 2
    assert (1, 2) in s and (2, 2) not in s
    assert s[(1, 2)].time == 0.5
    assert s[(1, 2)].counters == {"wait_s": 0.1}
    assert s.get((3, 1)) is None
    assert sorted(s.keys()) == [(0, 0), (1, 2)]
    # overwrite clears stale counters — in the dict view AND the raw
    # matrices the vectorized detectors/backtracker read
    s[(1, 2)] = PerfVector(time=0.7)
    assert s[(1, 2)].counters == {}
    assert s.counter_at("wait_s", 1, 2) == 0.0
    assert float(s.counter_matrix("wait_s")[1, 2]) == 0.0
    # growth past the initial column count
    s[(2, 10)] = PerfVector(time=1.0, counters={"flops": 3.0})
    assert s[(2, 10)].counters["flops"] == 3.0
    assert s.time_matrix(11).shape == (4, 11)
    assert float(s.time_matrix(11)[2, 10]) == 1.0


def test_ppg_get_time_defaults_zero():
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    v = g.new_vertex(COMP, "a", parent=root.vid)
    ppg = build_ppg(g, 4)
    assert ppg.get_time(2, v.vid) == 0.0
    assert ppg.times_across_procs(v.vid) == [0.0] * 4


# ---------------------------------------------------------------------------
# sparse counter columns vs dense reference
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n_procs=st.integers(1, 8), n_vertices=st.integers(1, 10),
       seed=st.integers(0, 10**6), n_ops=st.integers(1, 40))
def test_sparse_counters_match_dense_reference(n_procs, n_vertices, seed,
                                               n_ops):
    """Random write sequences through every store entry point: the sparse
    column layout must be observationally identical to dense (P, V)
    matrices, via counter_matrix, counter_columns, counter_at and the
    mapping API."""
    rng = np.random.default_rng(seed)
    store = PerfStore(n_procs, n_vertices)
    names = ["wait_s", "flops", "bytes"]
    v_max = n_vertices + 6                      # exercise growth past init
    dense = {nm: np.zeros((n_procs, v_max)) for nm in names}
    dmask = {nm: np.zeros((n_procs, v_max), bool) for nm in names}
    for _ in range(n_ops):
        op = int(rng.integers(3))
        vid = int(rng.integers(v_max))
        p = int(rng.integers(n_procs))
        counters = {nm: float(rng.uniform(0.1, 10.0))
                    for nm in names if rng.uniform() < 0.6}
        if op == 0:
            store.set_entry(p, vid, float(rng.uniform()), counters=counters)
            for nm, val in counters.items():
                dense[nm][p, vid], dmask[nm][p, vid] = val, True
        elif op == 1:
            store.set_column(vid, rng.uniform(0.1, 1.0, n_procs),
                             counters=counters)
            for nm, val in counters.items():
                dense[nm][:, vid], dmask[nm][:, vid] = val, True
        else:                                   # overwrite clears stale
            store[(p, vid)] = PerfVector(time=float(rng.uniform()),
                                         counters=counters)
            for nm in names:
                dense[nm][p, vid], dmask[nm][p, vid] = 0.0, False
            for nm, val in counters.items():
                dense[nm][p, vid], dmask[nm][p, vid] = val, True
    for nm in names:
        ref = np.where(dmask[nm], dense[nm], 0.0)
        assert np.array_equal(store.counter_matrix(nm, v_max), ref)
        vids, values, mask = store.counter_columns(nm)
        assert len(set(vids.tolist())) == len(vids)      # one slot per vid
        recon = np.zeros((n_procs, v_max))
        recon[:, vids] = np.where(mask, values, 0.0)
        assert np.array_equal(recon, ref)
        for p in range(n_procs):
            for vid in range(v_max):
                want = dense[nm][p, vid] if dmask[nm][p, vid] else -1.0
                assert store.counter_at(nm, p, vid, default=-1.0) == want
    for key in store.keys():
        for nm, val in store[key].counters.items():
            assert dmask[nm][key] and dense[nm][key] == val


def test_counter_storage_tracks_defining_subset():
    """A counter written at 2 of 100 columns must cost ~2 columns, not a
    dense (P, 100) matrix — the V/|Comm| memory claim."""
    store = PerfStore(64, 100)
    for vid in (3, 97):
        store.set_column(vid, 1.0, counters={"wait_s": 0.5})
    assert store.counter_nbytes() < store.counter_dense_nbytes() / 10
    assert store.counter_names() == ["wait_s"]


# ---------------------------------------------------------------------------
# implicit comm groups vs materialized edges
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n_procs=st.integers(2, 12), n_groups=st.integers(1, 3))
def test_comm_partners_match_materialized_clique(n_procs, n_groups):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    c = g.new_vertex(COMM, "psum", parent=root.vid)
    c.comm_kind = "all_reduce"
    procs = list(range(n_procs))
    groups = [procs[i::n_groups] for i in range(n_groups)]
    c.meta["replica_groups"] = groups
    p2p = g.new_vertex(COMM, "ppermute", parent=root.vid)
    p2p.p2p_pairs = [(p, (p + 1) % n_procs) for p in range(n_procs)]
    ppg = build_ppg(g, n_procs)

    edges = set()
    for grp in groups:
        for i in grp:
            for j in grp:
                if i != j:
                    edges.add(((i, c.vid), (j, c.vid)))
    for (s, d) in p2p.p2p_pairs:
        edges.add(((s, p2p.vid), (d, p2p.vid)))

    # the lazy view equals the materialized reference exactly
    assert set(ppg.comm_edges) == edges
    assert len(ppg.comm_edges) == len(edges)
    for e in edges:
        assert e in ppg.comm_edges
    for vid in (c.vid, p2p.vid):
        for p in range(n_procs):
            ref = sorted(src for (src, dst) in edges if dst == (p, vid))
            assert sorted(ppg.comm_partners(p, vid)) == ref


def test_comm_partners_unions_overlapping_groups():
    """Regression: a vertex carrying several groups (staged collectives)
    must union partners from every group containing the proc, deduplicated
    — exactly what the old materialized edge set produced."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    c = g.new_vertex(COMM, "psum", parent=root.vid)
    c.comm_kind = "all_reduce"
    from repro.core.graph import PPG
    ppg = PPG(g, 4)           # bare PPG: no auto full-range group
    ppg.add_collective_edges(c.vid, [0, 1])
    ppg.add_collective_edges(c.vid, [1, 2])
    ppg.add_collective_edges(c.vid, [0, 1, 3])    # overlaps the first group
    assert sorted(ppg.comm_partners(1, c.vid)) == \
        [(0, c.vid), (2, c.vid), (3, c.vid)]
    assert ((2, c.vid), (1, c.vid)) in ppg.comm_edges
    assert sorted(ppg.comm_partners(3, c.vid)) == [(0, c.vid), (1, c.vid)]


def _partners_reference(ci, proc, vid):
    """``CommIndex.partners`` as it read before the group arrays: the p2p
    preds, then each group's peers of ``proc`` through one seen set."""
    out = list(ci.p2p_preds_of((proc, vid)))
    seen = set(out)
    for group in ci.groups_of(vid):
        if proc not in group:
            continue
        for q in group:
            if q != proc and (q, vid) not in seen:
                seen.add((q, vid))
                out.append((q, vid))
    return out


def test_group_arrays_and_peers_match_the_group_tuples():
    """Each group's cached array is its tuple, each process once in
    first-occurrence order; ``group_peers`` is the group part of
    ``partners``, and ``partners`` is the old seen-set union, order
    included — with overlapping groups, repeated members and p2p preds
    at the same and at other vertices."""
    from repro.core.graph import CommIndex
    rng = np.random.default_rng(3)
    for trial in range(40):
        ci = CommIndex()
        n = int(rng.integers(2, 30))
        for vid in range(3):
            for _ in range(int(rng.integers(0, 4))):
                members = rng.choice(n, size=int(rng.integers(1, n + 1)),
                                     replace=bool(rng.random() < 0.3))
                ci.add_group(vid, members.tolist())
        for _ in range(int(rng.integers(0, 8))):
            ci.add_p2p((int(rng.integers(0, n)), int(rng.integers(0, 3))),
                       (int(rng.integers(0, n)), int(rng.integers(0, 3))))
        ci.add_p2p_batch(1, rng.integers(0, n, 3), rng.integers(0, n, 3))
        for vid in range(3):
            arrays = ci._group_arrays.get(vid, [])
            assert [a.tolist() for a in arrays] == \
                [list(dict.fromkeys(t)) for t in ci.groups_of(vid)], trial
            assert all(a.dtype == np.intp and not a.flags.writeable
                       for a in arrays)
            for p in range(n):
                ref = _partners_reference(ci, p, vid)
                assert ci.partners(p, vid) == ref, (trial, p, vid)
                peers = ci.group_peers(p, vid)
                assert peers.dtype == np.intp
                n_preds = len(ci.p2p_preds_of((p, vid)))
                assert [(q, vid) for q in peers.tolist()] == \
                    ref[n_preds:], (trial, p, vid)


def test_collective_storage_is_linear_in_procs():
    def comm_bytes(n):
        g = PSG()
        root = g.new_vertex("Root", "root")
        g.root = root.vid
        c = g.new_vertex(COMM, "psum", parent=root.vid)
        c.comm_kind = "all_reduce"
        return build_ppg(g, n).comm.nbytes()
    b256, b1024 = comm_bytes(256), comm_bytes(1024)
    assert b1024 <= 4 * b256 + 64          # O(P), not O(P^2)


# ---------------------------------------------------------------------------
# detect: vectorized vs scalar reference
# ---------------------------------------------------------------------------

def _ref_merge(times, strategy):
    arr = np.asarray([t for t in times if t > 0.0])
    if arr.size == 0:
        return 0.0
    if strategy == "mean":
        return float(arr.mean())
    if strategy == "median":
        return float(np.median(arr))
    if strategy == "max":
        return float(arr.max())
    if strategy == "cluster":
        s = np.sort(arr)
        best_cut, best_gap = None, -1.0
        for i in range(1, s.size):
            gap = s[i] - s[i - 1]
            if gap > best_gap:
                best_gap, best_cut = gap, i
        hi = s[best_cut:] if best_cut is not None else s
        return float(hi.mean())
    raise ValueError(strategy)


@settings(max_examples=25, deadline=None)
@given(p=st.integers(1, 16), v=st.integers(1, 12), seed=st.integers(0, 10**6),
       strategy=st.sampled_from(["mean", "median", "max", "cluster"]))
def test_merge_matrix_matches_scalar(p, v, seed, strategy):
    rng = np.random.default_rng(seed)
    t = rng.uniform(0.0, 1.0, (p, v))
    t[rng.uniform(size=(p, v)) < 0.3] = 0.0       # dead readings
    got = _merge_matrix(t, strategy)
    for col in range(v):
        assert got[col] == pytest.approx(
            _ref_merge(t[:, col].tolist(), strategy), abs=1e-12)


def test_merge_p0_ignores_dead_proc0():
    """Regression: 'p0' returned times[0] without the >0 filter, so a dead
    proc-0 reading (0.0) silently dropped the vertex."""
    assert _merge([0.0, 0.2, 0.4], "p0") == pytest.approx(0.3)   # mean of live
    assert _merge([0.5, 0.2, 0.4], "p0") == 0.5                  # p0 alive
    got = _merge_matrix(np.array([[0.0, 0.5], [0.2, 0.1], [0.4, 0.3]]), "p0")
    assert got[0] == pytest.approx(0.3)
    assert got[1] == 0.5


def _ref_detect_abnormal(ppg, abnorm_thd=1.3, min_share=0.01, top_k=20):
    """Verbatim port of the pre-refactor scalar detector."""
    psg = ppg.psg
    step_time = max(
        sum(ppg.get_time(p, v.vid) for v in psg.vertices
            if v.parent == psg.root)
        for p in range(ppg.n_procs)) or 1e-12
    out = []
    for v in psg.vertices:
        arr = np.asarray(ppg.times_across_procs(v.vid))
        if arr.max() <= 0:
            continue
        typical = float(np.median(arr))
        for proc, t in enumerate(arr.tolist()):
            if typical > 0 and t > abnorm_thd * typical \
                    and (t - typical) / step_time >= min_share:
                out.append((v.vid, proc, t, typical))
            elif typical == 0 and t / step_time >= min_share:
                out.append((v.vid, proc, t, typical))
    out.sort(key=lambda d: -(d[2] - d[3]))
    return out[:top_k]


@settings(max_examples=20, deadline=None)
@given(n_procs=st.integers(2, 16), seed=st.integers(0, 10**6),
       thd=st.floats(1.1, 3.0))
def test_detect_abnormal_matches_reference(n_procs, seed, thd):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    rng = np.random.default_rng(seed)
    vids = [g.new_vertex(COMP, f"c{i}", parent=root.vid).vid
            for i in range(6)]
    perf = {p: {vid: PerfVector(time=float(rng.uniform(0, 1))
                                if rng.uniform() > 0.2 else 0.0)
                for vid in vids} for p in range(n_procs)}
    ppg = build_ppg(g, n_procs, perf)
    got = [(a.vid, a.proc, a.time, a.typical)
           for a in detect_abnormal(ppg, abnorm_thd=thd)]
    ref = _ref_detect_abnormal(ppg, abnorm_thd=thd)
    assert [(v, p) for v, p, _, _ in got] == [(v, p) for v, p, _, _ in ref]
    for (gv, gp, gt, gy), (rv, rp, rt, ry) in zip(got, ref):
        assert gt == pytest.approx(rt, abs=1e-15)
        assert gy == pytest.approx(ry, abs=1e-15)


def _ref_detect_non_scalable(series, ideal_slope=-1.0, slope_margin=0.35,
                             min_share=0.02, strategy="mean"):
    """Verbatim port of the pre-refactor scalar detector (flag set only)."""
    scales = sorted(series)
    ref = series[scales[-1]]
    psg = ref.psg
    total_max = sum(max(ref.times_across_procs(v.vid) or [0.0])
                    for v in psg.vertices if v.parent == psg.root) or 1e-12
    flagged = []
    for v in psg.vertices:
        merged = {}
        for p in scales:
            ppg = series[p]
            if v.vid < len(ppg.psg.vertices):
                merged[p] = _ref_merge(ppg.times_across_procs(v.vid),
                                       strategy)
        if sum(merged.values()) <= 0:
            continue
        xs = [math.log(p) for p, t in merged.items() if t > 0]
        ys = [math.log(t) for t in merged.values() if t > 0]
        slope = float(np.polyfit(xs, ys, 1)[0]) if len(xs) >= 2 else 0.0
        share = merged.get(scales[-1], 0.0) / total_max
        if slope - ideal_slope > slope_margin and share >= min_share:
            flagged.append((v.vid, slope, share))
    return flagged


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10**6),
       strategy=st.sampled_from(["mean", "median", "max"]))
def test_detect_non_scalable_matches_reference(seed, strategy):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    rng = np.random.default_rng(seed)
    bad = set(rng.choice(6, 2, replace=False).tolist())
    for i in range(6):
        g.add_edge(root.vid, g.new_vertex(COMP, f"c{i}",
                                          parent=root.vid).vid, "control")

    def time_at(p, vid, n):
        if vid - 1 in bad:                       # serial fraction (Amdahl)
            return 1.0 * (0.6 + 0.4 / n)
        return 1.0 / n

    series = simulate_series(g, [4, 8, 16, 32], time_at, jitter=0.01,
                             seed=seed)
    got = detect_non_scalable(series, strategy=strategy, top_k=100)
    ref = _ref_detect_non_scalable(series, strategy=strategy)
    assert sorted(d.vid for d in got) == sorted(v for v, _, _ in ref)
    ref_by_vid = {v: (s, sh) for v, s, sh in ref}
    for d in got:
        assert d.slope == pytest.approx(ref_by_vid[d.vid][0], rel=1e-9)
        assert d.share == pytest.approx(ref_by_vid[d.vid][1], rel=1e-9)


# ---------------------------------------------------------------------------
# jitted detection vs the numpy reference
# ---------------------------------------------------------------------------

from repro.core.detect import JIT_STRATEGIES as JIT_MERGES  # noqa: E402


def _random_series(seed):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    rng = np.random.default_rng(seed)
    bad = set(rng.choice(6, 2, replace=False).tolist())
    for i in range(6):
        g.add_edge(root.vid, g.new_vertex(COMP, f"c{i}",
                                          parent=root.vid).vid, "control")

    def time_at(p, vid, n):
        if vid - 1 in bad:
            return 1.0 * (0.6 + 0.4 / n)
        return 1.0 / n

    return simulate_series(g, [4, 8, 16, 32], time_at, jitter=0.01,
                           seed=seed)


@settings(max_examples=15, deadline=None)
@given(p=st.integers(1, 16), v=st.integers(1, 12), seed=st.integers(0, 10**6),
       strategy=st.sampled_from(JIT_MERGES))
def test_merge_matrix_jax_matches_numpy(p, v, seed, strategy):
    pytest.importorskip("jax")
    from repro.core import detect_jax
    rng = np.random.default_rng(seed)
    t = rng.uniform(0.0, 1.0, (p, v))
    t[rng.uniform(size=(p, v)) < 0.3] = 0.0
    var = rng.uniform(0.0, 0.1, (p, v))
    got = detect_jax.merge_matrix(t, strategy, var=var)
    ref = _merge_matrix(t, strategy, var=var)
    assert np.allclose(got, ref, rtol=1e-12, atol=1e-15)


@settings(max_examples=15, deadline=None)
@given(p=st.integers(1, 12), v=st.integers(1, 8), seed=st.integers(0, 10**6))
def test_merge_var_matches_scalar(p, v, seed):
    rng = np.random.default_rng(seed)
    t = rng.uniform(0.0, 1.0, (p, v))
    t[rng.uniform(size=(p, v)) < 0.3] = 0.0
    var = rng.uniform(0.0, 0.1, (p, v))
    got = _merge_matrix(t, "var", var=var)
    for col in range(v):
        ref = _merge(t[:, col].tolist(), "var",
                     variances=var[:, col].tolist())
        assert got[col] == pytest.approx(ref, abs=1e-12)
    # without variance data every weight is equal: degrades to "mean"
    assert np.allclose(_merge_matrix(t, "var"), _merge_matrix(t, "mean"),
                       rtol=1e-9)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10**6), strategy=st.sampled_from(JIT_MERGES))
def test_detect_non_scalable_jax_matches_numpy(seed, strategy):
    pytest.importorskip("jax")
    series = _random_series(seed)
    a = detect_non_scalable(series, strategy=strategy, top_k=100,
                            backend="numpy")
    b = detect_non_scalable(series, strategy=strategy, top_k=100,
                            backend="jax")
    assert [d.vid for d in a] == [d.vid for d in b]
    for x, y in zip(a, b):
        assert y.slope == pytest.approx(x.slope, rel=1e-9)
        assert y.share == pytest.approx(x.share, rel=1e-9)
        assert sorted(y.times) == sorted(x.times)
        for scale, t in x.times.items():
            assert y.times[scale] == pytest.approx(t, rel=1e-9)


@settings(max_examples=10, deadline=None)
@given(n_procs=st.integers(2, 16), seed=st.integers(0, 10**6),
       thd=st.floats(1.1, 3.0))
def test_detect_abnormal_jax_matches_numpy(n_procs, seed, thd):
    pytest.importorskip("jax")
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    rng = np.random.default_rng(seed)
    vids = [g.new_vertex(COMP, f"c{i}", parent=root.vid).vid
            for i in range(6)]
    perf = {p: {vid: PerfVector(time=float(rng.uniform(0, 1))
                                if rng.uniform() > 0.2 else 0.0,
                                time_var=float(rng.uniform(0, 0.01)))
                for vid in vids} for p in range(n_procs)}
    ppg = build_ppg(g, n_procs, perf)
    a = detect_abnormal(ppg, abnorm_thd=thd, backend="numpy")
    b = detect_abnormal(ppg, abnorm_thd=thd, backend="jax")
    assert [(x.vid, x.proc) for x in a] == [(y.vid, y.proc) for y in b]
    for x, y in zip(a, b):
        assert y.time == pytest.approx(x.time, abs=1e-15)
        assert y.typical == pytest.approx(x.typical, abs=1e-12)


def test_analysis_layer_and_auto_backend_run_without_jax():
    """The jax-absent fallback path, end to end in a clean interpreter:
    importing the analysis layer and running both detectors with the
    default backend must never pull jax into the process."""
    code = textwrap.dedent("""
        import sys
        from repro.core import PSG, COMP, backtrack, detect_abnormal, \\
            detect_non_scalable
        from repro.core.detect import _resolve_backend
        from repro.core.inject import simulate, simulate_series
        assert "jax" not in sys.modules, "lazy analysis layer imported jax"
        assert _resolve_backend("auto") is None
        g = PSG()
        root = g.new_vertex("Root", "root")
        g.root = root.vid
        for i in range(4):
            v = g.new_vertex(COMP, f"c{i}", parent=root.vid)
            g.add_edge(root.vid, v.vid, "control")
        series = simulate_series(
            g, [2, 4, 8],
            lambda p, vid, n: 0.5 + 1.0 / n if vid == 1 else 1.0 / n)
        ns = detect_non_scalable(series)
        ab = detect_abnormal(series[8])
        paths = backtrack(series[8], ns, ab)
        assert ns and ns[0].vid == 1
        assert "jax" not in sys.modules, "detection pulled jax in"
        print("fallback-ok")
    """)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "fallback-ok" in out.stdout


# ---------------------------------------------------------------------------
# backtrack equivalence on the straggler scenario
# ---------------------------------------------------------------------------

def _straggler_scenario(n_procs=8):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    c0 = g.new_vertex(COMP, "load", parent=root.vid, source="app.py:10")
    p2p = g.new_vertex(COMM, "ppermute", parent=root.vid, source="app.py:30")
    p2p.comm_kind = "ppermute"
    p2p.p2p_pairs = [(i, (i + 1) % n_procs) for i in range(n_procs)]
    c2 = g.new_vertex(COMP, "solve", parent=root.vid, source="app.py:40")
    ar = g.new_vertex(COMM, "psum", parent=root.vid, source="app.py:50")
    ar.comm_kind, ar.comm_bytes = "all_reduce", 1e6
    for v in (c0, p2p, c2, ar):
        g.add_edge(root.vid, v.vid, "control")
    g.add_edge(c0.vid, p2p.vid, "data")
    g.add_edge(p2p.vid, c2.vid, "data")
    g.add_edge(c2.vid, ar.vid, "data")
    return g, c0.vid


def test_straggler_pipeline_end_to_end_deterministic():
    """detect + backtrack + root_causes on the injected-straggler scenario:
    the root cause is exactly the injected (proc, vertex), and a repeat run
    is node-for-node identical (index refactor kept walk order stable)."""
    g, c0 = _straggler_scenario()
    runs = []
    for _ in range(2):
        res = simulate(g, 8, lambda p, vid: 0.01, inject={(4, c0): 0.5})
        ab = detect_abnormal(res.ppg, abnorm_thd=1.3)
        paths = backtrack(res.ppg, [], ab)
        rcs = root_causes(paths, g, ppg=res.ppg)
        runs.append(([(a.proc, a.vid) for a in ab],
                     [p.nodes for p in paths], rcs))
    assert runs[0] == runs[1]
    ab_nodes, path_nodes, rcs = runs[0]
    assert any(node == (4, c0) for node, _, _ in rcs)


def test_anomaly_score_matches_scalar_reference():
    g, c0 = _straggler_scenario()
    res = simulate(g, 8, lambda p, vid: 0.01, inject={(4, c0): 0.5})
    ppg = res.ppg

    def ref_score(node):
        vec = ppg.perf.get(node)
        if vec is None:
            return 0.0

        def busy(p):
            v = ppg.perf.get((p, node[1]))
            if v is None:
                return 0.0
            return v.time - float(v.counters.get(WAIT_COUNTER, 0.0))

        mine = busy(node[0])
        others = sorted(b for p in range(ppg.n_procs)
                        if (b := busy(p)) > 0.0)
        if not others:
            return mine
        return mine - others[len(others) // 2]

    for vid in range(len(g.vertices)):
        for p in range(8):
            assert _anomaly_score(ppg, (p, vid)) == pytest.approx(
                ref_score((p, vid)), abs=1e-15)
