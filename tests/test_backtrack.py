"""Backtracking root-cause detection (Algorithm 1): the paper's core."""
import importlib

import numpy as np
import pytest

from repro.core import (COMM, COMP, PSG, backtrack, build_ppg,
                        detect_abnormal, detect_non_scalable, root_causes)
from repro.core.backtrack import WAIT_COUNTER, backtrack_one
from repro.core.detect import Abnormal
from repro.core.graph import PPG, PerfStore, PerfVector
from repro.core.inject import simulate, simulate_series
from repro.core.shard import ShardedStore

# the module itself: ``repro.core.backtrack`` as an attribute is the function
bt = importlib.import_module("repro.core.backtrack")


def _pipeline_psg():
    """comp0 -> comp1 -> p2p(0->1,2->3,...) -> comp2 -> allreduce."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    c0 = g.new_vertex(COMP, "load", parent=root.vid, source="app.py:10")
    c1 = g.new_vertex(COMP, "halo", parent=root.vid, source="app.py:20")
    p2p = g.new_vertex(COMM, "ppermute", parent=root.vid, source="app.py:30")
    p2p.comm_kind = "ppermute"
    p2p.comm_bytes = 1e5
    p2p.p2p_pairs = [(i, (i + 1) % 8) for i in range(8)]
    c2 = g.new_vertex(COMP, "solve", parent=root.vid, source="app.py:40")
    ar = g.new_vertex(COMM, "psum", parent=root.vid, source="app.py:50")
    ar.comm_kind, ar.comm_bytes = "all_reduce", 1e6
    for v in (c0, c1, p2p, c2, ar):
        g.add_edge(root.vid, v.vid, "control")
    g.add_edge(c0.vid, c1.vid, "data")
    g.add_edge(c1.vid, p2p.vid, "data")
    g.add_edge(p2p.vid, c2.vid, "data")
    g.add_edge(c2.vid, ar.vid, "data")
    return g, (c0.vid, c1.vid, p2p.vid, c2.vid, ar.vid)


def test_straggler_propagates_and_backtracks_to_root_cause():
    """The paper's NPB-CG experiment in miniature: a delay injected into one
    process propagates through p2p dependence and surfaces at the
    all-reduce; Algorithm 1 walks it back to the injected computation."""
    g, (c0, c1, p2p, c2, ar) = _pipeline_psg()
    res = simulate(g, 8, lambda p, vid: 0.01,
                   inject={(4, c0): 0.5})       # straggler: proc 4 at 'load'
    ab = detect_abnormal(res.ppg, abnorm_thd=1.3)
    assert ab, "propagated delay must create abnormal vertices"
    paths = backtrack(res.ppg, [], ab)
    assert paths
    rcs = root_causes(paths, g, ppg=res.ppg)
    assert any(node == (4, c0) for node, _, _ in rcs), \
        f"root cause must be (proc 4, load); got {rcs}"


def test_backtrack_prunes_nonwaiting_p2p():
    """p2p edges without waiting events are pruned (search-space opt)."""
    g, (c0, c1, p2p, c2, ar) = _pipeline_psg()
    perf = {p: {} for p in range(4)}
    for p in range(4):
        for vid in (c0, c1, c2):
            perf[p][vid] = PerfVector(time=0.01)
        # p2p with NO waiting
        perf[p][p2p] = PerfVector(time=0.001,
                                  counters={WAIT_COUNTER: 0.0})
        perf[p][ar] = PerfVector(time=0.001)
    ppg = build_ppg(g, 4, perf)
    path = backtrack_one(ppg, (0, c2), reason="abnormal", scanned=set())
    # must walk straight through data deps within proc 0, never jumping
    procs = {n[0] for n in path.nodes}
    assert procs == {0}


def test_backtrack_follows_waiting_p2p():
    g, (c0, c1, p2p, c2, ar) = _pipeline_psg()
    perf = {p: {} for p in range(4)}
    for p in range(4):
        for vid in (c0, c1, c2):
            perf[p][vid] = PerfVector(time=0.3 if (p, vid) == (1, c1)
                                      else 0.01)
        perf[p][p2p] = PerfVector(
            time=0.3 if p == 2 else 0.001,
            counters={WAIT_COUNTER: 0.29 if p == 2 else 0.0})
        perf[p][ar] = PerfVector(time=0.001)
    ppg = build_ppg(g, 4, perf)
    # proc 2 waited on the p2p; its cause is proc 1 (pairs 1->2)
    path = backtrack_one(ppg, (2, c2), reason="abnormal", scanned=set())
    procs = {n[0] for n in path.nodes}
    assert 1 in procs, f"walk must cross to proc 1: {path.nodes}"


def test_backtrack_terminates_and_covers_all_abnormal():
    g, ids = _pipeline_psg()
    res = simulate(g, 8, lambda p, vid: 0.01,
                   inject={(2, ids[0]): 0.3, (6, ids[3]): 0.2})
    ab = detect_abnormal(res.ppg)
    paths = backtrack(res.ppg, [], ab)
    # Algorithm 1 main loop: every abnormal vertex scanned or started from
    scanned = set()
    for p in paths:
        scanned.update(p.nodes)
    for a in ab:
        assert (a.proc, a.vid) in scanned
    for p in paths:
        assert len(p.nodes) <= 256            # termination bound


def _paths_key(paths):
    return [(p.nodes, p.start_reason) for p in paths]


def _random_psg(rng, n_procs):
    """Random PSG mixing comp chains, p2p rings, global and grouped
    collectives, loops and diamond data edges."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    prev = None
    for i in range(int(rng.integers(4, 12))):
        r = rng.random()
        if r < 0.35:
            v = g.new_vertex(COMP, f"c{i}", parent=root.vid)
        elif r < 0.5 and prev is not None:
            lp = g.new_vertex("Loop", f"loop{i}", parent=root.vid)
            g.add_edge(root.vid, lp.vid, "control")
            b0 = g.new_vertex(COMP, f"b{i}a", parent=lp.vid)
            b1 = g.new_vertex(COMP, f"b{i}b", parent=lp.vid)
            g.add_edge(b0.vid, b1.vid, "data")
            g.add_edge(prev, lp.vid, "data")
            prev = lp.vid
            continue
        elif r < 0.75:
            v = g.new_vertex(COMM, f"pp{i}", parent=root.vid)
            v.comm_kind, v.comm_bytes = "ppermute", 1e5
            off = int(rng.integers(1, max(n_procs, 2)))
            v.p2p_pairs = [(p, (p + off) % n_procs) for p in range(n_procs)]
        else:
            v = g.new_vertex(COMM, f"ar{i}", parent=root.vid)
            v.comm_kind, v.comm_bytes = "all_reduce", 1e6
            gs = int(rng.choice([2, 4, n_procs]))
            if gs < n_procs:
                v.meta["replica_groups"] = [
                    list(range(a, min(a + gs, n_procs)))
                    for a in range(0, n_procs, gs)]
        g.add_edge(root.vid, v.vid, "control")
        if prev is not None:
            g.add_edge(prev, v.vid, "data")
        if prev is not None and v.vid >= 3 and rng.random() < 0.3:
            g.add_edge(max(1, v.vid - 2), v.vid, "data")   # diamond
        prev = v.vid
    return g


def _main_copying(ppg, non_scalable, abnormal):
    """Algorithm 1 Main() over the copying reference walk: non-scalable
    vertices first, each from its slowest process, then the abnormal
    (proc, vertex) pairs no earlier path has scanned."""
    tm = ppg.times_matrix()
    starts = [((int(tm[:, n.vid].argmax()) if tm.size else 0, n.vid),
               "non_scalable") for n in non_scalable]
    starts += [((a.proc, a.vid), "abnormal") for a in abnormal]
    scanned, paths = set(), []
    for node, reason in starts:
        if reason == "abnormal" and node in scanned:
            continue
        p = _backtrack_one_copying(ppg, node, reason=reason,
                                   scanned=scanned)
        if p.nodes:
            paths.append(p)
    return paths


def test_backtrack_equals_copying_reference_on_random_ppgs():
    """``backtrack`` returns EXACTLY the copying reference Main()'s
    paths — overlapping starts, ties (jitter-free waits), grouped and
    global collectives, p2p chains, loops and diamonds included."""
    rng = np.random.default_rng(42)
    for trial in range(40):
        n_procs = int(rng.integers(4, 28))
        g = _random_psg(rng, n_procs)
        inj = {}
        for _ in range(int(rng.integers(1, 7))):
            inj[(int(rng.integers(0, n_procs)),
                 int(rng.integers(1, len(g.vertices))))] = \
                float(rng.uniform(0.05, 0.5))
        # every other trial jitter-free: exact ties stress the stable
        # first-min/first-max ordering
        res = simulate(g, n_procs, lambda p, vid: 0.01, inject=inj,
                       jitter=0.1 if trial % 2 else 0.0, seed=trial)
        ab = detect_abnormal(res.ppg, top_k=500)
        series = simulate_series(g, [max(n_procs // 2, 2), n_procs],
                                 lambda p, vid, n: 0.02 * (0.5 + 0.5 / n),
                                 seed=trial)
        ns = detect_non_scalable(series, min_share=0.0, top_k=20)
        assert _paths_key(backtrack(res.ppg, ns, ab)) == \
            _paths_key(_main_copying(res.ppg, ns, ab)), trial


def test_backtrack_equals_copying_reference_overlapping_straggler_block():
    """Many starts flagged at the SAME vertices: the shared scanned set
    must prune later paths exactly as the sequential reference does."""
    rng = np.random.default_rng(7)
    for trial in range(10):
        n_procs = 12
        g = _random_psg(rng, n_procs)
        vid = int(rng.integers(1, len(g.vertices)))
        inj = {(p, vid): 0.3 for p in range(0, n_procs, 2)}
        res = simulate(g, n_procs, lambda p, vid_: 0.01, inject=inj,
                       seed=trial)
        ab = detect_abnormal(res.ppg, top_k=500)
        assert _paths_key(backtrack(res.ppg, [], ab)) == \
            _paths_key(_main_copying(res.ppg, [], ab)), trial


def test_backtrack_export_survives_submodule_import():
    # a direct `import repro.core.backtrack` (as repro.scenarios.bank does)
    # must not shadow the package-level function export with the submodule
    import importlib
    import sys

    importlib.import_module("repro.core.backtrack")
    from repro.core import backtrack as fn
    assert callable(fn) and fn is sys.modules["repro.core.backtrack"].backtrack


def test_non_scalable_plus_backtrack_end_to_end():
    g, (c0, c1, p2p, c2, ar) = _pipeline_psg()

    def time_at(p, vid, n):
        if vid == c1:
            return 0.1 * (0.7 + 0.3 / n) + (0.2 if p == 1 else 0.0)
        if g.vertices[vid].kind == COMM:
            return 0.0
        return 0.1 / n

    series = simulate_series(g, [4, 8, 16], time_at)
    ns = detect_non_scalable(series, min_share=0.01)
    assert ns
    ab = detect_abnormal(series[16])
    paths = backtrack(series[16], ns, ab)
    assert paths
    rcs = root_causes(paths, g, ppg=series[16])
    assert rcs


# ---------------------------------------------------------------------------
# backtrack_one: non-copying scanned-union view (regression)
# ---------------------------------------------------------------------------

def _latest_participant_scalar(ppg, node):
    """The scalar reference: ``min`` over ``comm_partners + [node]`` by
    each candidate's own ``counter_at`` read (first minimum wins)."""
    group = ppg.comm_partners(*node) + [node]
    if len(group) <= 1:
        return None
    return min(group, key=lambda n: bt._wait_of(ppg, n))


def _backtrack_one_copying(ppg, start, *, reason, scanned, max_len=256):
    """The pre-fix reference walk: rebuilds ``scanned | set(path)`` on
    every step and picks a collective's latest participant by scalar
    reads.  Retained here to pin the walk to the old semantics
    exactly."""
    from repro.core.backtrack import (Path, WAIT_EPS, _comm_partner,
                                      _control_end, _data_pred,
                                      _is_collective, _is_p2p, _wait_of)
    from repro.core.graph import BRANCH, CALL, LOOP
    psg = ppg.psg
    path = []
    v = start
    first = True
    while v is not None and len(path) < max_len:
        proc, vid = v
        vert = psg.vertices[vid]
        if vert.kind == "Root":
            break
        if _is_collective(psg, vid) and not first:
            path.append(v)
            break
        path.append(v)
        nxt = None
        visited = scanned | set(path)            # the quadratic copy
        if _is_collective(psg, vid):
            late = _latest_participant_scalar(ppg, v)
            if late is not None and late not in visited:
                nxt = _data_pred(ppg, late, visited) or late
            else:
                nxt = _data_pred(ppg, v, visited)
        elif _is_p2p(psg, vid):
            if _wait_of(ppg, v) > WAIT_EPS:
                nxt = _comm_partner(ppg, v, visited)
            if nxt is None:
                nxt = _data_pred(ppg, v, visited)
        elif vert.kind in (LOOP, BRANCH, CALL) and v not in scanned:
            nxt = _control_end(ppg, v, visited) or _data_pred(ppg, v,
                                                              visited)
        else:
            nxt = _data_pred(ppg, v, visited)
        first = False
        v = nxt
    scanned.update(path)
    return Path(nodes=path, start_reason=reason)


def test_backtrack_one_union_view_matches_copying_reference():
    """The union-view walk must equal the old per-step-copy walk node for
    node — including evolving shared scanned sets across many starts on
    conflict-heavy random PPGs."""
    rng = np.random.default_rng(11)
    for trial in range(20):
        n_procs = int(rng.integers(4, 20))
        g = _random_psg(rng, n_procs)
        vid = int(rng.integers(1, len(g.vertices)))
        inj = {(p, vid): 0.2 + 0.01 * p for p in range(0, n_procs, 2)}
        for _ in range(int(rng.integers(0, 5))):
            inj[(int(rng.integers(0, n_procs)),
                 int(rng.integers(1, len(g.vertices))))] = \
                float(rng.uniform(0.05, 0.5))
        res = simulate(g, n_procs, lambda p, v: 0.01, inject=inj,
                       seed=trial)
        ab = detect_abnormal(res.ppg, top_k=500)
        scanned_new, scanned_ref = set(), set()
        for a in ab:
            got = backtrack_one(res.ppg, (a.proc, a.vid),
                                reason="abnormal", scanned=scanned_new)
            ref = _backtrack_one_copying(res.ppg, (a.proc, a.vid),
                                         reason="abnormal",
                                         scanned=scanned_ref)
            assert got.nodes == ref.nodes, trial
        assert scanned_new == scanned_ref


# ---------------------------------------------------------------------------
# collective start: the latest participant by one gathered wait column
# ---------------------------------------------------------------------------

def _collective_ppg(rng, store):
    """A PPG over ``n`` processes with four collective vertices: ``a``
    carries overlapping groups (one of them a lone repeated process, which
    leaves no peers) that some processes are in none of, ``b`` one group,
    both with p2p reverse edges from ``a``, ``b`` or ``c``; ``c`` has no
    ``wait_s`` column at all, ``d`` one group whose waits are all unset.
    Waits come from {0.0, 0.1, 0.2} on a random subset: ties throughout."""
    n = int(rng.integers(6, 40))
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    vids = []
    for name in "abcd":
        v = g.new_vertex(COMM, name, parent=root.vid)
        v.comm_kind = "all_reduce"
        vids.append(v.vid)
    a, b, c, d = vids
    if store == "plain":
        perf = PerfStore(n, len(g.vertices))
    else:                                   # uneven shard ranges
        cuts = sorted(rng.choice(np.arange(1, n), size=int(rng.integers(
            1, min(n - 1, 8))), replace=False).tolist())
        perf = ShardedStore(list(zip([0] + cuts, cuts + [n])),
                            len(g.vertices))
    ppg = PPG(g, n, perf=perf)
    for _ in range(int(rng.integers(2, 5))):
        k = int(rng.integers(2, n))
        ppg.add_collective_edges(a, rng.choice(n, size=k, replace=False))
    lone = int(rng.integers(0, n))
    ppg.add_collective_edges(a, [lone, lone])
    ppg.add_collective_edges(b, range(n))
    for _ in range(int(rng.integers(1, 6))):
        src = (int(rng.integers(0, n)), int(rng.choice([a, b, c])))
        dst = (int(rng.integers(0, n)), int(rng.choice([a, b])))
        ppg.add_p2p_edge(*src, *dst)
    ppg.add_collective_edges(c, rng.permutation(n))
    ppg.add_collective_edges(d, range(0, n, 2))
    for vid in vids:
        perf.set_column(vid, 0.01)
    for vid in (a, b, d):
        procs = rng.choice(n, size=int(rng.integers(0, n)), replace=False)
        waits = rng.choice([0.0, 0.1, 0.2], size=procs.size)
        if vid == d:
            procs, waits = np.zeros(0, np.intp), np.zeros(0)
        perf.set_entries(procs, vid, 0.01, counters={WAIT_COUNTER: waits})
    perf.set_entries([0], a, 0.01, counters={"other": 1.0})
    return ppg, vids


@pytest.mark.parametrize("store", ["plain", "sharded"])
def test_latest_participant_equals_scalar_min(store):
    """The gathered first minimum equals ``min`` over the scalar reads,
    node for node, and counts the participants it gathered: the group
    peers and the node itself, 0 where there is no partner (``None``)."""
    rng = np.random.default_rng(18)
    nones = 0
    for trial in range(30):
        ppg, vids = _collective_ppg(rng, store)
        for vid in vids:
            for p in range(ppg.n_procs):
                node = (p, vid)
                ref = _latest_participant_scalar(ppg, node)
                late, n_read = bt._latest_participant(ppg, node)
                assert late == ref, (trial, node)
                nones += ref is None
                want = 0 if ref is None else (
                    len(ppg.comm_partners(*node))
                    - len(ppg.comm.p2p_preds_of(node)) + 1)
                assert n_read == want, (trial, node)
    assert nones                       # ungrouped and lone processes


def test_backtrack_straggler_at_all_reduce_on_512_procs_128_shards(
        monkeypatch):
    """End to end on a sharded fleet: a straggler everyone waits on at
    the all-reduce.  The walks equal those that pick the latest
    participant by scalar reads, the all-reduce start gathers all 512
    waits, and a walk that reaches no collective start gathers none."""
    g, (c0, c1, p2p, c2, ar) = _pipeline_psg()
    res = simulate(g, 512, lambda p, vid: 0.01, inject={(300, c2): 0.4},
                   shards=128)
    ppg = res.ppg
    assert isinstance(ppg.perf, ShardedStore)
    assert len(ppg.perf.shards) == 128
    ab = detect_abnormal(ppg, top_k=50)
    starts = [Abnormal(vid=ar, proc=7, time=0.0, typical=0.0, ratio=1.0)]
    starts += ab
    got = backtrack(ppg, [], starts)
    assert got[0].nodes[:2] == [(7, ar), (300, c2)]
    assert got[0].participants == 512
    assert sum(p.participants for p in got) == 512
    assert backtrack_one(ppg, (5, c2), reason="abnormal",
                         scanned=set()).participants == 0
    monkeypatch.setattr(bt, "_latest_participant", lambda ppg_, node: (
        _latest_participant_scalar(ppg_, node), 0))
    assert _paths_key(backtrack(ppg, [], starts)) == _paths_key(got)
