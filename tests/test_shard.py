"""Sharded perf store + streamed multi-host assembly.

Pins the tentpole refactor to the old single-controller semantics:

* ``PerfStore.from_shards`` / ``assemble_streamed`` must be bit-identical
  to writing the same entries into one store through ``set_entries``
  directly — including uneven shard proc-ranges, disjoint counter sets,
  per-row counter signatures and overlapping shards;
* ``ShardedStore``-backed replay (``simulate(..., shards=...)``) must be
  bit-identical to the unsharded replay, and its stacked read views must
  equal the merged store's matrices;
* the cross-scale stacked collective leg must be bit-identical to the
  retained per-lane reference;
* ``build_ppg`` must accept shard iterables (streamed, one at a time).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (COMM, COMP, PSG, PerfShard, PerfStore, ShardedStore,
                        build_ppg, detect_abnormal, shard_ranges)
from repro.core.graph import PerfVector
from repro.core.inject import (_collective, _collective_stacked, _make_lane,
                               simulate, simulate_series)

COUNTER_SETS = [(), ("wait_s",), ("flops", "bytes"), ("wait_s", "comm_bytes"),
                ("flops",)]


# ---------------------------------------------------------------------------
# shard-merge == direct set_entries assembly
# ---------------------------------------------------------------------------

@st.composite
def entry_plan(draw):
    """Random (n_procs, ranges, entries): entries are (proc, vid,
    counter-set-index) triples with deterministic values derived below."""
    n_procs = draw(st.integers(3, 24))
    n_hosts = draw(st.integers(1, 5))
    uneven = draw(st.booleans())
    if uneven:
        # uneven ranges: random cut points
        cuts = sorted({draw(st.integers(1, n_procs - 1))
                       for _ in range(n_hosts - 1)} | {0, n_procs})
        ranges = list(zip(cuts, cuts[1:]))
    else:
        ranges = shard_ranges(n_procs, n_hosts)
    n_entries = draw(st.integers(0, 40))
    entries = [(draw(st.integers(0, n_procs - 1)), draw(st.integers(0, 9)),
                draw(st.integers(0, len(COUNTER_SETS) - 1)))
               for _ in range(n_entries)]
    return n_procs, ranges, entries


def _value(p, vid, i):
    return 0.25 + 0.125 * p + 17.0 * vid + 0.0625 * i


def _apply(store, entries, off=0):
    """Write (global_index, (proc, vid, counter-set)) entries through
    set_entries, one call per entry (the reference single-store assembly;
    proc indices shifted by -off).  Values derive from the GLOBAL entry
    index so shard-local and direct writes agree."""
    for i, (p, vid, ci) in entries:
        names = COUNTER_SETS[ci]
        store.set_entries(
            np.asarray([p - off]), vid, _value(p, vid, i),
            time_var=0.5 * _value(p, vid, i), samples=1 + (i % 3),
            counters={nm: _value(p, vid, i) + 100.0 * j
                      for j, nm in enumerate(names)})


def _stores_equal(a, b, V=12):
    assert np.array_equal(a.time_matrix(V), b.time_matrix(V))
    assert np.array_equal(a.var_matrix(V), b.var_matrix(V))
    names = set(a.counter_names()) | set(b.counter_names())
    for nm in names:
        assert np.array_equal(a.counter_matrix(nm, V),
                              b.counter_matrix(nm, V)), nm
    keys_a = sorted((p, v) for p, v in a.keys())
    keys_b = sorted((p, v) for p, v in b.keys())
    assert keys_a == keys_b
    for key in keys_a:
        assert a[key] == b[key], key


@given(entry_plan())
@settings(max_examples=40, deadline=None)
def test_from_shards_equals_direct_assembly(plan):
    n_procs, ranges, entries = plan
    entries = list(enumerate(entries))
    direct = PerfStore(n_procs)
    _apply(direct, entries)
    shards = []
    for lo, hi in ranges:
        sh = PerfShard(lo, hi - lo)
        _apply(sh, [(i, e) for i, e in entries if lo <= e[0] < hi], off=lo)
        shards.append(sh)
    merged = PerfStore.from_shards(shards, n_procs=n_procs)
    _stores_equal(merged, direct)
    # streamed (iterator) form: one shard at a time, same result
    streamed = PerfStore.assemble_streamed(iter(shards))
    _stores_equal(streamed, direct)


def test_from_shards_disjoint_counter_sets_and_uneven_ranges():
    """Hosts that measured entirely different counters still merge into
    one column-sparse store equal to direct assembly."""
    direct = PerfStore(7)
    a = PerfShard(0, 2)      # [0, 2): wait_s only
    b = PerfShard(2, 5)      # [2, 7): flops only, different vertices
    for p in (0, 1):
        direct.set_entries([p], 3, 1.0 + p, counters={"wait_s": 0.5 * p})
        a.set_entries([p], 3, 1.0 + p, counters={"wait_s": 0.5 * p})
    for p in (2, 4, 6):
        direct.set_entries([p], 5, 2.0 + p, counters={"flops": 1e9 * p})
        b.set_entries([p - 2], 5, 2.0 + p, counters={"flops": 1e9 * p})
    merged = PerfStore.from_shards([a, b])
    assert merged.n_procs == 7
    _stores_equal(merged, direct)
    assert sorted(merged.counter_names()) == ["flops", "wait_s"]


def test_from_shards_overlap_last_writer_wins():
    """Overlapping ranges behave like repeated set_entries calls: the
    later shard overwrites."""
    a = PerfShard(0, 4)
    b = PerfShard(2, 4)
    a.set_entries(np.arange(4), 1, 1.0)
    b.set_entries(np.arange(4), 1, 2.0)
    merged = PerfStore.from_shards([a, b])
    assert merged.n_procs == 6
    np.testing.assert_array_equal(merged.time_column(1),
                                  [1.0, 1.0, 2.0, 2.0, 2.0, 2.0])


def test_shard_ranges_tile():
    assert shard_ranges(10, 3) == [(0, 4), (4, 7), (7, 10)]
    assert shard_ranges(8, 8) == [(i, i + 1) for i in range(8)]
    assert shard_ranges(4, 16) == [(i, i + 1) for i in range(4)]
    with pytest.raises(ValueError):
        shard_ranges(8, 0)


def test_shard_ranges_zero_procs_rejected():
    """Regression: shard_ranges(0, h) used to return [(0, 0)], which
    ShardedStore then rejected with a confusing contiguity error — the
    two now agree: sharding zero processes is an explicit ValueError at
    both layers, and in ``simulate(..., shards=)``."""
    with pytest.raises(ValueError, match="0 processes"):
        shard_ranges(0, 4)
    with pytest.raises(ValueError):
        ShardedStore([(0, 0)])
    with pytest.raises(ValueError):
        simulate(_pipeline_psg(4), 0, lambda p, vid: 0.01, shards=2)


def test_build_ppg_empty_shard_iterable():
    """No hosts reported yet: streamed assembly of an empty iterable is a
    valid (empty) n_procs-row store, and detection runs clean on it."""
    g = _pipeline_psg(4)
    ppg = build_ppg(g, 4, iter([]))
    assert isinstance(ppg.perf, PerfStore)
    assert ppg.perf.n_procs == 4 and len(ppg.perf) == 0
    assert ppg.times_matrix().shape == (4, len(g.vertices))
    assert detect_abnormal(ppg, backend="numpy") == []


# ---------------------------------------------------------------------------
# contiguous-block merge fast path == grouped reference
# ---------------------------------------------------------------------------

def _grouped_merge(shards, n_procs):
    """Reference assembly through the retained per-(vertex, signature)
    path only (the pre-fast-path behavior)."""
    store = PerfStore(n_procs)
    for sh in shards:
        store.ensure_rows(sh.proc_start + sh.n_procs)
        store.ensure_columns(sh._cols)
        store._merge_shard_grouped(sh, sh.proc_start)
    return store


@given(entry_plan())
@settings(max_examples=40, deadline=None)
def test_merge_block_fast_path_equals_grouped(plan):
    """Fresh-target merges take the whole-block fast path; it must be
    bit-identical to the grouped set_entries reference on uneven ranges,
    disjoint counter sets and per-row signatures."""
    n_procs, ranges, entries = plan
    entries = list(enumerate(entries))
    shards = []
    for lo, hi in ranges:
        sh = PerfShard(lo, hi - lo)
        _apply(sh, [(i, e) for i, e in entries if lo <= e[0] < hi], off=lo)
        shards.append(sh)
    fast = PerfStore.from_shards(shards, n_procs=n_procs)
    slow = _grouped_merge(shards, n_procs)
    _stores_equal(fast, slow)
    np.testing.assert_array_equal(fast._mask, slow._mask[:, :fast._cols])
    assert sorted(fast.dirty_rows()) == sorted(slow.dirty_rows())


def test_merge_block_fast_path_disjoint_counters_uneven_ranges():
    a = PerfShard(0, 3)      # wait_s only, vids {1, 5}
    b = PerfShard(3, 2)      # flops only, vid 2; row signatures differ
    a.set_entries([0, 2], 1, 1.5, counters={"wait_s": [0.1, 0.2]})
    a.set_entries([1], 5, 2.5, counters={"wait_s": 0.3})
    b.set_entries([0], 2, 3.5, counters={"flops": 1e9})
    b.set_entries([1], 2, 4.5)                   # same vid, no counter
    fast = PerfStore.from_shards([a, b])
    slow = _grouped_merge([a, b], 5)
    _stores_equal(fast, slow, V=6)
    # overlap forces the grouped fallback and stays last-writer-wins
    c = PerfShard(2, 2)
    c.set_entries([0, 1], 1, 9.0)
    fast.merge_shard(c)
    slow.merge_shard(c)
    _stores_equal(fast, slow, V=6)
    np.testing.assert_array_equal(fast.time_column(1),
                                  [1.5, 0.0, 9.0, 9.0, 0.0])


# ---------------------------------------------------------------------------
# sharded build_ppg (device-resident detection threading)
# ---------------------------------------------------------------------------

def test_build_ppg_sharded_keeps_blocks():
    """``sharded=True`` adopts per-host shards AS the ShardedStore blocks
    (no merge), producing the same detection as the merged store."""
    g = _pipeline_psg(6)
    res = simulate(g, 6, lambda p, vid: 0.01, inject={(2, 1): 0.4},
                   shards=3)
    ppg = build_ppg(g, 6, list(res.shards), sharded=True)
    assert isinstance(ppg.perf, ShardedStore)
    assert ppg.perf.shards[0] is res.shards[0]   # adopted, not copied
    merged = build_ppg(g, 6, iter(res.shards))
    assert np.array_equal(ppg.times_matrix(), merged.times_matrix())
    assert [(x.proc, x.vid) for x in detect_abnormal(ppg, backend="numpy")] \
        == [(x.proc, x.vid) for x in detect_abnormal(merged,
                                                     backend="numpy")]
    # hosts may report out of order: blocks are sorted by range
    shuffled = build_ppg(g, 6, [res.shards[2], res.shards[0],
                                res.shards[1]], sharded=True)
    assert np.array_equal(shuffled.times_matrix(), merged.times_matrix())
    with pytest.raises(ValueError):              # ranges must tile n_procs
        build_ppg(g, 8, list(res.shards), sharded=True)
    with pytest.raises(ValueError):              # gap in the tiling
        build_ppg(g, 6, [PerfShard(0, 2), PerfShard(4, 2)], sharded=True)
    with pytest.raises(ValueError):              # not a shard iterable
        build_ppg(g, 6, {0: {1: PerfVector(time=0.1)}}, sharded=True)
    with pytest.raises(ValueError):              # already-merged store
        build_ppg(g, 6, PerfStore(6), sharded=True)
    with pytest.raises(ValueError):              # no perf data at all
        build_ppg(g, 6, None, sharded=True)
    with pytest.raises(ValueError):              # ready store, wrong size
        build_ppg(g, 8, ppg.perf, sharded=True)
    with pytest.raises(ValueError):              # same check, sharded=False
        build_ppg(g, 8, ppg.perf)


# ---------------------------------------------------------------------------
# ShardedStore: routed writes + stacked views == plain store
# ---------------------------------------------------------------------------

@given(entry_plan(), st.booleans())
@settings(max_examples=25, deadline=None)
def test_sharded_store_routes_like_plain_store(plan, accumulate):
    n_procs, ranges, entries = plan
    plain = PerfStore(n_procs)
    sharded = ShardedStore(ranges)
    for i, (p, vid, ci) in enumerate(entries):
        names = COUNTER_SETS[ci]
        kw = dict(time_var=0.25 * i, samples=1 + (i % 2),
                  counters={nm: _value(p, vid, i) for nm in names},
                  accumulate=accumulate)
        plain.set_entries([p], vid, _value(p, vid, i), **kw)
        sharded.set_entries([p], vid, _value(p, vid, i), **kw)
    _stores_equal(sharded, plain)
    _stores_equal(sharded.merge(), plain)
    # stacked counter_columns view == plain columns at the shared vids
    for nm in plain.counter_names():
        vp, valp, mp = plain.counter_columns(nm)
        vs, vals, ms = sharded.counter_columns(nm)
        order_p, order_s = np.argsort(vp), np.argsort(vs)
        assert np.array_equal(vp[order_p], vs[order_s])
        assert np.array_equal(valp[:, order_p] * mp[:, order_p],
                              vals[:, order_s] * ms[:, order_s])
        assert np.array_equal(mp[:, order_p], ms[:, order_s])


# ---------------------------------------------------------------------------
# ShardedStore: resident stacked reads == a fresh stack after every seam
# ---------------------------------------------------------------------------

def _seeded_store():
    """Four uneven shards over 12 procs and 12 columns; ``bytes`` lives
    at column 11 only in shard 0 and at column 9 in shard 3, so replacing
    shard 0 with a block that holds it at 9 shrinks the stacked union of
    its columns."""
    store = ShardedStore([(0, 3), (3, 4), (4, 9), (9, 12)], 12)
    procs = np.arange(12)
    for vid in range(1, 8):
        store.set_entries(procs, vid, 0.01 * vid + 0.001 * procs,
                          time_var=1e-4 * vid, samples=2,
                          counters={"flops": 10.0 * vid} if vid < 4 else {})
    store.set_entries(procs, 10, 0.5 + 0.01 * procs,
                      counters={"wait_s": 0.1 + 0.01 * procs})
    store.set_entries([0, 2], 11, 0.75, counters={"bytes": [64.0, 32.0]})
    store.set_entries([10], 9, 0.5, counters={"bytes": 16.0})
    return store


def _fresh_reads(store):
    """Today's reads the stack-everything way: ``np.vstack`` of every
    shard's block, and the union of the shards' counter columns."""
    n = store._cols
    out = {"time": np.vstack([sh.time_matrix(n) for sh in store.shards]),
           "var": np.vstack([sh.var_matrix(n) for sh in store.shards])}
    for name in store.counter_names() + ["absent"]:
        out[name] = np.vstack([sh.counter_matrix(name, n)
                               for sh in store.shards])
        per = [sh.counter_columns(name) for sh in store.shards]
        vids = np.unique(np.concatenate([v for v, _, _ in per]))
        values = np.zeros((store.n_procs, vids.size))
        mask = np.zeros((store.n_procs, vids.size), bool)
        for sh, (v, val, m) in zip(store.shards, per):
            slots = np.searchsorted(vids, v)
            values[sh.proc_start:sh.proc_stop, slots] = val
            mask[sh.proc_start:sh.proc_stop, slots] = m
        out[name + ".columns"] = (vids, values, mask)
    return out


def _store_reads(store):
    out = {"time": store.time_matrix(), "var": store.var_matrix()}
    for name in store.counter_names() + ["absent"]:
        out[name] = store.counter_matrix(name)
        out[name + ".columns"] = store.counter_columns(name)
    return out


def _reads_equal(got, want):
    assert set(got) == set(want)
    for key, w in want.items():
        g = got[key]
        for a, b in zip(g if isinstance(g, tuple) else (g,),
                        w if isinstance(w, tuple) else (w,)):
            assert a.dtype == b.dtype and np.array_equal(a, b), key


def _check_reads(store):
    got = _store_reads(store)
    _reads_equal(got, _fresh_reads(store))
    for key, arrays in got.items():
        for a in arrays if isinstance(arrays, tuple) else (arrays,):
            assert not a.flags.writeable, key
            with pytest.raises(ValueError):
                a[...] = 0


def _replace_shard(store, step):
    """Shard 0 restored from another block's tree: new values, and
    ``bytes`` at column 9 in place of 11."""
    other = PerfShard(0, 3, 12)
    other.set_column(9, 3.0 + step, counters={"bytes": 7.0})
    other.set_column(10, 0.25, counters={"wait_s": 0.05})
    store.shards[0].load_tree(*other.to_tree())


def _apply_host_rows(store, step):
    """A host's rows applied straight to its shard (the diagnosis
    write in `drivers/diagnose.py`), with a counter column the shard
    never held."""
    sh = store.shards[2]
    blk = sh.extract_rows([1, 3])
    blk.time[:, 4] += 1.0 + step
    blk.counters["wait_s"] = (np.asarray([10, 5]), np.full((2, 2), 0.3),
                              np.ones((2, 2), bool))
    sh.apply_rows(blk)


STACK_SEAMS = {
    "set_column": lambda s, k: s.set_column(
        2, 0.1 * k + np.arange(12.0), counters={"flops": 5.0 + k}),
    "set_column_procs": lambda s, k: s.set_column(
        3, [1.0 + k, 2.0], procs=np.asarray([1, 9]),
        counters={"wait_s": [0.2, 0.3]}),
    "set_entries": lambda s, k: s.set_entries(
        [0, 5, 5, 11], 4, [1.0, 2.0 + k, 3.0, 4.0],
        counters={"flops": 1.0 + k}),
    "set_entries_accumulate": lambda s, k: s.set_entries(
        [3, 5, 5], 10, 0.5 + k, counters={"wait_s": 0.25},
        accumulate=True),
    "set_entry": lambda s, k: s.set_entry(
        7, 1, 2.5 + k, time_var=0.5, counters={"wait_s": 0.125}),
    "setitem": lambda s, k: s.__setitem__(
        (4, 6), PerfVector(time=1.5 + k, counters={"bytes": 8.0})),
    "apply_rows": _apply_host_rows,
    "ensure_columns": lambda s, k: s.ensure_columns(s._cols + 1),
    "new_counter_column": lambda s, k: s.set_entries(
        [6], 8 + k, 0.5, counters={"wait_s": 0.4}),
    "load_tree": _replace_shard,
}


@pytest.mark.parametrize("seam", sorted(STACK_SEAMS))
def test_stacked_reads_equal_fresh_stack_after_each_write_seam(seam):
    """Every write seam moves a shard's generation, so the resident
    stacked reads re-copy it: they equal a fresh stack, are read-only,
    and never change under a caller holding an earlier read — also
    after a device view's refresh cleared the dirty flags the write
    set."""
    from repro.core.shard import DeviceShardView
    write = STACK_SEAMS[seam]
    store = _seeded_store()
    held, before = _store_reads(store), _fresh_reads(store)
    write(store, 0)
    _check_reads(store)
    _reads_equal(held, before)
    del held
    view = DeviceShardView(store)
    view.refresh()
    write(store, 1)
    view.refresh()
    assert not any(sh.dirty_rows().size for sh in store.shards)
    _check_reads(store)


def test_sharded_store_requires_contiguous_ranges():
    with pytest.raises(ValueError):
        ShardedStore([(0, 2), (3, 5)])
    with pytest.raises(ValueError):
        ShardedStore([])
    with pytest.raises(ValueError):
        ShardedStore([(0, 2), (2, 2)])


def test_simulate_rejects_partial_shard_ranges():
    """Explicit ranges must tile [0, n_procs) — a partial tiling would
    silently drop processes from the perf store."""
    g = _pipeline_psg(8)
    for bad in ([(0, 4)], [(0, 4), (4, 16)], []):
        with pytest.raises(ValueError):
            simulate(g, 8, lambda p, vid: 0.01, shards=bad)
    ok = simulate(g, 8, lambda p, vid: 0.01, shards=[(0, 5), (5, 8)])
    assert [s.n_procs for s in ok.shards] == [5, 3]


# ---------------------------------------------------------------------------
# multi-host replay == single-host replay
# ---------------------------------------------------------------------------

def _pipeline_psg(n_procs):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    c0 = g.new_vertex(COMP, "load", parent=root.vid, source="app.py:10")
    p2p = g.new_vertex(COMM, "ppermute", parent=root.vid, source="app.py:30")
    p2p.comm_kind, p2p.comm_bytes = "ppermute", 1e5
    p2p.p2p_pairs = [(i, (i + 1) % n_procs) for i in range(n_procs)]
    c2 = g.new_vertex(COMP, "solve", parent=root.vid, source="app.py:40")
    ar = g.new_vertex(COMM, "psum", parent=root.vid, source="app.py:50")
    ar.comm_kind, ar.comm_bytes = "all_reduce", 1e6
    half = n_procs // 2 or 1
    ar.meta["replica_groups"] = [list(range(half)),
                                 list(range(half, n_procs))]
    for v in (c0, p2p, c2, ar):
        g.add_edge(root.vid, v.vid, "control")
    g.add_edge(c0.vid, p2p.vid, "data")
    g.add_edge(p2p.vid, c2.vid, "data")
    g.add_edge(c2.vid, ar.vid, "data")
    return g


@given(st.integers(4, 24), st.integers(1, 6), st.booleans())
@settings(max_examples=20, deadline=None)
def test_sharded_simulate_bit_identical(n_procs, n_hosts, jitter):
    g = _pipeline_psg(n_procs)
    kw = dict(inject={(1, 1): 0.4}, jitter=0.05 if jitter else 0.0, seed=3)
    ref = simulate(g, n_procs, lambda p, vid: 0.01, **kw)
    res = simulate(g, n_procs, lambda p, vid: 0.01, shards=n_hosts, **kw)
    assert res.shards is not None
    assert len(res.shards) == min(n_hosts, n_procs)
    assert ref.clocks == res.clocks
    V = len(g.vertices)
    assert np.array_equal(ref.ppg.times_matrix(), res.ppg.times_matrix())
    assert np.array_equal(ref.ppg.var_matrix(), res.ppg.var_matrix())
    for nm in ("wait_s", "comm_bytes", "flops", "bytes"):
        assert np.array_equal(ref.ppg.counter_matrix(nm),
                              res.ppg.counter_matrix(nm)), nm
    # the sharded PPG drives detection identically (stacked shard views)
    ab_ref = detect_abnormal(ref.ppg, backend="numpy")
    ab_sh = detect_abnormal(res.ppg, backend="numpy")
    assert [(a.proc, a.vid, a.time) for a in ab_ref] == \
           [(a.proc, a.vid, a.time) for a in ab_sh]
    # merged blocks == the unsharded store
    _stores_equal(PerfStore.from_shards(res.shards), ref.ppg.perf, V)


def test_build_ppg_accepts_shard_iterable():
    """Per-host shards stream into build_ppg one at a time."""
    g = _pipeline_psg(6)
    res = simulate(g, 6, lambda p, vid: 0.01, shards=3)
    ppg = build_ppg(g, 6, iter(res.shards))
    assert isinstance(ppg.perf, PerfStore)
    assert np.array_equal(ppg.times_matrix(), res.ppg.times_matrix())
    assert np.array_equal(ppg.counter_matrix("wait_s"),
                          res.ppg.counter_matrix("wait_s"))


def test_sharded_ppg_mapping_api_and_report():
    """Mapping reads + render_report work on a sharded store."""
    from repro.core import backtrack, render_report
    g = _pipeline_psg(8)
    res = simulate(g, 8, lambda p, vid: 0.01, inject={(4, 1): 0.5}, shards=4)
    ab = detect_abnormal(res.ppg)
    paths = backtrack(res.ppg, [], ab)
    text = render_report(res.ppg, [], ab, paths)
    assert "Root causes" in text
    vec = res.ppg.perf.get((4, 1))
    assert vec is not None and vec.time > 0.4
    assert (4, 1) in res.ppg.perf


# ---------------------------------------------------------------------------
# cross-scale stacked collective == per-lane reference
# ---------------------------------------------------------------------------

@given(st.integers(2, 5), st.integers(2, 16), st.booleans())
@settings(max_examples=25, deadline=None)
def test_collective_stacked_equals_per_lane(S, n_max, grouped):
    """One cross-scale masked max == the retained per-scale reference,
    bitwise, for global and grouped collectives at uneven scales."""
    rng = np.random.default_rng(S * 100 + n_max)
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    v = g.new_vertex(COMM, "psum", parent=root.vid)
    v.comm_kind, v.comm_bytes = "all_reduce", 1e6
    if grouped:
        half = n_max // 2 or 1
        v.meta["replica_groups"] = [list(range(half)),
                                    list(range(half, n_max))]
    ns = sorted(int(x) for x in rng.integers(2, n_max + 1, size=S))
    P_max = max(ns)
    clocks_a = rng.uniform(0.0, 1.0, (S, P_max))
    clocks_b = clocks_a.copy()
    lanes_a = [_make_lane(g, n, lambda p, vid: 0.0, 0, None, clocks_a[i])
               for i, n in enumerate(ns)]
    lanes_b = [_make_lane(g, n, lambda p, vid: 0.0, 0, None, clocks_b[i])
               for i, n in enumerate(ns)]
    from repro.core.inject import default_comm_time
    _collective_stacked(lanes_a, clocks_a, v, v.vid, default_comm_time)
    for lane in lanes_b:
        _collective(lane, v, v.vid, default_comm_time)
    assert np.array_equal(clocks_a, clocks_b)
    for la, lb in zip(lanes_a, lanes_b):
        assert np.array_equal(la.store.time_matrix(2), lb.store.time_matrix(2))
        vids_a, val_a, m_a = la.store.counter_columns("wait_s")
        vids_b, val_b, m_b = lb.store.counter_columns("wait_s")
        assert np.array_equal(vids_a, vids_b)
        assert np.array_equal(val_a, val_b)
        assert np.array_equal(m_a, m_b)


def test_series_with_grouped_collectives_matches_per_scale():
    """End-to-end: the one-pass stacked series (stacked collective legs
    included) stays bit-identical to independent per-scale simulates."""
    g = _pipeline_psg(16)
    series = simulate_series(g, [4, 8, 16],
                             lambda p, vid, n: 0.01 * (1 + p % 3))
    for n in (4, 8, 16):
        one = simulate(g, n, lambda p, vid: 0.01 * (1 + p % 3), seed=n)
        assert np.array_equal(series[n].times_matrix(),
                              one.ppg.times_matrix())
        assert np.array_equal(series[n].counter_matrix("wait_s"),
                              one.ppg.counter_matrix("wait_s"))


# ---------------------------------------------------------------------------
# profiler shard emission (jax-dependent, kept minimal)
# ---------------------------------------------------------------------------

def test_profiler_perf_shard_roundtrip():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core import GraphProfiler

    def step(x):
        return jnp.tanh(x @ x).sum()

    prof = GraphProfiler(step, (np.ones((4, 4), np.float32),),
                         sample_every=1)
    prof.step(np.ones((4, 4), np.float32))
    vecs = prof.perf_vectors()
    assert vecs
    # host 1 of 2, covering procs [3, 6)
    shard = prof.perf_shard(proc_start=3, n_procs=3)
    assert shard.proc_start == 3 and shard.n_procs == 3
    merged = PerfStore.from_shards([PerfShard(0, 3), shard])
    assert merged.n_procs == 6
    for vid, vec in vecs.items():
        assert merged[(4, vid)].time == vec.time
        assert merged[(4, vid)].counters == vec.counters
    assert (0, next(iter(vecs))) not in merged
