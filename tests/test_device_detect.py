"""Device-resident shard buffers feeding the jitted detectors (PR 5).

Pins the tentpole to the host-fed semantics:

* device-fed detection (the blocks' rows pinned as one resident device
  buffer per matrix, blockwise merge/median/top-k kernels) must pick
  exactly the same vertices as the host-fed jitted path and the numpy
  reference — f64 results bitwise where the math is order-independent (max merge, median,
  winner sets), ~1e-12 for blockwise-reassociated sums, ~1e-4 under
  ``SCALANA_DETECT_F32``;
* the incremental upload must transfer exactly the rows written since
  the previous detect call, in one row scatter per matrix, and the
  device buffers must equal the stacked host blocks after every refresh
  — interleaved writes/detects included;
* a ShardedStore-backed PPG must run detection WITHOUT materializing the
  stacked host matrix (asserted by making the stacked views explode);
* regression: an all-dead final scale (``total_max <= 0``) yields share
  0 / no flags — never inf/nan (the unguarded-divide bug).

Everything jax-dependent skips cleanly when jax is absent.
"""
import numpy as np
import pytest

from repro.core import (COMM, COMP, PSG, DeviceShardView, PerfShard,
                        PerfStore, ShardedStore, build_ppg, detect_abnormal,
                        detect_non_scalable)
from repro.core.graph import PerfVector
from repro.core.inject import simulate


def _step_psg(n_procs, n_comp=6):
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    prev = None
    for i in range(n_comp):
        v = g.new_vertex(COMP, f"c{i}", parent=root.vid,
                         source=f"m.py:{i}")
        g.add_edge(root.vid, v.vid, "control")
        if prev is not None:
            g.add_edge(prev, v.vid, "data")
        prev = v.vid
    p2p = g.new_vertex(COMM, "ppermute", parent=root.vid, source="m.py:h")
    p2p.comm_kind, p2p.comm_bytes = "ppermute", 1e5
    p2p.p2p_pairs = [(p, (p + 1) % n_procs) for p in range(n_procs)]
    g.add_edge(prev, p2p.vid, "data")
    g.add_edge(root.vid, p2p.vid, "control")
    ar = g.new_vertex(COMM, "psum", parent=root.vid, source="m.py:ar")
    ar.comm_kind, ar.comm_bytes = "all_reduce", 1e6
    g.add_edge(p2p.vid, ar.vid, "data")
    g.add_edge(root.vid, ar.vid, "control")
    return g


def _base(p, vid):
    return 0.01 * (1 + p % 3) + 0.001 * vid


def _sim_pair(n_procs, n_hosts, inject=None, seed=0):
    """(plain, sharded) bit-identical replays of the same scenario."""
    g = _step_psg(n_procs)
    plain = simulate(g, n_procs, _base, inject=inject, seed=seed)
    sharded = simulate(g, n_procs, _base, inject=inject, seed=seed,
                       shards=n_hosts)
    return g, plain.ppg, sharded.ppg


def _ab_key(ab):
    return [(a.proc, a.vid, a.time, a.typical) for a in ab]


# ---------------------------------------------------------------------------
# device-fed == host-fed == numpy
# ---------------------------------------------------------------------------

def test_abnormal_device_equals_host_and_numpy():
    pytest.importorskip("jax")
    for n_procs, n_hosts, seed in [(12, 3, 0), (16, 4, 1), (9, 2, 2),
                                   (24, 5, 3)]:
        _, plain, sharded = _sim_pair(n_procs, n_hosts,
                                      inject={(4, 2): 0.5}, seed=seed)
        ab_np = detect_abnormal(plain, backend="numpy")
        ab_host = detect_abnormal(plain, backend="jax")
        ab_dev = detect_abnormal(sharded, backend="jax")
        # winners, times AND typical (device median) bitwise vs numpy
        assert _ab_key(ab_dev) == _ab_key(ab_np) == _ab_key(ab_host)


def test_non_scalable_device_equals_host_and_numpy():
    pytest.importorskip("jax")
    g = _step_psg(16)

    def t_at(p, vid, n):
        return 0.08 if vid == 3 else 0.4 / n       # vid 3 does not scale

    series_plain, series_sh = {}, {}
    for n in (4, 8, 16):
        series_plain[n] = simulate(g, n, lambda p, v, n=n: t_at(p, v, n)).ppg
        series_sh[n] = simulate(g, n, lambda p, v, n=n: t_at(p, v, n),
                                shards=min(4, n)).ppg
    for strategy in ("mean", "max", "p0", "var"):
        ns_np = detect_non_scalable(series_plain, backend="numpy",
                                    strategy=strategy)
        ns_host = detect_non_scalable(series_plain, backend="jax",
                                      strategy=strategy)
        ns_dev = detect_non_scalable(series_sh, backend="jax",
                                     strategy=strategy)
        assert [d.vid for d in ns_dev] == [d.vid for d in ns_np] \
            == [d.vid for d in ns_host], strategy
        assert ns_dev and ns_dev[0].vid == 3
        for a, b in zip(ns_host, ns_dev):
            # blockwise reassociation: sums agree to reduction-order
            # rounding; the "max" merge is order-independent, so its
            # merged times and slope land bitwise (share still divides by
            # the blockwise-summed total step time)
            tol = 0 if strategy == "max" else 1e-12
            assert abs(a.slope - b.slope) <= tol * max(abs(a.slope), 1)
            assert abs(a.share - b.share) <= 1e-12 * max(abs(a.share), 1)
            for scale in a.times:
                assert abs(a.times[scale] - b.times[scale]) <= \
                    tol * max(abs(a.times[scale]), 1)


def test_device_detection_f32_parity(monkeypatch):
    pytest.importorskip("jax")
    monkeypatch.setenv("SCALANA_DETECT_F32", "1")
    g = _step_psg(12)
    series_sh = {n: simulate(g, n, _base, shards=3).ppg for n in (6, 12)}
    series_plain = {n: simulate(g, n, _base).ppg for n in (6, 12)}
    ns_np = detect_non_scalable(series_plain, backend="numpy",
                                min_share=0.0)
    ns_dev = detect_non_scalable(series_sh, backend="jax", min_share=0.0)
    assert [d.vid for d in ns_dev] == [d.vid for d in ns_np]
    for a, b in zip(ns_np, ns_dev):
        assert np.isclose(a.slope, b.slope, rtol=1e-4, atol=1e-4)
        assert np.isclose(a.share, b.share, rtol=1e-4, atol=1e-4)
    # abnormal: unambiguous stragglers (uniform base, distinct injects) —
    # f32 rounding must not reorder clearly-separated winners
    g2 = _step_psg(12)
    inject = {(5, 1): 0.4, (2, 3): 0.2, (8, 2): 0.1}
    plain = simulate(g2, 12, lambda p, vid: 0.01, inject=inject).ppg
    sharded = simulate(g2, 12, lambda p, vid: 0.01, inject=inject,
                       shards=3).ppg
    ab_np = detect_abnormal(plain, backend="numpy")
    ab_dev = detect_abnormal(sharded, backend="jax")
    assert [(a.proc, a.vid) for a in ab_dev] == \
        [(a.proc, a.vid) for a in ab_np]
    for a, b in zip(ab_np, ab_dev):
        assert np.isclose(a.typical, b.typical, rtol=1e-4, atol=1e-6)


def test_device_path_never_stacks_host_matrix(monkeypatch):
    """The acceptance criterion, asserted directly: detection on a
    ShardedStore-backed PPG must not touch the stacked (P, V) host views.
    """
    pytest.importorskip("jax")
    g = _step_psg(12)
    sharded = simulate(g, 12, _base, inject={(3, 2): 0.5}, shards=3).ppg
    series_sh = {n: simulate(g, n, _base, shards=3).ppg for n in (6, 12)}

    def boom(*a, **k):                                 # pragma: no cover
        raise AssertionError("stacked host matrix materialized")

    monkeypatch.setattr(ShardedStore, "time_matrix", boom)
    monkeypatch.setattr(ShardedStore, "var_matrix", boom)
    ab = detect_abnormal(sharded, backend="jax")
    assert ab and ab[0].proc == 3 and ab[0].vid == 2
    ns = detect_non_scalable(series_sh, backend="jax", min_share=0.0)
    assert [d.vid for d in ns] == [d.vid for d in
                                   detect_non_scalable(
                                       {n: simulate(g, n, _base).ppg
                                        for n in (6, 12)},
                                       backend="numpy", min_share=0.0)]


# ---------------------------------------------------------------------------
# dirty-row incremental upload
# ---------------------------------------------------------------------------

def _assert_buffers_match(view, V):
    """The resident time/var buffers equal the host blocks stacked in
    block order (padded to V columns); each counter block equals its
    host block."""
    assert len(view.time_blocks()) == len(view.var_blocks()) == 1
    np.testing.assert_array_equal(
        np.asarray(view.time_blocks()[0]),
        np.vstack([blk.time_matrix(V) for blk in view.blocks]))
    np.testing.assert_array_equal(
        np.asarray(view.var_blocks()[0]),
        np.vstack([blk.var_matrix(V) for blk in view.blocks]))
    for i, blk in enumerate(view.blocks):
        for name in blk.counter_names():
            vids, values, mask = blk.counter_columns(name)
            key, buf = view.counter_blocks(name)[i]
            assert key == tuple(vids.tolist())
            np.testing.assert_array_equal(np.asarray(buf),
                                          np.where(mask, values, 0.0))


def test_incremental_upload_after_interleaved_writes():
    pytest.importorskip("jax")
    g = _step_psg(16)
    ppg = simulate(g, 16, _base, shards=[(0, 5), (5, 11), (11, 16)]).ppg
    V = len(g.vertices)
    view = ppg.device_view()
    assert view is ppg.device_view()                   # cached, one per PPG

    view.refresh(V)                                    # first: full upload
    assert view.full_uploads == 1 and view.last_upload_rows == 16
    _assert_buffers_match(view, V)
    full_bytes = view.last_upload_bytes

    view.refresh(V)                                    # clean: no transfer
    assert view.last_upload_rows == 0 and view.last_upload_bytes == 0

    rng = np.random.default_rng(0)
    for round_ in range(4):
        rows = np.unique(rng.integers(0, 16, size=rng.integers(1, 5)))
        vid = int(rng.integers(0, V))
        ppg.perf.set_entries(rows, vid, 1.0 + round_,
                             counters={"wait_s": 0.25})
        if round_ == 2:                                # scalar write path
            ppg.perf.set_entry(2, 1, 3.5, accumulate=True)
            rows = np.union1d(rows, [2])
        view.refresh(V)
        assert view.full_uploads == 1                  # still incremental
        assert view.last_upload_rows == rows.size
        assert view.last_upload_bytes < full_bytes
        _assert_buffers_match(view, V)
        # detection agrees with the numpy reference after every round
        assert _ab_key(detect_abnormal(ppg, backend="jax")) == \
            _ab_key(detect_abnormal(ppg, backend="numpy"))

    # a dtype flip re-pins in full (no stale f64 buffers feed f32 runs)
    view.refresh(V, dtype=np.float32)
    assert view.full_uploads == 2 and view.last_upload_rows == 16


def test_device_view_single_store_and_errors():
    pytest.importorskip("jax")
    store = PerfStore(6, 4)
    store.set_column(2, np.arange(6.0))
    view = DeviceShardView(store)
    with pytest.raises(RuntimeError):                  # read before refresh
        view.time_blocks()
    view.refresh(4)
    assert len(view.time_blocks()) == 1
    np.testing.assert_array_equal(np.asarray(view.time_blocks()[0]),
                                  store.time_matrix(4))
    assert view.row_ranges() == [(0, 6)]
    with pytest.raises(TypeError):
        DeviceShardView({})


class _Span:
    def __init__(self, stats):
        self.stats = stats

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **more):
        self.stats.update(more)


class _SpanLog:
    """Stand-in for ``repro.core.spans.span``: records each span's name
    and stats (the ones passed in and those ``set_metadata`` adds)."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **stats):
        self.spans.append((name, stats))
        return _Span(stats)

    def stats(self, name):
        return [st for n, st in self.spans if n == name]


def _uneven_ranges(n_shards):
    sizes = [1 + i % 4 for i in range(n_shards)]          # 1, 2, 3, 4, ...
    stops = np.cumsum(sizes).tolist()
    return list(zip([0] + stops[:-1], stops))


def test_many_uneven_shards_feed_one_resident_buffer(monkeypatch):
    """36 shards of 1-4 rows: the view hands detection ONE (P, V) buffer
    per matrix, a refresh after writes to several blocks issues one row
    scatter per matrix, and detection still equals the numpy
    reference, with and without a process mask."""
    pytest.importorskip("jax")
    import repro.core.shard as shard_mod

    ranges = _uneven_ranges(36)
    P = ranges[-1][1]
    g = _step_psg(P)
    V = len(g.vertices)
    inject = {(7, 2): 0.5}

    def t_at(p, vid, n):
        return _base(p, vid) * (0.5 if vid == 3 else P / n)

    series_sh, series_plain = {}, {}
    for n, sh in ((P // 2, 9), (P, ranges)):
        f = (lambda p, v, n=n: t_at(p, v, n))
        series_sh[n] = simulate(g, n, f, inject=inject, shards=sh).ppg
        series_plain[n] = simulate(g, n, f, inject=inject).ppg
    live, ref = series_sh[P], series_plain[P]
    assert len(live.perf.shards) == 36

    view = live.device_view()
    view.refresh(V)
    assert len(view.time_blocks()) == len(view.var_blocks()) == 1
    assert view.time_blocks()[0].shape == (P, V)
    _assert_buffers_match(view, V)

    # interleaved writes to several blocks, mirrored on the plain store
    for store in (live.perf, ref.perf):
        store.set_entries([0, 9, 40, 71], 2, 0.03)
        store.set_entry(55, 4, 0.02, accumulate=True)
        store.set_entries([10, 11], 5, 0.04)
    spans = _SpanLog()
    monkeypatch.setattr(shard_mod, "span", spans)
    view.refresh(V)
    view.refresh(V)                                      # clean
    monkeypatch.undo()
    first, clean = spans.stats("feed.refresh")
    assert first["scatters"] == 2 and first["rows"] == 7
    assert first["dirty_blocks"] == len(
        {int(np.searchsorted([lo for lo, _ in ranges], r, "right"))
         for r in (0, 9, 40, 71, 55, 10, 11)})
    assert clean["scatters"] == 0 and clean["rows"] == 0
    _assert_buffers_match(view, V)

    assert _ab_key(detect_abnormal(live, backend="jax")) == \
        _ab_key(detect_abnormal(ref, backend="numpy"))
    mask = np.ones(P, bool)
    mask[[3, 7, 40, P - 1]] = False
    assert _ab_key(detect_abnormal(live, backend="jax", proc_mask=mask)) \
        == _ab_key(detect_abnormal(ref, backend="numpy", proc_mask=mask))
    ns_dev = detect_non_scalable(series_sh, backend="jax", min_share=0.0)
    ns_np = detect_non_scalable(series_plain, backend="numpy",
                                min_share=0.0)
    assert ns_dev and [d.vid for d in ns_dev] == [d.vid for d in ns_np]
    for a, b in zip(ns_np, ns_dev):
        assert abs(a.slope - b.slope) <= 1e-12 * max(abs(a.slope), 1)
        assert abs(a.share - b.share) <= 1e-12 * max(abs(a.share), 1)


def test_dirty_counts_share_power_of_two_scatters_and_repin():
    """Dirty-row counts 1, 3, 5 and 32 leave the buffers exact and
    compile at most one scatter per power-of-two bucket; counts in the
    same buckets compile nothing more.  A block whose row count changes
    re-pins the whole matrix."""
    pytest.importorskip("jax")
    from repro.core.shard import _row_scatter

    ranges = _uneven_ranges(40)
    P, V = ranges[-1][1], 7
    store = ShardedStore(ranges, V)
    rng = np.random.default_rng(5)
    for vid in range(V):
        store.set_column(vid, rng.random(P))
    view = DeviceShardView(store)
    view.refresh(V)
    scatter = _row_scatter()

    def write(k):
        rows = rng.choice(P, k, replace=False)
        store.set_entries(rows, int(rng.integers(V)), rng.random(k))
        view.refresh(V)
        assert view.last_upload_rows == k and view.full_uploads == 1
        _assert_buffers_match(view, V)

    before = scatter._cache_size()
    for k in (1, 3, 5, 32):                          # buckets 1, 4, 8, 32
        write(k)
    grown = scatter._cache_size() - before
    assert grown <= 4
    for k in (4, 7, 6, 31, 17):                      # the same buckets
        write(k)
    assert scatter._cache_size() - before == grown

    last = view.blocks[-1]
    last.ensure_rows(last.n_procs + 2)               # a host's range grows
    last.set_entries([last.n_procs - 1], 1, 0.5)
    view.refresh(V)
    assert view.full_uploads == 2 and view.last_upload_rows == P + 2
    assert view.time_blocks()[0].shape == (P + 2, V)
    _assert_buffers_match(view, V)


# ---------------------------------------------------------------------------
# regression: unguarded share divide (total_max <= 0)
# ---------------------------------------------------------------------------

def _dead_top_series():
    """Final scale whose root children are ALL dead (t == 0) while a
    nested vertex still has time: total_max == 0."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    loop = g.new_vertex("Loop", "loop", parent=root.vid)
    g.add_edge(root.vid, loop.vid, "control")
    body = g.new_vertex(COMP, "body", parent=loop.vid, source="m.py:9")
    series = {}
    for n in (2, 4, 8):
        perf = {loop.vid: PerfVector(time=0.0 if n == 8 else 0.05,
                                     samples=1),
                body.vid: PerfVector(time=0.04, samples=1)}
        series[n] = build_ppg(g, n, perf)
    return series


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_total_max_zero_yields_zero_share_no_flags(backend):
    if backend == "jax":
        pytest.importorskip("jax")
    series = _dead_top_series()
    with np.errstate(all="raise"):                     # inf/nan would raise
        out = detect_non_scalable(series, backend=backend, min_share=0.01)
    assert out == []                                   # share 0: nothing


def test_non_scalable_kernel_guards_total_max_directly():
    detect_jax = pytest.importorskip("repro.core.detect_jax")
    S, P, V = 2, 3, 4
    rng = np.random.default_rng(1)
    t = rng.uniform(0.1, 1.0, (S, P, V))
    M, slope, share, flagged = detect_jax.non_scalable_arrays(
        [2, 4], t, np.zeros_like(t), np.ones((S, V), bool), 0.0,
        -1.0, 0.35, 0.01, "mean")
    assert np.all(share == 0.0) and not flagged.any()
    assert np.isfinite(M).all() and np.isfinite(slope).all()


# ---------------------------------------------------------------------------
# measured-profile threading: profiler shards -> sharded PPG -> device path
# ---------------------------------------------------------------------------

def test_profiler_shards_feed_device_detection():
    """Per-host ``GraphProfiler.perf_shard`` blocks adopted via
    ``build_ppg(sharded=True)`` run device-fed detection equal to the
    merged-store numpy reference."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core import GraphProfiler

    def step(x):
        return jnp.tanh(x @ x).sum()

    prof = GraphProfiler(step, (np.ones((4, 4), np.float32),),
                         sample_every=1)
    prof.step(np.ones((4, 4), np.float32))
    shards = [prof.perf_shard(proc_start=lo, n_procs=hi - lo)
              for lo, hi in [(0, 3), (3, 5), (5, 8)]]
    shards[1].set_entry(1, 1, 7.5)                 # host 1's straggler
    ppg = build_ppg(prof.psg, 8, shards, sharded=True)
    assert isinstance(ppg.perf, ShardedStore)
    merged = build_ppg(prof.psg, 8, iter(shards))
    ab_dev = detect_abnormal(ppg, backend="jax")
    ab_ref = detect_abnormal(merged, backend="numpy")
    assert _ab_key(ab_dev) == _ab_key(ab_ref)
    assert any(a.proc == 4 and a.vid == 1 for a in ab_dev)


# ---------------------------------------------------------------------------
# degraded-fleet row masks on the device path (PR 6)
# ---------------------------------------------------------------------------

def test_abnormal_device_proc_mask_equals_numpy_masked():
    """Masked device detection == masked numpy == one-shot on a fleet
    that never contained the dead rows (exclusion, not zero-pollution)."""
    pytest.importorskip("jax")
    n_procs, n_hosts = 16, 4
    _, plain, sharded = _sim_pair(n_procs, n_hosts,
                                  inject={(2, 2): 6.0, (9, 3): 6.0}, seed=0)
    mask = np.ones(n_procs, bool)
    mask[8:12] = False                 # host 2 dead (incl. straggler p9)
    live = np.nonzero(mask)[0]

    got_dev = detect_abnormal(sharded, backend="jax", proc_mask=mask)
    got_np = detect_abnormal(plain, backend="numpy", proc_mask=mask)
    assert _ab_key(got_dev) == _ab_key(got_np)
    assert any(a.proc == 2 for a in got_dev)       # live straggler found
    assert all(a.proc != 9 for a in got_dev)       # dead one is silent
    assert all(mask[a.proc] for a in got_dev)      # procs are GLOBAL

    # reference: a store that simply never had the dead rows
    restricted = PerfStore(live.size, len(plain.psg.vertices))
    restricted.apply_rows(plain.perf.extract_rows(live),
                          rows=np.arange(live.size))
    sub = build_ppg(plain.psg, live.size, restricted)
    want = detect_abnormal(sub, backend="numpy")
    assert _ab_key(got_np) == [(int(live[p]), v, t, m)
                               for p, v, t, m in _ab_key(want)]


def test_device_proc_mask_reuses_buffers_across_masks():
    """Changing the mask between detects must not force a re-upload —
    the live gather happens on device, the pinned buffers stand."""
    pytest.importorskip("jax")
    n_procs = 12
    _, _, sharded = _sim_pair(n_procs, 3, inject={(1, 2): 5.0}, seed=1)
    full = detect_abnormal(sharded, backend="jax")
    view = sharded.device_view()
    uploads = view.total_upload_bytes
    for dead in (0, 4, 8):
        mask = np.ones(n_procs, bool)
        mask[dead] = False
        detect_abnormal(sharded, backend="jax", proc_mask=mask)
    assert view.total_upload_bytes == uploads      # no re-transfer
    again = detect_abnormal(sharded, backend="jax")
    assert _ab_key(again) == _ab_key(full)         # full-fleet path intact


# ---------------------------------------------------------------------------
# refresh atomicity: a failed upload must not eat the dirty flags (PR 6)
# ---------------------------------------------------------------------------

def test_refresh_failure_keeps_dirty_rows_for_retry(monkeypatch):
    """A device upload that raises mid-refresh leaves the dirty flags and
    the pinned buffers untouched; the retried refresh re-uploads exactly
    the rows the failed call lost.  (Regression: clearing dirty flags
    eagerly dropped those rows forever.)"""
    pytest.importorskip("jax")
    n_procs = 12
    _, _, sharded = _sim_pair(n_procs, 3, seed=2)
    view = sharded.perf.device_view() if hasattr(sharded.perf, "device_view") \
        else DeviceShardView(sharded.perf)
    view.refresh()                                  # clean baseline upload
    assert all(not b.dirty_rows().size for b in view.blocks)

    # dirty a couple of rows, then make the upload die mid-flight
    sharded.perf.set_entry(1, 1, 9.0)
    sharded.perf.set_entry(7, 2, 9.5)
    calls = {"n": 0}
    real = DeviceShardView._rows_slab

    def dying(self, mat, rows, V, dtype):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise RuntimeError("injected device OOM")
        return real(self, mat, rows, V, dtype)

    monkeypatch.setattr(DeviceShardView, "_rows_slab", dying)
    before_time = [np.asarray(t).copy() for t in view.time_blocks()]
    with pytest.raises(RuntimeError, match="injected device OOM"):
        view.refresh()
    monkeypatch.undo()

    # the failed refresh changed NOTHING: flags intact, buffers intact
    dirty = np.concatenate([b.dirty_rows() + b.proc_start
                            for b in view.blocks])
    assert sorted(dirty.tolist()) == [1, 7]
    for buf, ref in zip(view.time_blocks(), before_time):
        np.testing.assert_array_equal(np.asarray(buf), ref)

    # the retry re-uploads exactly those rows and converges to the hosts
    view.refresh()
    assert view.last_upload_rows == 2
    assert all(not b.dirty_rows().size for b in view.blocks)
    host = np.concatenate([b.time for b in view.blocks], axis=0)
    dev = np.concatenate([np.asarray(t) for t in view.time_blocks()], axis=0)
    np.testing.assert_array_equal(dev, host)


def test_refresh_failure_on_full_upload_leaves_view_unprimed(monkeypatch):
    """Same contract on the FULL-upload branch: a fresh view whose first
    refresh dies stays unprimed (reads still refuse) and the stores stay
    fully dirty for the retry."""
    pytest.importorskip("jax")
    _, _, sharded = _sim_pair(8, 2, seed=3)
    view = DeviceShardView(sharded.perf)

    def dying(self, mat, rows, V, dtype):
        raise RuntimeError("boom on first slab")

    monkeypatch.setattr(DeviceShardView, "_rows_slab", dying)
    with pytest.raises(RuntimeError, match="boom on first slab"):
        view.refresh()
    monkeypatch.undo()
    with pytest.raises(RuntimeError):
        view.time_blocks()                          # still unprimed
    assert all(b.dirty_rows().size == b.n_procs for b in view.blocks)
    view.refresh()                                  # retry fully recovers
    host = np.concatenate([b.time for b in view.blocks], axis=0)
    dev = np.concatenate([np.asarray(t) for t in view.time_blocks()], axis=0)
    np.testing.assert_array_equal(dev, host)


# ---------------------------------------------------------------------------
# routing: no silent numpy fallback on an accelerator
# ---------------------------------------------------------------------------

def test_auto_backend_raises_on_accelerator_when_device_path_unusable(
        monkeypatch):
    """On a non-CPU backend "auto" must take the device path or fail: a
    numpy fallback there would hide the device.  The platform and an
    unimportable device path are steered here."""
    jax = pytest.importorskip("jax")
    import sys

    import repro.core
    from repro.core.detect import _resolve_backend

    monkeypatch.delattr(repro.core, "detect_jax", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.detect_jax", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ImportError):
        _resolve_backend("auto")
    _, _, sharded = _sim_pair(8, 2, inject={(3, 2): 0.5})
    with pytest.raises(ImportError):
        detect_abnormal(sharded)
    # a CPU host keeps host-side stores on numpy without touching it
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert _resolve_backend("auto") is None
