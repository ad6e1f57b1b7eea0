"""Graph-core scalability: build + simulate + detect + backtrack at 512..8192.

The indexed-graph + replay-engine acceptance benchmark: a synthetic-but-
realistic training step PSG (comp chain + halo-exchange p2p ring + grouped
and global collectives) is simulated with an injected straggler, then the
full post-mortem pipeline runs at 512/2048/8192 processes.  Reported per
scale:

  * ``simulate_series_s`` — one stacked multi-scale replay pass (the PPG
    series the detectors consume; also reported as ``build_s``);
  * ``simulate_s`` vs ``simulate_seq_s`` — the wavefront replay engine
    against the retained PR-2-style baseline (per-pair Python p2p loop +
    scalar ``base_times`` callbacks) on a p2p-HEAVY schedule; the outputs
    are asserted bit-identical and the speedup is asserted >= 10x at the
    top scale (the vectorized-replay acceptance criterion);
  * wall time for detection and backtracking — ``detect_s`` is the
    DEFAULT configuration (``backend=None``/auto, which stays on numpy
    on CPU-only jax with host stores and is asserted within 2x of
    ``detect_numpy_s``); the explicit jitted timing is
    ``detect_jax_s`` (full run, post-warmup);
  * ``detect_fused_s`` vs ``detect_cached_steady_s`` (full run) — one
    device-fed detect cycle (non-scalable over the series + abnormal at
    the top scale) through the fused one-launch ops with cold
    merged-column caches, and the steady state (warm historical-scale
    cache, a 16-row dirty write on the live scale); the steady state is
    asserted to be exactly 2 fused launches (``detect_cached_launches``,
    via the launch-count seam);
  * ``backtrack_s`` — the backtracking walk on a many-straggler
    scenario (>= 256 flagged (proc, vertex) pairs at the top scale,
    asserted);
  * ``shard_merge_s`` — merging an 8-host sharded replay
    (``simulate(..., shards=8)``) into one store through
    ``PerfStore.from_shards`` (contiguous fresh ranges take the
    whole-block fast path), asserted equal to the unsharded replay;
  * ``detect_device_s`` vs ``detect_host_fed_s`` (full run only) — the
    jitted abnormal detector fed from device-resident shard buffers
    (``ppg.device_view()``) against the host-fed jitted path, with the
    incremental-upload guarantee asserted: after a 16-row write, the
    per-call transfer (``device_dirty_bytes``) must scale with the dirty
    rows, not O(P·V);
  * ``ppg.nbytes()`` and the comm-dependence share of it — collective
    dependence is stored as participant groups, so comm bytes grow O(P),
    not O(P²) (asserted);
  * counter storage: the column-sparse layout vs the dense (P, V)
    equivalent (asserted smaller);
  * ``socket_ingest/*`` rows — 512/2048/4096 loopback producers
    streaming versioned wire frames through shared real TCP connections
    into one ``SocketServer`` + resident ``Monitor``, asserted bit-
    identical to one-shot detection, with the wire-level delta
    compression ratio priced against a full-row baseline;
  * ``run_store_record_s`` / ``run_store_load_s`` / ``run_store_diff_s``
    — the persistent regression service priced per scale (record the
    faulted series + detect output through the checkpoint seam, reload,
    cross-run diff; the same-run diff asserted quiet), plus a
    ``run_store_fleet`` row: a 65536-proc clean/slowed pair clustered to
    <= 64 behavior representatives on record and diffed, with >= 100x
    row compression asserted on full runs and the regressed cluster
    required to contain every true culprit proc.

``run`` returns the rows as dicts; ``benchmarks/run.py`` snapshots them to
``BENCH_graph_scale.json`` so the perf trajectory is machine-readable
across PRs.

The smoke mode (`run.py --smoke` / `make check` via `make bench-smoke`)
imports only the lazy analysis layer of `repro.core` and never touches jax
— it is the jax-free canary.  The full run additionally times
`backend="jax"` detection.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.core import (COMM, COMP, PSG, PerfStore, backtrack,
                        detect_abnormal, detect_non_scalable, root_causes)
from repro.core.inject import simulate, simulate_series, vectorized_base_times

FULL_SCALES = (512, 2048, 8192)
SMOKE_SCALES = (8, 32)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    # local copy of benchmarks.common.emit: common.py imports jax + the
    # model zoo, which this pure-numpy benchmark must not depend on
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def halo_ring_pairs(n_procs: int) -> List:
    """Ring-neighbor exchange posted in the standard even/odd interleave
    (all even-sender pairs, then all odd-sender pairs) — how concurrent
    halo exchanges are actually scheduled.  The interleave keeps the
    order-dependent p2p semantics two wavefront rounds deep instead of an
    artificial P-deep chain."""
    return ([(p, (p + 1) % n_procs) for p in range(0, n_procs, 2)]
            + [(p, (p + 1) % n_procs) for p in range(1, n_procs, 2)])


def build_step_psg(n_comp: int = 24, n_procs_hint: int = 8) -> PSG:
    """Synthetic train-step PSG: comp chain with a p2p halo ring, a grouped
    reduce-scatter and a global all-reduce (the GSPMD shapes that matter)."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    prev = None
    for i in range(n_comp):
        v = g.new_vertex(COMP, f"layer{i}", parent=root.vid,
                         source=f"model.py:{100 + i}")
        v.flops = 1e12
        if prev is not None:
            g.add_edge(prev, v.vid, "data")
        g.add_edge(root.vid, v.vid, "control")
        prev = v.vid
        if i == n_comp // 3:                      # halo exchange ring
            p2p = g.new_vertex(COMM, "ppermute", parent=root.vid,
                               source="model.py:halo")
            p2p.comm_kind, p2p.comm_bytes = "ppermute", 1e6
            p2p.p2p_pairs = halo_ring_pairs(n_procs_hint)
            g.add_edge(prev, p2p.vid, "data")
            g.add_edge(root.vid, p2p.vid, "control")
            prev = p2p.vid
        if i == 2 * n_comp // 3:                  # grouped reduce-scatter
            rs = g.new_vertex(COMM, "reduce_scatter", parent=root.vid,
                              source="model.py:rs")
            rs.comm_kind, rs.comm_bytes = "reduce_scatter", 4e6
            half = n_procs_hint // 2 or 1
            rs.meta["replica_groups"] = [list(range(half)),
                                         list(range(half, n_procs_hint))]
            g.add_edge(prev, rs.vid, "data")
            g.add_edge(root.vid, rs.vid, "control")
            prev = rs.vid
    ar = g.new_vertex(COMM, "psum", parent=root.vid, source="optim.py:60")
    ar.comm_kind, ar.comm_bytes = "all_reduce", 8e6
    g.add_edge(prev, ar.vid, "data")
    g.add_edge(root.vid, ar.vid, "control")
    return g


def build_p2p_heavy_psg(n_comp: int = 8, n_procs_hint: int = 8,
                        n_halo: int = 6) -> PSG:
    """p2p-heavy schedule for the replay-engine acceptance measurement:
    ``n_halo`` halo-exchange vertices (one full ring of pairs each)
    interleaved with a comp chain, closed by a global all-reduce."""
    g = PSG()
    root = g.new_vertex("Root", "root")
    g.root = root.vid
    prev = None
    for i in range(max(n_comp, n_halo)):
        if i < n_comp:
            v = g.new_vertex(COMP, f"stage{i}", parent=root.vid,
                             source=f"model.py:{200 + i}")
            v.flops = 1e12
            if prev is not None:
                g.add_edge(prev, v.vid, "data")
            g.add_edge(root.vid, v.vid, "control")
            prev = v.vid
        if i < n_halo:
            p2p = g.new_vertex(COMM, f"ppermute{i}", parent=root.vid,
                               source=f"model.py:halo{i}")
            p2p.comm_kind, p2p.comm_bytes = "ppermute", 1e6
            p2p.p2p_pairs = halo_ring_pairs(n_procs_hint)
            if prev is not None:
                g.add_edge(prev, p2p.vid, "data")
            g.add_edge(root.vid, p2p.vid, "control")
            prev = p2p.vid
    ar = g.new_vertex(COMM, "psum", parent=root.vid, source="optim.py:60")
    ar.comm_kind, ar.comm_bytes = "all_reduce", 8e6
    g.add_edge(prev, ar.vid, "data")
    g.add_edge(root.vid, ar.vid, "control")
    return g


def build_fleet_ppg(psg, n_procs: int, slow: float = 1.0):
    """A fleet-scale PPG written straight into a PerfStore (replaying at
    65536 procs is not the point here): comp columns with deterministic
    per-proc jitter, the heaviest vertex slowed ``slow``x on the culprit
    procs (every 1024th-plus-7), one global collective group.  Shared
    with ``tools/run_store_smoke.py``.

    Returns (ppg, slowed_vid, culprit_proc_set)."""
    from repro.core.graph import PPG

    ppg = PPG(psg, n_procs)
    procs = np.arange(n_procs)
    culprits = procs[procs % 1024 == 7]
    comp = [v.vid for v in psg.vertices if v.kind == COMP]
    heavy = comp[len(comp) // 2]
    for i, vid in enumerate(comp):
        t = np.full(n_procs, 1e-3 * (1 + i % 3))
        t *= 1.0 + 1e-4 * ((procs * 2654435761 % 97) / 97.0)  # jitter
        if vid == heavy and slow != 1.0:
            t[culprits] *= slow
        ppg.perf.set_column(vid, t, counters={"flops": 1e9})
    for v in psg.vertices:
        if v.kind == COMM:
            ppg.perf.set_column(v.vid, np.full(n_procs, 1e-4))
            ppg.comm.add_group(v.vid, tuple(range(n_procs)))
    return ppg, heavy, set(culprits.tolist())


def bench_run_store(series, ns, ab):
    """Price the regression service per scale: record the faulted series
    (scaling curves + detect output) into a throwaway RunStore through
    the checkpoint seam, reload it, and diff two records of the same
    run.  Returns (record_s, load_s, diff_s); the same-run diff is
    asserted quiet and the detect output asserted to survive the disk
    round trip."""
    import tempfile

    from repro.runs import RunStore, diff_runs

    detect = {"non_scalable": ns, "abnormal": ab}
    with tempfile.TemporaryDirectory() as d:
        store = RunStore(d)
        t0 = time.perf_counter()
        rid = store.record(series=series, detect=detect)
        record_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        a = store.load(rid)
        load_s = time.perf_counter() - t0
        b = store.load(store.record(series=series, detect=detect))
        t0 = time.perf_counter()
        diff = diff_runs(a, b)
        diff_s = time.perf_counter() - t0
    assert not diff.regressions, \
        f"same-run diff flagged {len(diff.regressions)} regressions"
    assert a.detect is not None and \
        [d.vid for d in a.detect["non_scalable"]] == [d.vid for d in ns] \
        and [(x.proc, x.vid) for x in a.detect["abnormal"]] \
        == [(x.proc, x.vid) for x in ab], \
        "detect output did not survive the run-store round trip"
    return record_s, load_s, diff_s


def bench_run_store_fleet(n_procs: int, max_clusters: int = 64,
                          smoke: bool = False) -> Dict:
    """Clustered record + cross-run diff at fleet scale: a clean and a
    culprit-slowed PPG are each compressed to <= ``max_clusters``
    behavior representatives on record, then diffed; the regressed
    cluster must contain every true culprit proc, and on full runs the
    row compression is asserted >= 100x at 65536 procs."""
    import tempfile

    from repro.runs import RunStore, diff_runs, regressed_cluster

    psg = build_step_psg(n_comp=12, n_procs_hint=8)
    t0 = time.perf_counter()
    good, heavy, culprits = build_fleet_ppg(psg, n_procs, slow=1.0)
    bad, _, _ = build_fleet_ppg(psg, n_procs, slow=2.5)
    fleet_build_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as d:
        store = RunStore(d)
        t0 = time.perf_counter()
        a = store.load(store.record(ppg=good, cluster=max_clusters))
        b = store.load(store.record(ppg=bad, cluster=max_clusters))
        record_cluster_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        diff = diff_runs(a, b)
        diff_s = time.perf_counter() - t0

    reps = b.clustering.n_clusters
    compression = b.clustering.compression()
    k = regressed_cluster(b, diff)
    members = set(b.clustering.members(k).tolist()) if k is not None \
        else set()
    assert reps <= max_clusters, \
        f"{reps} representatives > cap {max_clusters}"
    assert heavy in diff.regressed_vids, \
        "clustered diff missed the slowed vertex"
    assert k is not None and culprits <= members, \
        f"regressed cluster {k} missing culprits: " \
        f"{len(culprits & members)}/{len(culprits)}"
    if not smoke:
        assert compression >= 100.0, \
            f"clustered store compression {compression:.0f}x < 100x " \
            f"at {n_procs} procs"
    return {
        "name": f"graph_scale/run_store_fleet/{n_procs}procs",
        "n_procs": n_procs,
        "run_store_fleet_build_s": fleet_build_s,
        "run_store_cluster_record_s": record_cluster_s,
        "run_store_fleet_diff_s": diff_s,
        "run_store_reps": reps,
        "run_store_compression": compression,
        "run_store_regressed_cluster": -1 if k is None else int(k),
        "run_store_culprits": len(culprits),
        "run_store_culprits_in_cluster": len(culprits & members),
    }


def bench_monitor(psg, target: int, straggler: int, n_procs: int,
                  backend: str):
    """Steady-state ingest->detect latency of the always-on monitor,
    clean fleet vs ~10% of hosts behind a seeded faulty transport.

    Returns (clean_s, faulty_s, n_hosts, n_faulty) — per-step wall time
    for flush(all hosts) + poll + detect, averaged post-warmup, with the
    final streamed detection asserted identical to the one-shot run on
    the fully-assembled truth store."""
    from repro.core.shard import ShardedStore, shard_ranges
    from repro.monitor import (FaultyTransport, Monitor, QueueTransport,
                               ShardProducer)

    # 512-host ceiling (was 128): 16 procs/host at the top scale, the
    # fleet shape the socket ingest bench (below) extends further
    n_hosts = max(2, min(512, n_procs // 16 or 2))
    n_faulty = max(1, n_hosts // 10)
    ranges = shard_ranges(n_procs, n_hosts)

    @vectorized_base_times
    def time_at(procs, vid):
        t = np.full(procs.shape, 0.128 / n_procs)
        if vid == target:
            t[procs == straggler] += 0.05
        return t

    truth = simulate(psg, n_procs, time_at, shards=ranges).ppg
    ab_ref = [(a.proc, a.vid) for a in detect_abnormal(truth,
                                                       backend=backend)]
    V = len(psg.vertices)
    results = {}
    for variant in ("clean", "faulty"):
        queue = QueueTransport()
        monitor = Monitor(psg, ranges, queue, comm=truth.comm,
                          detect_every=None, backend=backend)
        prod = ShardedStore(ranges, V)
        producers = []
        for h in range(n_hosts):
            tr = queue
            if variant == "faulty" and h < n_faulty:
                # delivery through the same queue, but lossy: drops are
                # retried (no-op sleeps), lost acks resend -> duplicates
                tr = FaultyTransport(queue, seed=h, p_drop=0.3,
                                     p_ack_loss=0.2)
            producers.append(ShardProducer(h, prod.shards[h], tr,
                                           sleep=lambda s: None))

        def step():
            for h, p in enumerate(producers):
                sh = prod.shards[h]
                sh.apply_rows(truth.perf.shards[h].extract_rows(
                    np.arange(sh.n_procs)))
                p.flush(heartbeat=False)
            monitor.poll()
            return monitor.force_detect()

        step()                                   # warmup (jit, first pin)
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            report = step()
        results[variant] = (time.perf_counter() - t0) / reps
        got = [(a.proc, a.vid) for a in report.abnormal]
        assert got == ab_ref, \
            f"monitor ({variant}) diverged from one-shot: {got} != {ab_ref}"
    return results["clean"], results["faulty"], n_hosts, n_faulty


def bench_socket_ingest(n_producers: int, *, rounds: int = 3,
                        backend: str = "numpy", n_comp: int = 12,
                        conn_cap: int = 128,
                        deadline_s: float = 300.0) -> Dict:
    """Multi-thousand-host fan-in over REAL loopback sockets.

    ``n_producers`` single-proc hosts stream ``rounds`` flushes — one
    full seed round, then steady-state single-column drifts — through at
    most ``conn_cap`` shared ``SocketTransport`` connections into one
    ``SocketServer`` + resident ``Monitor``.  The streamed store and
    detection are asserted bit-identical to the one-shot run on the
    producers' own store, and the wire-level delta compression is priced
    against a full-row baseline: a second ``DeltaEncoder(compress=False)``
    encodes the SAME deltas (resends included) purely to count bytes.
    Returns the metrics row dict; ``socket_ingest_s`` covers flush +
    drain for all rounds (including the baseline pricing overhead, so
    ``socket_deltas_per_s`` is a lower bound on ingest throughput)."""
    from repro.core.graph import PPG
    from repro.core.shard import ShardedStore, shard_ranges
    from repro.monitor import (Monitor, ProducerLink, ShardProducer,
                               SocketServer, SocketTransport, stores_equal)
    from repro.monitor.chaos import _ab_key, build_chaos_psg
    from repro.monitor.producer import ShardDelta
    from repro.monitor.transport import Transport
    from repro.monitor.wire import DeltaEncoder, encode_message

    class CountingTransport(Transport):
        """Forwards to a shared socket transport; prices the SAME deltas
        as full rows so the compression ratio is measured on identical
        traffic."""

        def __init__(self, inner):
            self.inner = inner
            self.baseline = DeltaEncoder(compress=False)
            self.full_bytes = 0

        def send(self, msg):
            self.inner.send(msg)        # raises on failure: not priced
            if isinstance(msg, ShardDelta):
                self.full_bytes += len(encode_message(msg, self.baseline))

        def recv(self, max_messages=None):
            return self.inner.recv(max_messages)

        def pending(self):
            return self.inner.pending()

    psg = build_chaos_psg(n_comp)
    V = len(psg.vertices)
    n_procs = n_producers                 # one proc per host: fleet fan-in
    ranges = shard_ranges(n_procs, n_producers)
    comps = [v.vid for v in psg.vertices if v.kind == COMP]
    target = comps[len(comps) // 2]
    straggler = n_procs // 3

    server = SocketServer().start()
    monitor = Monitor(psg, ranges, server, detect_every=None,
                      backend=backend)
    prod_store = ShardedStore(ranges, V)
    n_conns = min(conn_cap, n_producers)
    conns = [SocketTransport(server.address, seed=i) for i in range(n_conns)]
    counting = [CountingTransport(tr) for tr in conns]
    producers: List = []
    links: List = []
    try:
        for h in range(n_producers):
            p = ShardProducer(h, prod_store.shards[h],
                              counting[h % n_conns], max_retries=4,
                              base_backoff=0.001, max_backoff=0.01)
            producers.append(p)
            links.append(ProducerLink(p, conns[h % n_conns],
                                      resend_after=2.0))

        def drain(deadline):
            while True:
                if all(monitor.high[h] >= producers[h].seq
                       and not monitor.parked[h]
                       for h in range(n_producers)):
                    return
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"socket ingest did not converge at "
                        f"{n_producers} producers: "
                        f"applied={sum(monitor.high.values())}/"
                        f"{sum(p.seq for p in producers)} "
                        f"server={server.stats()}")
                monitor.poll()
                server.send_acks({h: monitor.acked_seq(h)
                                  for h in range(n_producers)})
                for tr in conns:
                    tr.recv()             # pump acks -> prune unacked
                for link in links:
                    link.tick()
                time.sleep(0.001)

        deadline = time.monotonic() + deadline_s
        marks = []                        # (wire, fullrow) after each round
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            if r == 1:                    # full seed: every column, once
                for vid in range(1, V):
                    t = np.full(n_procs, 0.01 + 0.001 * vid)
                    if vid == target:
                        t[straggler] += 0.05
                    prod_store.set_column(vid, t, samples=1,
                                          counters={"PAPI_TOT_CYC":
                                                    1e6 * vid})
            else:                         # steady state: one column drifts
                t = np.full(n_procs, 0.01 + 0.001 * target + 1e-4 * r)
                t[straggler] += 0.05
                prod_store.set_column(target, t, samples=1)
            for p in producers:
                p.flush(heartbeat=False)
            drain(deadline)
            marks.append((sum(tr.stats["delta_bytes"] for tr in conns),
                          sum(ct.full_bytes for ct in counting)))
        socket_ingest_s = time.perf_counter() - t0
        wire_bytes, fullrow_bytes = marks[-1]
        steady_wire = wire_bytes - marks[0][0]
        steady_full = fullrow_bytes - marks[0][1]

        # ack-prune tail (not timed): deliver the final acks
        tail = time.monotonic() + 30.0
        while any(p.unacked for p in producers) \
                and time.monotonic() < tail:
            server.send_acks({h: monitor.acked_seq(h)
                              for h in range(n_producers)})
            for tr in conns:
                tr.recv()
            time.sleep(0.002)
        assert not any(p.unacked for p in producers), \
            "acks did not prune the producers' unacked buffers"

        report = monitor.force_detect()
        ref_ppg = PPG(psg, n_procs, prod_store)
        ab_ref = detect_abnormal(ref_ppg, backend=backend)
        paths_ref = backtrack(ref_ppg, [], ab_ref)
        assert [_ab_key(a) for a in report.abnormal] \
            == [_ab_key(a) for a in ab_ref], \
            "streamed detection diverged from one-shot"
        assert [(p.start_reason, p.nodes) for p in report.paths] \
            == [(p.start_reason, p.nodes) for p in paths_ref], \
            "streamed backtrack diverged from one-shot"
        assert stores_equal(monitor.store, prod_store, V), \
            "streamed store not bit-identical to the producers' store"
    finally:
        for tr in conns:
            tr.close()
        server.stop()

    deltas = sum(p.seq for p in producers)
    wire_ratio = wire_bytes / max(fullrow_bytes, 1)
    steady_ratio = steady_wire / max(steady_full, 1)
    # the acceptance bar: compressed wire traffic measurably below the
    # full-row baseline over the whole run (the steady-state ratio is
    # far smaller still — one changed column per row)
    assert wire_ratio < 0.9, \
        f"wire compression not measurably below full rows: {wire_ratio:.2f}"
    return {
        "name": f"socket_ingest/{n_producers}hosts",
        "socket_producers": n_producers,
        "socket_conns": n_conns,
        "socket_rounds": rounds,
        "socket_deltas": deltas,
        "socket_ingest_s": socket_ingest_s,
        "socket_deltas_per_s": deltas / max(socket_ingest_s, 1e-9),
        "socket_wire_bytes": wire_bytes,
        "socket_fullrow_bytes": fullrow_bytes,
        "socket_wire_ratio": wire_ratio,
        "socket_steady_ratio": steady_ratio,
        "detect_backend": backend,
    }


def run(smoke: bool = False) -> List[Dict]:
    scales = SMOKE_SCALES if smoke else FULL_SCALES
    detect_backend = "numpy"
    if not smoke:
        try:
            import jax                                        # noqa: F401
            detect_backend = "jax"
        except ImportError:
            pass
    rows: List[Dict] = []
    for n_procs in scales:
        psg = build_step_psg(n_procs_hint=n_procs)
        target = next(v.vid for v in psg.vertices if v.kind == COMP)
        straggler = min(4, n_procs - 1)

        @vectorized_base_times
        def time_at(procs, vid, n):
            t = np.full(procs.shape, 0.128 / n)
            if vid == target:
                t[procs == straggler] += 0.05
            return t

        series_scales = [max(n_procs // 4, 2), max(n_procs // 2, 2), n_procs]
        t0 = time.perf_counter()
        series = simulate_series(psg, series_scales, time_at)
        build_s = simulate_series_s = time.perf_counter() - t0
        top = series[n_procs]

        # -- replay engine: wavefront vs the PR-2-style sequential loop --
        hpsg = build_p2p_heavy_psg(n_procs_hint=n_procs)

        @vectorized_base_times
        def base_vec(procs, vid):
            return np.full(procs.shape, 0.128 / n_procs)

        def base_scalar(p, vid):
            return 0.128 / n_procs

        base_scalar.scalana_vectorized = False   # PR-2 baseline: P·V calls
        simulate(hpsg, n_procs, base_vec, p2p="wavefront")      # warmup
        t0 = time.perf_counter()
        res_wave = simulate(hpsg, n_procs, base_vec, p2p="wavefront")
        simulate_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        res_seq = simulate(hpsg, n_procs, base_scalar, p2p="sequential")
        simulate_seq_s = time.perf_counter() - t0
        assert np.array_equal(res_wave.ppg.times_matrix(),
                              res_seq.ppg.times_matrix()) \
            and res_wave.clocks == res_seq.clocks, \
            "wavefront and sequential replay disagree"
        simulate_speedup = simulate_seq_s / max(simulate_s, 1e-12)
        if not smoke and n_procs == max(scales):
            assert simulate_speedup >= 10.0, \
                f"replay engine speedup {simulate_speedup:.1f}x < 10x " \
                f"at {n_procs} procs"

        # detect_s is the DEFAULT-configuration number: backend=None
        # (auto).  On CPU-only jax with host-side stores auto resolves
        # to numpy — the dispatch-bound jitted path is ~10x slower there
        # — so this must track detect_numpy_s; the explicit jitted
        # timing lives in detect_jax_s.
        t0 = time.perf_counter()
        ns = detect_non_scalable(series)
        ab = detect_abnormal(top)
        detect_s = time.perf_counter() - t0

        detect_np_s = detect_s
        detect_jax_s = 0.0
        if detect_backend == "jax":
            t0 = time.perf_counter()
            ns_np = detect_non_scalable(series, backend="numpy")
            ab_np = detect_abnormal(top, backend="numpy")
            detect_np_s = time.perf_counter() - t0
            # warm the jit caches so detect_jax_s reports steady-state
            # latency, not trace+compile
            detect_non_scalable(series, backend="jax")
            detect_abnormal(top, backend="jax")
            t0 = time.perf_counter()
            ns_jx = detect_non_scalable(series, backend="jax")
            ab_jx = detect_abnormal(top, backend="jax")
            detect_jax_s = time.perf_counter() - t0
            assert [d.vid for d in ns] == [d.vid for d in ns_np] \
                == [d.vid for d in ns_jx] \
                and [(a.proc, a.vid) for a in ab] == [(a.proc, a.vid)
                                                     for a in ab_np] \
                == [(a.proc, a.vid) for a in ab_jx], \
                "auto, numpy and jitted detection disagree"
            import jax as _jax
            if _jax.default_backend() == "cpu":
                # the auto-backend acceptance bar: with jax importable
                # but CPU-only, the default path must stay numpy-fast
                # (the old auto->jax pessimization was ~10x slower)
                assert detect_s <= 2.0 * detect_np_s + 0.05, \
                    f"backend=auto not tracking numpy on CPU-only jax: " \
                    f"{detect_s:.4f}s vs numpy {detect_np_s:.4f}s " \
                    f"at {n_procs} procs"

        t0 = time.perf_counter()
        paths = backtrack(top, ns, ab)
        rcs = root_causes(paths, psg, ppg=top)
        pipeline_backtrack_s = time.perf_counter() - t0

        # -- backtracking over many stragglers ---------------------------
        # many distinct stragglers at a mid-chain comp vertex: hundreds of
        # flagged (proc, vertex) pairs whose causal walks are long and
        # disjoint — the regime Algorithm 1 faces at scale
        comps = [v.vid for v in psg.vertices if v.kind == COMP]
        mid = comps[len(comps) // 2]

        @vectorized_base_times
        def straggle(procs, vid):
            t = np.full(procs.shape, 0.128 / n_procs)
            if vid == mid:
                sel = procs % 16 == 5
                t[sel] += 0.05 * (1.0 + (procs[sel] % 7))
            return t

        res_bt = simulate(psg, n_procs, straggle)
        ab_bt = detect_abnormal(res_bt.ppg, top_k=4096, backend="numpy")
        t0 = time.perf_counter()
        backtrack(res_bt.ppg, [], ab_bt)
        backtrack_s = time.perf_counter() - t0
        if not smoke and n_procs == max(scales):
            assert len(ab_bt) >= 256, \
                f"backtrack scenario flagged only {len(ab_bt)} pairs"

        # -- streamed shard merge ---------------------------------------
        res_sh = simulate(psg, n_procs, straggle, shards=8)
        t0 = time.perf_counter()
        merged = PerfStore.from_shards(res_sh.shards, n_procs=n_procs)
        shard_merge_s = time.perf_counter() - t0
        V = len(psg.vertices)
        assert np.array_equal(merged.time_matrix(V),
                              res_bt.ppg.perf.time_matrix(V)), \
            "shard-merged store differs from single-store replay"

        # -- device-resident detection (sharded store -> device buffers) -
        # the online regime: the jitted abnormal detector feeds from
        # per-host device blocks; after the first (full) pin, each call
        # re-uploads only the rows written since the last one — transfer
        # is O(dirty rows · V), asserted below against the full pin
        detect_device_s = detect_host_fed_s = 0.0
        device_full_bytes = device_dirty_bytes = device_dirty_rows = 0
        if detect_backend == "jax":
            sh_ppg = res_sh.ppg
            ab_dev = detect_abnormal(sh_ppg, backend="jax")  # pin + warm
            view = sh_ppg.device_view()
            device_full_bytes = view.last_upload_bytes
            ab_host = detect_abnormal(res_bt.ppg, backend="jax")  # warm
            assert [(a.proc, a.vid) for a in ab_dev] == \
                [(a.proc, a.vid) for a in ab_host], \
                "device-fed and host-fed abnormal detection disagree"
            t0 = time.perf_counter()
            detect_abnormal(sh_ppg, backend="jax")     # steady state
            detect_device_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            detect_abnormal(res_bt.ppg, backend="jax")
            detect_host_fed_s = time.perf_counter() - t0
            # an online step: a handful of rows change, then detect
            dirty = np.arange(0, n_procs, max(n_procs // 16, 1))[:16]
            sh_ppg.perf.set_entries(dirty, mid, 0.5)
            detect_abnormal(sh_ppg, backend="jax")
            device_dirty_rows = view.last_upload_rows
            device_dirty_bytes = view.last_upload_bytes
            assert device_dirty_rows == dirty.size, \
                f"expected {dirty.size} dirty rows, " \
                f"uploaded {device_dirty_rows}"
            # per-call transfer scales with dirty rows, not O(P·V):
            # dirty/full ratio must track rows/P (2x layout slack)
            assert device_dirty_bytes * n_procs <= \
                2 * device_full_bytes * device_dirty_rows, \
                f"incremental upload not O(dirty rows): " \
                f"{device_dirty_bytes}B for {device_dirty_rows} rows vs " \
                f"{device_full_bytes}B full pin at {n_procs} procs"

        # -- fused one-launch detection + historical-scale cache ---------
        # one full device-fed detect CYCLE (non-scalable over the series
        # + abnormal at the top scale), two ways: the fused ops with cold
        # merged-column caches (a first call), and the steady state —
        # warm caches, a 16-row dirty write on the live scale, exactly 2
        # fused launches (asserted via the launch-count seam, not
        # inferred)
        detect_fused_s = detect_cached_steady_s = 0.0
        detect_cached_launches = 0
        if detect_backend == "jax":
            from repro.kernels.detect_fused import ops as fused_ops

            def _time_at_scale(n):
                # the series straggler base, pinned to one scale (plain
                # simulate() passes (procs, vid), not the series' 3-arg
                # form)
                @vectorized_base_times
                def f(procs, vid):
                    t = np.full(procs.shape, 0.128 / n)
                    if vid == target:
                        t[procs == straggler] += 0.05
                    return t
                return f

            series_sh = {n: simulate(psg, n, _time_at_scale(n),
                                     shards=min(8, n)).ppg
                         for n in series_scales}
            top_sh = series_sh[n_procs]
            views = [series_sh[n].device_view() for n in sorted(series_sh)]

            def fused_cycle():
                return (detect_non_scalable(series_sh, backend="jax"),
                        detect_abnormal(top_sh, backend="jax"))

            fused_cycle()                       # warm fused + fill caches
            for v in views[:-1]:                # cold caches: a 1st call
                v.cache_merged_column(None)
            t0 = time.perf_counter()
            fused_cycle()
            detect_fused_s = time.perf_counter() - t0

            # steady state: caches warm, 16 rows written on the live
            # scale since the last detect
            dirty = np.arange(0, n_procs, max(n_procs // 16, 1))[:16]
            top_sh.perf.set_entries(dirty, mid, 0.5)
            fused_ops.reset_launch_counts()
            t0 = time.perf_counter()
            fused_cycle()
            detect_cached_steady_s = time.perf_counter() - t0
            detect_cached_launches = sum(fused_ops.launch_counts.values())
            assert dict(fused_ops.launch_counts) == \
                {"non_scalable_live": 1, "abnormal": 1}, \
                f"steady-state detect not 2 fused launches: " \
                f"{dict(fused_ops.launch_counts)}"

        # -- always-on monitor: steady-state ingest -> detect latency ----
        # per-host producers stream full-row deltas into a resident
        # Monitor; one "step" is flush + poll + detect.  The faulty
        # variant puts ~10% of the hosts behind a seeded lossy transport
        # (drops retried with no-op backoff sleeps, lost acks causing
        # duplicates), so the number reports the protocol overhead of a
        # misbehaving fleet, not time.sleep.  Both variants must end bit-
        # identical to the one-shot detection on the truth store.
        (monitor_ingest_detect_s, monitor_faulty_ingest_detect_s,
         monitor_hosts, monitor_faulty_hosts) = bench_monitor(
            psg, target, straggler, n_procs, detect_backend)

        # -- run store: record / reload / diff latency per scale ---------
        # the persistent regression service priced on this scale's
        # series: one record through the checkpoint seam (curves +
        # detect output + top-scale PPG), one reload, one cross-run diff
        (run_store_record_s, run_store_load_s,
         run_store_diff_s) = bench_run_store(series, ns, ab)

        nbytes = top.nbytes()
        comm_nbytes = top.comm.nbytes()
        clique_nbytes = 16 * sum(
            sum(len(g_) * (len(g_) - 1) for g_ in top.comm.groups_of(v.vid))
            for v in psg.by_kind(COMM))
        # O(P) guarantee: implicit groups, never the materialized clique
        assert comm_nbytes < 64 * len(psg.vertices) * n_procs, \
            f"comm storage not O(P): {comm_nbytes} bytes at {n_procs} procs"
        # column-sparse counters must beat the dense (P, V) layout
        counter_nbytes = top.perf.counter_nbytes()
        counter_dense = top.perf.counter_dense_nbytes()
        assert counter_nbytes < counter_dense, \
            f"counter storage not sparse: {counter_nbytes} >= {counter_dense}"
        found = any(node[1] == target for node, _, _ in rcs)
        row = {
            "name": f"graph_scale/{n_procs}procs",
            "n_procs": n_procs,
            "simulate_s": simulate_s,
            "simulate_seq_s": simulate_seq_s,
            "simulate_speedup": simulate_speedup,
            "simulate_series_s": simulate_series_s,
            "build_s": build_s,
            "detect_s": detect_s,
            "detect_backend": detect_backend,
            "detect_numpy_s": detect_np_s,
            "detect_jax_s": detect_jax_s,
            "pipeline_backtrack_s": pipeline_backtrack_s,
            "backtrack_s": backtrack_s,
            "backtrack_flagged": len(ab_bt),
            "shard_merge_s": shard_merge_s,
            "shard_hosts": len(res_sh.shards),
            "detect_device_s": detect_device_s,
            "detect_host_fed_s": detect_host_fed_s,
            "detect_fused_s": detect_fused_s,
            "detect_cached_steady_s": detect_cached_steady_s,
            "detect_cached_launches": detect_cached_launches,
            "monitor_ingest_detect_s": monitor_ingest_detect_s,
            "monitor_faulty_ingest_detect_s": monitor_faulty_ingest_detect_s,
            "monitor_hosts": monitor_hosts,
            "monitor_faulty_hosts": monitor_faulty_hosts,
            "run_store_record_s": run_store_record_s,
            "run_store_load_s": run_store_load_s,
            "run_store_diff_s": run_store_diff_s,
            "device_full_bytes": device_full_bytes,
            "device_dirty_bytes": device_dirty_bytes,
            "device_dirty_rows": device_dirty_rows,
            "ppg_bytes": nbytes,
            "comm_bytes": comm_nbytes,
            "clique_equiv_bytes": clique_nbytes,
            "counter_bytes": counter_nbytes,
            "counter_dense_equiv_bytes": counter_dense,
            "paths": len(paths),
            "root_cause_found": found,
        }
        rows.append(row)
        emit(row["name"],
             (build_s + detect_s + pipeline_backtrack_s) * 1e6,
             f"simulate_s={simulate_s:.4f};simulate_seq_s="
             f"{simulate_seq_s:.4f};simulate_speedup="
             f"{simulate_speedup:.1f};simulate_series_s="
             f"{simulate_series_s:.3f};detect_s={detect_s:.4f};"
             f"detect_backend={detect_backend};detect_numpy_s="
             f"{detect_np_s:.4f};detect_jax_s={detect_jax_s:.4f};"
             f"backtrack_s={backtrack_s:.3f};"
             f"backtrack_flagged={len(ab_bt)};"
             f"shard_merge_s={shard_merge_s:.4f};"
             f"detect_device_s={detect_device_s:.4f};"
             f"detect_host_fed_s={detect_host_fed_s:.4f};"
             f"detect_fused_s={detect_fused_s:.4f};"
             f"detect_cached_steady_s={detect_cached_steady_s:.4f};"
             f"detect_cached_launches={detect_cached_launches};"
             f"monitor_ingest_detect_s={monitor_ingest_detect_s:.4f};"
             f"monitor_faulty_ingest_detect_s="
             f"{monitor_faulty_ingest_detect_s:.4f};"
             f"monitor_hosts={monitor_hosts};"
             f"monitor_faulty_hosts={monitor_faulty_hosts};"
             f"run_store_record_s={run_store_record_s:.4f};"
             f"run_store_load_s={run_store_load_s:.4f};"
             f"run_store_diff_s={run_store_diff_s:.4f};"
             f"device_full_bytes={device_full_bytes};"
             f"device_dirty_bytes={device_dirty_bytes};"
             f"device_dirty_rows={device_dirty_rows};"
             f"ppg_bytes={nbytes};comm_bytes={comm_nbytes};"
             f"clique_equiv_bytes={clique_nbytes};"
             f"counter_bytes={counter_nbytes};"
             f"counter_dense_equiv_bytes={counter_dense};"
             f"paths={len(paths)};root_cause_found={found}")

    # -- real-socket ingest fan-in ------------------------------------
    # 512/2048/4096 loopback producers (8/32 in smoke) through <= 128
    # shared connections; one full seed round, then steady-state drift
    # rounds.  Streamed store + detection asserted bit-identical to the
    # one-shot run; the delta-compression ratio vs the full-row wire
    # baseline lands in BENCH_graph_scale.json.
    socket_scales = SMOKE_SCALES if smoke else (512, 2048, 4096)
    for n_hosts in socket_scales:
        srow = bench_socket_ingest(n_hosts, backend=detect_backend)
        rows.append(srow)
        emit(srow["name"], srow["socket_ingest_s"] * 1e6,
             f"producers={srow['socket_producers']};"
             f"conns={srow['socket_conns']};"
             f"deltas={srow['socket_deltas']};"
             f"deltas_per_s={srow['socket_deltas_per_s']:.0f};"
             f"wire_bytes={srow['socket_wire_bytes']};"
             f"fullrow_bytes={srow['socket_fullrow_bytes']};"
             f"wire_ratio={srow['socket_wire_ratio']:.3f};"
             f"steady_ratio={srow['socket_steady_ratio']:.3f}")

    # -- run store at fleet scale: clustered record + cross-run diff --
    # a 65536-proc clean/slowed pair (2048 in smoke) compressed to <= 64
    # behavior representatives on record, then diffed; compression is
    # asserted >= 100x on full runs and the regressed cluster must hold
    # every true culprit proc
    fleet_procs = 2048 if smoke else 65536
    frow = bench_run_store_fleet(fleet_procs, smoke=smoke)
    rows.append(frow)
    emit(frow["name"],
         (frow["run_store_cluster_record_s"]
          + frow["run_store_fleet_diff_s"]) * 1e6,
         f"build_s={frow['run_store_fleet_build_s']:.4f};"
         f"cluster_record_s={frow['run_store_cluster_record_s']:.4f};"
         f"diff_s={frow['run_store_fleet_diff_s']:.4f};"
         f"reps={frow['run_store_reps']};"
         f"compression={frow['run_store_compression']:.0f};"
         f"regressed_cluster={frow['run_store_regressed_cluster']};"
         f"culprits_in_cluster={frow['run_store_culprits_in_cluster']}"
         f"/{frow['run_store_culprits']}")
    return rows


if __name__ == "__main__":
    run()
