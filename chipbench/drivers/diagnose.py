"""Driver of the diagnosis loop: the time-to-diagnosis path.

Traffic keys (``traffic/<name>.json``): ``scales`` (the finished runs'
sizes and the live one, P, the largest), ``procs_per_host``,
``hosts_per_cycle`` (hosts whose newest rows each cycle brings),
``warmup_cycles`` (cycles run in set-up),
``jitter_sigma`` (lognormal spread of every time), ``straggler_delay``
(the straggler's extra time as a share of the step), ``fleet_chip`` (the
device kind whose peaks give the per-vertex times), ``sample_cycles``
(how many of the window's cycles are compared with the reference) and
``detect`` (the program's detection constants, which the reference
repeats).

Set-up builds the job's program structure graph (PSG) from its train
step's jaxpr, abstractly (no weights), appends the data-parallel
gradient all-reduce, and generates the fleet from the seed: each
top-level vertex's time is its static FLOP and byte roofline time on
``fleet_chip``, times lognormal jitter, divided by the scale's share of
P (strong scaling) except for one vertex that keeps its time; one
process carries a delay at the first top-level loop; the all-reduce
waits for the slowest arrival.  The fleet lands in the program's
per-host sharded stores, one per scale, and a few warm-up cycles compile
and upload everything.

Each window cycle, ``hosts_per_cycle`` hosts of the largest scale bring
their newest rows (``PerfShard.apply_rows``; the straggler re-sends its
row unchanged, so the slowest arrival stays where the seed put it and
every seed's cycles flag the same entries), then
``detect_non_scalable`` + ``detect_abnormal`` + ``backtrack`` +
``root_causes`` + ``render_report`` run; a cycle's time runs from
handing the rows to the store to the rendered report.  Closed loop: one
cycle follows another.
"""
from __future__ import annotations

import gc
import os
import time
from typing import Dict, List, Tuple

import numpy as np

from harness import BENCH_DIR, load_module, peaks_for, seed_words

ref_detect = load_module(os.path.join(BENCH_DIR, "references", "detect.py"),
                         "reference_detect")


class Fleet:
    """The benchmark's own fleet data: per-(process, vertex) times at
    every scale, and the rows each cycle brings."""

    def __init__(self, psg_info: Dict, traffic: Dict, seed: int):
        self.t = traffic
        self.V = psg_info["V"]
        self.comp = psg_info["comp"]          # top-level compute vertices
        self.ar = psg_info["ar"]              # the all-reduce vertex
        self.base = psg_info["base"]          # (V,) seconds at P
        self.tc = psg_info["tc"]              # all-reduce transfer seconds
        self.target = psg_info["target"]
        self.serial = psg_info["serial"]
        self.scales = sorted(traffic["scales"])
        self.P = self.scales[-1]
        self.pph = traffic["procs_per_host"]
        self.rng = np.random.default_rng(seed_words(seed, 2))
        self.straggler = int(self.rng.integers(self.P))
        self.delay = traffic["straggler_delay"] * float(
            self.base[self.comp].sum())
        self.series = {n: self._matrix(n) for n in self.scales}
        live = self.series[self.P]
        self.arrival = live[:, self.comp].sum(axis=1)

    def _times(self, n: int, procs: np.ndarray) -> np.ndarray:
        """(len(procs), V) compute times at scale n, all-reduce column
        left for :meth:`_allreduce`."""
        scale = np.where(np.arange(self.V) == self.serial, 1.0, self.P / n)
        t = np.zeros((procs.size, self.V))
        z = self.rng.standard_normal((procs.size, len(self.comp)))
        t[:, self.comp] = (self.base * scale)[self.comp] * np.exp(
            self.t["jitter_sigma"] * z)
        if n == self.P:
            t[procs == self.straggler, self.target] += self.delay
        return t

    def _matrix(self, n: int) -> np.ndarray:
        t = self._times(n, np.arange(n))
        arrival = t[:, self.comp].sum(axis=1)
        t[:, self.ar] = arrival.max() - arrival + self.tc
        return t

    def cycle_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """The next cycle's (procs, rows) of the largest scale: the hosts
        that report, their new times, and their wait at the all-reduce
        for the slowest arrival known now."""
        hosts = self.rng.choice(self.P // self.pph,
                                self.t["hosts_per_cycle"], replace=False)
        procs = (hosts[:, None] * self.pph
                 + np.arange(self.pph)[None, :]).ravel()
        rows = self._times(self.P, procs)
        # a redrawn straggler would move the slowest arrival, and with it
        # the waits of the rows written after it, until the fastest
        # processes' waits pass the abnormal threshold on some seeds
        late = procs == self.straggler
        rows[late] = self.series[self.P][procs[late]]
        self.arrival[procs] = rows[:, self.comp].sum(axis=1)
        rows[:, self.ar] = self.arrival.max() - self.arrival[procs] + self.tc
        self.series[self.P][procs] = rows
        return procs, rows


def warm_slices(k: int) -> None:
    """Compile every slice of a top-k order that a window can ask for:
    the program cuts its (k,) device order to the number of flagged
    entries (``detect_jax.abnormal_topk_view``), a new shape whenever
    that number changes.  The order is sliced as the program holds it:
    uncommitted (a ``device_put`` array compiles other programs), inside
    the detection precision's context."""
    import jax.numpy as jnp
    from repro.core.detect_jax import precision
    _, ctx = precision()
    with ctx:
        order = jnp.zeros((k,), jnp.int32)
        for n in range(k + 1):
            order[:n].block_until_ready()


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.tr = cell.traffic
        self.attempted = self.failed = 0

    # -- set-up --------------------------------------------------------
    def build_psg(self):
        """The job's contracted PSG, built abstractly from its train
        step, plus the data-parallel gradient all-reduce."""
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
        from repro.core import COMM, GraphProfiler
        from repro.training import Trainer

        arch = ArchConfig(**self.cell.config["arch"])
        shape = self.cell.config["shape"]
        trainer = Trainer(RunConfig(arch=arch.name), arch_cfg=arch,
                          shape=ShapeConfig(self.cell.name, shape["seq_len"],
                                            shape["batch"], "train"))
        state = jax.eval_shape(trainer.init_state)
        tokens = jax.ShapeDtypeStruct(
            (shape["batch"], shape["seq_len"] + 1), jnp.int32)
        prof = GraphProfiler(trainer.train_step_fn,
                             (state, {"tokens": tokens}),
                             max_loop_depth=trainer.run.max_loop_depth)
        psg = prof.psg
        tops = list(psg.children(psg.root))
        grad_bytes = float(sum(x.size * x.dtype.itemsize
                               for x in jax.tree.leaves(state.params)))
        ar = psg.new_vertex(COMM, "psum(grads)", parent=psg.root,
                            source="src/repro/optim/adamw.py:60")
        ar.comm_kind, ar.comm_bytes = "all_reduce", grad_bytes
        psg.add_edge(tops[-1], ar.vid, "data")
        psg.add_edge(psg.root, ar.vid, "control")
        return psg, tops, ar.vid, grad_bytes

    def setup(self):
        from repro.core import LOOP, ShardedStore, build_ppg
        from repro.core.graph import RowBlock
        self.RowBlock = RowBlock

        t0 = time.perf_counter()
        psg, tops, ar, grad_bytes = self.build_psg()
        t_psg = time.perf_counter()
        chip = peaks_for(self.tr["fleet_chip"])
        V = len(psg.vertices)
        base = np.zeros(V)
        for v in tops:
            vx = psg.vertices[v]
            base[v] = max(vx.flops / chip["bf16_flops_per_s"],
                          vx.bytes / chip["hbm_bytes_per_s"])
        comp = [v for v in tops if base[v] > 0.0]
        target = next(v for v in tops if psg.vertices[v].kind == LOOP)
        serial = max((v for v in comp if v != target), key=lambda v: base[v])
        scales = sorted(self.tr["scales"])
        # ring all-reduce: each chip sends and receives 2 (n-1)/n of the
        # gradient over its interconnect
        tc = 2.0 * grad_bytes * (scales[-1] - 1) / scales[-1] \
            / chip["ici_bytes_per_s"]
        self.psg, self.top = psg, tops + [ar]
        self.fleet = Fleet({"V": V, "comp": comp, "ar": ar, "base": base,
                            "tc": tc, "target": target, "serial": serial},
                           self.tr, self.cell.seed)
        self.counters = {"flops": (np.asarray(comp),
                                   np.asarray([psg.vertices[v].flops
                                               for v in comp], float)),
                         "bytes": (np.asarray(comp),
                                   np.asarray([psg.vertices[v].bytes
                                               for v in comp], float)),
                         "comm_bytes": (np.asarray([ar]),
                                        np.asarray([grad_bytes]))}
        self.initial = {n: m.copy() for n, m in self.fleet.series.items()}
        self.series = {}
        for n in scales:
            store = ShardedStore([(h * self.fleet.pph, (h + 1) * self.fleet.pph)
                                  for h in range(n // self.fleet.pph)], V)
            t = self.fleet.series[n]
            for sh in store.shards:
                rows = np.arange(sh.proc_start, sh.proc_stop)
                sh.apply_rows(self.block(rows, t[rows]))
            self.series[n] = build_ppg(psg, n, store)
        self.ppg = self.series[scales[-1]]
        self.live = self.ppg.perf
        self.log: List[Tuple[np.ndarray, np.ndarray]] = []
        self.results: List[Tuple[List[Dict], List[Dict], bool]] = []
        t_fleet = time.perf_counter()
        warm_slices(self.tr["detect"]["top_k_abnormal"])
        warm = [self.cycle() for _ in range(self.tr["warmup_cycles"])]
        # set-up's heap (the PSG with its jaxpr, the per-host stores) is
        # long-lived; frozen, the window's full collections scan only
        # what the window allocates, and no longer stall a cycle for it
        gc.collect()
        gc.freeze()
        self.cell.log(f"fleet: V {V}, P {self.fleet.P} on "
                      f"{self.fleet.P // self.fleet.pph} hosts, scales "
                      f"{scales}, straggler ({self.fleet.straggler}, "
                      f"{target}), non-scaling vertex {serial}; set-up: "
                      f"PSG {t_psg - t0:.2f} s, fleet and stores "
                      f"{t_fleet - t_psg:.2f} s, warm-up cycles "
                      f"{[round(w, 3) for w in warm]} s")

    def block(self, procs: np.ndarray, rows: np.ndarray):
        """A host's rows as the program's row-state delta, with the
        counters the replay engine writes (static costs at the compute
        vertices, the wait and bytes at the all-reduce)."""
        k, V, ar = procs.size, self.fleet.V, self.fleet.ar
        local = procs - procs[0]
        mask = np.zeros((k, V), bool)
        mask[:, self.fleet.comp] = True
        mask[:, ar] = True
        counters = {name: (vids, np.broadcast_to(vals, (k, vids.size)).copy(),
                           np.ones((k, vids.size), bool))
                    for name, (vids, vals) in self.counters.items()}
        wait = rows[:, ar] - self.fleet.tc
        counters["wait_s"] = (np.asarray([ar]), wait[:, None],
                              np.ones((k, 1), bool))
        return self.RowBlock(rows=local, n_cols=V, time=rows.copy(),
                             time_var=np.zeros((k, V)),
                             samples=mask.astype(np.int64), mask=mask,
                             counters=counters)

    # -- one cycle -----------------------------------------------------
    def cycle(self) -> float:
        """One diagnosis; returns its seconds (delta to rendered report)."""
        from repro.core import (backtrack, detect_abnormal,
                                detect_non_scalable, render_report,
                                root_causes)
        procs, rows = self.fleet.cycle_rows()
        self.log.append((procs, rows))
        pph, span = self.fleet.pph, self.cell.span
        t0 = time.perf_counter()
        with span("apply"):
            for i in range(0, procs.size, pph):
                host = procs[i:i + pph]
                self.live.shard_of(int(host[0])).apply_rows(
                    self.block(host, rows[i:i + pph]))
        with span("detect"):
            ns = detect_non_scalable(self.series)
            ab = detect_abnormal(self.ppg)
        with span("backtrack"):
            paths = backtrack(self.ppg, ns, ab)
            rcs = root_causes(paths, self.psg, ppg=self.ppg)
        with span("render"):
            report = render_report(self.ppg, ns, ab, paths)
        dt = time.perf_counter() - t0
        s, v = self.fleet.straggler, self.fleet.target
        named = (any(node == (s, v) for node, _, _ in rcs)
                 and f"v{v} p{s} " in report)
        self.results.append((
            [{"vid": d.vid, "slope": d.slope, "share": d.share,
              "times": dict(d.times)} for d in ns],
            [{"vid": a.vid, "proc": a.proc, "time": a.time,
              "typical": a.typical} for a in ab], named))
        return dt

    def views(self):
        return [self.series[n].device_view() for n in sorted(self.series)]

    def window(self):
        from repro.kernels.detect_fused import ops
        ops.reset_launch_counts()
        up0 = sum(v.total_upload_bytes for v in self.views())
        self.first = len(self.results)
        self.times: List[float] = []
        start = time.perf_counter()
        while time.perf_counter() - start < self.cell.seconds:
            self.times.append(self.cycle())
        self.wall = time.perf_counter() - start
        n = len(self.times)
        self.attempted = n
        view = self.ppg.device_view()
        self.cell.raw.update({
            "cycles": n,
            "launches": sum(ops.launch_counts.values()),
            "upload_bytes": sum(v.total_upload_bytes for v in self.views())
            - up0,
            "detect_input_bytes": self.input_bytes(view),
            "cycle_s_total": self.wall})

    def input_bytes(self, view) -> int:
        """What one detection must read, by the shapes: the live scale's
        (P, V) time and variance blocks and each finished scale's merged
        (4, V) column, in the dtype the device holds."""
        itemsize = np.dtype(view.time_blocks()[0].dtype).itemsize
        V, P = self.fleet.V, self.fleet.P
        return itemsize * (2 * P * V + 4 * V * (len(self.series) - 1))

    def end_to_end(self) -> Dict[str, float]:
        ms = np.asarray(self.times) * 1e3
        slow = np.argsort(-ms)[:5]
        self.cell.log(f"cycle ms: quartiles {np.percentile(ms, [25, 50, 75])}"
                      f", slowest {ms[slow].round(1).tolist()} at cycles "
                      f"{slow.tolist()} of {ms.size}")
        return {"diagnose_ms_p95": float(np.percentile(ms, 95)),
                "diagnoses_per_s": len(self.times) / self.wall}

    def release(self):
        del self.series, self.ppg, self.live
        gc.unfreeze()
        gc.collect()

    # -- the comparison ------------------------------------------------
    def sample(self) -> List[int]:
        """The window's cycles compared with the reference: a sample
        drawn from the seed, and the last one."""
        n = len(self.results) - self.first
        rng = np.random.default_rng(seed_words(self.cell.seed, 3))
        k = min(self.tr["sample_cycles"], n)
        picks = set(rng.choice(n, k, replace=False).tolist()) | {n - 1}
        return sorted(self.first + i for i in picks)

    def reference_answers(self, picks: List[int], dtype=np.float64):
        """The reference's answers at the picked cycles, the benchmark's
        own fleet replayed from its initial state and the delta log, the
        reference computed on the times rounded to ``dtype``."""
        series = {n: m.copy() for n, m in self.initial.items()}
        live = series[max(series)]
        d = self.tr["detect"]
        out, done = {}, 0
        for c in picks:
            for procs, rows in self.log[done:c + 1]:
                live[procs] = rows
            done = c + 1
            cast = {n: m.astype(dtype).astype(np.float64)
                    for n, m in series.items()}
            out[c] = (
                ref_detect.non_scalable(
                    cast, self.top, ideal_slope=d["ideal_slope"],
                    slope_margin=d["slope_margin"],
                    min_share=d["min_share_non_scalable"],
                    top_k=d["top_k_non_scalable"]),
                ref_detect.abnormal(
                    cast[max(cast)], self.top, abnorm_thd=d["abnorm_thd"],
                    min_share=d["min_share_abnormal"],
                    top_k=d["top_k_abnormal"]))
        return out

    def readings(self, ref, prog=None) -> Dict[str, float]:
        """Cycles whose flagged sets differ, and the widest value gap,
        over the picked cycles; ``prog`` defaults to the program's own
        answers (pass a control's answers to read the control)."""
        ns_bad = ab_bad = 0
        gap = 0.0
        for c, (r_ns, r_ab) in ref.items():
            p_ns, p_ab = (self.results[c][:2] if prog is None else prog[c])
            ns_d, ab_d, g = ref_detect.compare(p_ns, p_ab, r_ns, r_ab)
            ns_bad += ns_d
            ab_bad += ab_d
            gap = max(gap, g)
        return {"non_scalable_mismatch": float(ns_bad),
                "abnormal_mismatch": float(ab_bad), "value_gap": gap}

    def verify(self) -> List[Tuple[str, float, float]]:
        picks = self.sample()
        readings = self.readings(self.reference_answers(picks))
        missed = sum(not named for _, _, named in self.results[self.first:])
        lim = self.cell.limits
        self.cell.log(f"compared {len(picks)} of "
                      f"{len(self.results) - self.first} window cycles")
        return [(k, readings[k], lim[k]) for k in
                ("non_scalable_mismatch", "abnormal_mismatch",
                 "value_gap")] + [("straggler_missed", float(missed),
                                   lim["straggler_missed"])]
