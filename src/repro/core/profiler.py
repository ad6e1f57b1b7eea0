"""Graph-guided sampling profiler (paper §III-B).

OS-interrupt sampling of a running XLA executable cannot attribute time to
IR vertices (the program is a single fused binary), so the TPU/JAX-native
equivalent samples in *step space*: every K-th step is executed through an
instrumented jaxpr interpreter that times each top-level PSG vertex
(`block_until_ready` fences); all other steps run the compiled fast path.
Expected overhead ≈ (instrumented_step/compiled_step − 1)/K, directly
tunable like the paper's sampling frequency — measured by
benchmarks/bench_overhead.py.

Per-vertex performance vectors combine this measured channel with the
static counter channel (flops/bytes from repro.core.costs), the PAPI
analogue.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import psg as psg_lib
from repro.core.contraction import contract
from repro.core.graph import COMM, PSG, PerfVector
from repro.core.spans import span


def _block(x):
    return jax.tree.map(
        lambda v: v.block_until_ready() if hasattr(v, "block_until_ready")
        else v, x)


class _TimedEval:
    """eval_jaxpr with a per-top-level-eqn timing callback.

    Each value is dropped right after the equation that reads it last:
    run one equation at a time, a train step would otherwise hold every
    intermediate of the step at once (22.6 GB for mamba2-130m at batch 8,
    seq 4096 — more than a 16 GB chip).  Each equation's fence is the
    span ``profiler.fence``, its stat ``vid`` the equation's entry of
    ``vids``."""

    def __init__(self, closed_jaxpr, vids: Sequence[int]):
        from jax._src.core import Literal
        self.closed = closed_jaxpr
        self.vids = list(vids)
        jaxpr = closed_jaxpr.jaxpr
        last: Dict[Any, int] = {}
        for idx, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    last[v] = idx
        for v in jaxpr.outvars:
            if not isinstance(v, Literal):
                last.pop(v, None)
        self._dead_after: List[List[Any]] = [[] for _ in jaxpr.eqns]
        for v, idx in last.items():
            self._dead_after[idx].append(v)

    def __call__(self, args: Sequence[Any],
                 on_eqn: Callable[[int, float], None]) -> List[Any]:
        from jax._src.core import DropVar, Literal
        jaxpr = self.closed.jaxpr
        env: Dict[Any, Any] = {}

        def read(v):
            return v.val if isinstance(v, Literal) else env[v]

        def write(v, val):
            if not isinstance(v, DropVar):
                env[v] = val

        for var, val in zip(jaxpr.constvars, self.closed.consts):
            write(var, val)
        flat = list(args)
        assert len(flat) == len(jaxpr.invars), \
            (len(flat), len(jaxpr.invars))
        for var, val in zip(jaxpr.invars, flat):
            write(var, val)

        for idx, eqn in enumerate(jaxpr.eqns):
            invals = [read(v) for v in eqn.invars]
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            t0 = time.perf_counter()
            ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
            with span("profiler.fence", vid=self.vids[idx]):
                _block(ans)
            on_eqn(idx, time.perf_counter() - t0)
            del invals
            if eqn.primitive.multiple_results:
                for var, val in zip(eqn.outvars, ans):
                    write(var, val)
            else:
                write(eqn.outvars[0], ans)
            for v in self._dead_after[idx]:
                env.pop(v, None)
        return [read(v) for v in jaxpr.outvars]


class GraphProfiler:
    """Profiles ``fn`` at PSG-vertex granularity with step-space sampling.

    Storage accounting mirrors the paper: per-vertex perf vectors (KBs)
    instead of per-event traces (GBs).
    """

    def __init__(self, fn: Callable, example_args: Sequence[Any], *,
                 sample_every: int = 16, max_loop_depth: int = 10,
                 static_argnums: Tuple[int, ...] = ()):
        self.fn = fn
        self.sample_every = max(int(sample_every), 1)
        self.closed = jax.make_jaxpr(fn)(*example_args)
        self.psg_full = psg_lib.build_psg(jaxpr=self.closed)
        self.psg, self.mapping = contract(self.psg_full, max_loop_depth)
        # top-level eqn index -> contracted vertex id
        top = psg_lib.top_level_order(self.psg_full)
        self._eqn_to_vertex = [self.mapping.get(vid, self.psg.root)
                               for vid in top]
        self._compiled = jax.jit(fn)
        self._evaluator = _TimedEval(self.closed, self._eqn_to_vertex)
        # accumulators
        self._vertex_times: Dict[int, List[float]] = {}
        self.step_times: List[float] = []
        self.sampled_steps = 0
        self.total_steps = 0

    # ------------------------------------------------------------------
    def step(self, *args) -> Any:
        """Run one step; every K-th step is the instrumented sampled run."""
        self.total_steps += 1
        if self.total_steps % self.sample_every == 0:
            return self._sampled_step(*args)
        with span("profiler.compiled_step"):
            t0 = time.perf_counter()
            out = self._compiled(*args)
            _block(out)
            self.step_times.append(time.perf_counter() - t0)
        return out

    def _sampled_step(self, *args) -> Any:
        self.sampled_steps += 1
        flat, _ = jax.tree.flatten(args)

        def on_eqn(idx: int, dt: float):
            vid = self._eqn_to_vertex[idx]
            self._vertex_times.setdefault(vid, []).append(dt)

        with span("profiler.sampled_step",
                  eqns=len(self.closed.jaxpr.eqns)):
            t0 = time.perf_counter()
            outs = self._evaluator(flat, on_eqn)
            self.step_times.append(time.perf_counter() - t0)
            out_tree = jax.tree.structure(
                jax.eval_shape(self.fn, *args))
            return jax.tree.unflatten(out_tree, outs)

    # ------------------------------------------------------------------
    def perf_vectors(self) -> Dict[int, PerfVector]:
        """Per-vertex perf vectors: measured time + static counters."""
        out: Dict[int, PerfVector] = {}
        for v in self.psg.vertices:
            times = self._vertex_times.get(v.vid, [])
            counters = {"flops": v.flops, "bytes": v.bytes,
                        "comm_bytes": v.comm_bytes}
            if times:
                t = float(np.mean(times))
                counters["flops_per_sec"] = v.flops / t if t > 0 else 0.0
                out[v.vid] = PerfVector(time=t,
                                        time_var=float(np.var(times)),
                                        samples=len(times),
                                        counters=counters)
            elif v.flops or v.comm_bytes:
                out[v.vid] = PerfVector(time=0.0, samples=0,
                                        counters=counters)
        return out

    def perf_shard(self, proc_start: int = 0, n_procs: int = 1):
        """This host's measured profile as a proc-range shard.

        Returns a :class:`~repro.core.shard.PerfShard` covering global
        processes ``[proc_start, proc_start + n_procs)``, each local row
        filled with this profiler's per-vertex vectors (an SPMD host runs
        identical top-level structure on its local processes).  Hosts
        profile independently and the controller merges blocks late:
        ``PerfStore.from_shards(shards)`` or streamed
        ``build_ppg(psg, P, shards)`` — no single-controller gather of
        per-(proc, vertex) vectors.
        """
        from repro.core.shard import PerfShard
        shard = PerfShard(proc_start, n_procs, len(self.psg.vertices))
        procs = np.arange(int(n_procs))
        for vid, vec in self.perf_vectors().items():
            shard.set_entries(procs, vid, vec.time, time_var=vec.time_var,
                              samples=vec.samples, counters=vec.counters)
        return shard

    def base_times(self, default: float = 0.0) -> Callable:
        """Vectorized ``base_times`` seeded from the measured profile.

        Returns a callable with the replay engine's vectorized contract
        (``fn(procs_array, vid) -> seconds``; see
        :func:`repro.core.inject.seeded_base_times`), so case studies
        replay real measured models without O(P·V) Python callbacks.
        Unprofiled vertices replay at ``default`` seconds.
        """
        from repro.core.inject import seeded_base_times
        table = np.full(len(self.psg.vertices), float(default))
        for vid, vec in self.perf_vectors().items():
            table[vid] = vec.time
        return seeded_base_times(table)

    def storage_bytes(self) -> int:
        """Bytes ScalAna retains: contracted PSG + per-vertex vectors."""
        vec_bytes = sum(8 * (3 + len(v.counters))
                        for v in self.perf_vectors().values())
        return self.psg.nbytes() + vec_bytes

    def full_trace_bytes(self) -> int:
        """What a full per-event tracer would have written for the same run:
        one 64-byte event per (eqn execution, step) incl. loop iterations."""
        events_per_step = 0
        for v in self.psg_full.vertices:
            trips = 1
            p = v.parent
            while p >= 0:
                trips *= int(self.psg_full.vertices[p].meta.get(
                    "trip_count", 1) or 1)
                p = self.psg_full.vertices[p].parent
            events_per_step += trips
        return events_per_step * 64 * self.total_steps

    def overhead_estimate(self) -> Dict[str, float]:
        if not self.step_times:
            return {}
        fast = [t for i, t in enumerate(self.step_times, start=1)
                if i % self.sample_every != 0]
        slow = [t for i, t in enumerate(self.step_times, start=1)
                if i % self.sample_every == 0]
        if not fast:
            return {}
        base = float(np.median(fast))
        extra = sum(max(t - base, 0.0) for t in slow)
        total = sum(self.step_times)
        return {
            "base_step_s": base,
            "sampled_step_s": float(np.median(slow)) if slow else 0.0,
            "overhead_frac": extra / max(total - extra, 1e-12),
        }
