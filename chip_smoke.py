"""Chip smoke: ScalAna's main path on a TPU, end to end.

    python chip_smoke.py              # one chip: profile -> detect -> backtrack
    python chip_smoke.py --chips 4    # four chips: the sharded train step only

One chip (the default), in one process:

1. train — mamba2-130m at its published widths, ``train_4k``'s sequence
   length (4096) and a per-chip batch of 8 (``train_4k``'s global batch
   of 256 over a 32-chip data-parallel job; halved while the compiled
   step does not fit the chip), through ``repro.training.Trainer`` with
   ScalAna profiling on: 6 steps, every 3rd one run through the eager
   per-equation profiler.  Weights and tokens come from a seed.
2. diagnose — a 2,048-process fleet PPG on 8 hosts, replayed from the
   trainer's measured profile (``GraphProfiler.base_times``) with a
   straggler injected at a known (process, vertex), plus a
   512/1,024/2,048 series in which one vertex does not scale.
   Detection runs through the default routing — on a TPU the sharded
   store feeds ``DeviceShardView`` and the compiled Pallas kernels in
   float32 — and again with ``backend="numpy"`` on the same PPGs.
   Backtracking must recover the injected root cause; the report is
   printed.

Four chips (``--chips 4``): the same train step on a 4-chip data-parallel
mesh, profiled; its sharded cell compiled through
``launch.shardings.build_cell``, whose HLO must hold an all-reduce; and
its step-1 loss compared with the same global batch on one chip.

Any failed check exits non-zero.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and names the platform it
found: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "mamba2-130m"
SEQ = 4096                    # train_4k (configs/base.py)
BATCHES = (8, 4, 2)           # per chip: train_4k's 256 over 32 chips first
STEPS, SAMPLE_EVERY = 6, 3
N_PROCS, HOSTS, SCALES = 2048, 8, (512, 1024, 2048)
STRAGGLER = 1234              # a process on host 5 of 8
MESH_BATCH = 8                # --chips 4: global batch, 2 per chip
# Detection in float32 on the chip against the float64 numpy reference:
# the f32 parity bar of the repository's tests (1e-4 relative), with an
# absolute part for values near zero — a vertex that does not scale has
# slope 0 in float64 and a few ulps in float32.
F32_RTOL, F32_ATOL = 1e-4, 1e-4
# Step-1 loss on the 4-chip mesh against one chip: the same float32 math,
# but the loss and gradient sums reassociate across four shards and
# different fusions.
LOSS_RTOL = 1e-3
GIB = 2.0 ** 30


def log(msg: str) -> None:
    print(msg, flush=True)


def fitting_batch(cfg, seq: int, batches=BATCHES):
    """The largest per-chip batch in ``batches`` whose compiled train step
    fits the chip: (batch, bytes needed, bytes available)."""
    import jax

    from repro.configs.base import RunConfig, ShapeConfig
    from repro.training import Trainer

    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    need = 0
    for batch in batches:
        tr = Trainer(RunConfig(arch=cfg.name), arch_cfg=cfg,
                     shape=ShapeConfig("train_4k/chip", seq, batch, "train"))
        state = jax.eval_shape(tr.init_state)
        tokens = jax.ShapeDtypeStruct((batch, seq + 1), "int32")
        mem = jax.jit(tr.train_step_fn).lower(
            state, {"tokens": tokens}).compile().memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        if need <= limit:
            return batch, need, limit
        log(f"[train] per-chip batch {batch} needs {need / GIB:.2f} GiB "
            f"of {limit / GIB:.2f} GiB: halving")
    raise SystemExit(f"no batch in {batches} fits: {need} > {limit} bytes")


def train_phase(cfg, seq: int, batch: int, steps: int = STEPS,
                sample_every: int = SAMPLE_EVERY, seed: int = 0):
    """Train ``steps`` steps with ScalAna profiling on; returns
    (trainer, checks, facts)."""
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.training import Trainer

    run = RunConfig(arch=cfg.name, total_steps=steps, warmup_steps=1,
                    seed=seed, scalana_sample_every=sample_every)
    tr = Trainer(run, arch_cfg=cfg,
                 shape=ShapeConfig("train_4k/chip", seq, batch, "train"))
    t0 = time.perf_counter()
    tr.train(num_steps=steps)
    wall = time.perf_counter() - t0
    losses = [m["loss"] for m in tr.metrics_log if "loss" in m]
    times = tr.step_wall_times
    sampled = [i for i in range(steps) if (i + 1) % sample_every == 0]
    compiled = [t for i, t in enumerate(times)
                if i and i not in sampled]
    facts = {
        "params": tr.model.param_count(),
        "layers": cfg.n_layers, "d_model": cfg.d_model,
        "seq": seq, "batch": batch, "steps": steps,
        "loss_first": losses[0], "loss_last": losses[-1],
        "wall_s": wall, "first_step_s": times[0],
        "compiled_step_s": statistics.median(compiled) if compiled else 0.0,
        "sampled_step_s": [times[i] for i in sampled],
        "psg": tr.profiler.psg.stats(),
    }
    checks = [("loss finite on every step",
               len(losses) == steps and all(map(math.isfinite, losses))),
              ("a sampled step ran", tr.profiler.sampled_steps >= 1)]
    return tr, checks, facts


def _close(a: float, b: float) -> bool:
    return math.isclose(a, b, rel_tol=F32_RTOL, abs_tol=F32_ATOL)


def diagnose_phase(prof, grad_bytes: float, n_procs: int = N_PROCS,
                   hosts: int = HOSTS, scales=SCALES,
                   straggler: int = STRAGGLER):
    """Fleet PPG from the measured profile -> device detection, checked
    against numpy on the same PPGs -> backtracking.  Adds the gradient
    all-reduce (``grad_bytes``) to ``prof.psg``.  Returns (checks, facts,
    report)."""
    from repro.core import (COMM, LOOP, backtrack, detect_abnormal,
                            detect_non_scalable, render_report, root_causes,
                            simulate)
    from repro.core.inject import schedule, vectorized_base_times
    from repro.kernels.detect_fused import ops

    psg = prof.psg
    tops = list(psg.children(psg.root))
    # the data-parallel gradient all-reduce every process runs: a
    # one-chip program has none to take from its HLO
    ar = psg.new_vertex(COMM, "psum(grads)", parent=psg.root,
                        source="src/repro/optim/adamw.py:60")
    ar.comm_kind, ar.comm_bytes = "all_reduce", grad_bytes
    psg.add_edge(tops[-1], ar.vid, "data")
    psg.add_edge(psg.root, ar.vid, "control")

    base = prof.base_times()
    measured = {v: float(base(None, v)) for v in tops}
    target = next(v for v in schedule(psg) if psg.vertices[v].kind == LOOP)
    serial = max((v for v in tops if v != target), key=measured.get)
    delay = 0.5 * sum(measured.values())

    def at_scale(n):
        # strong scaling from the measured n_procs-process profile; the
        # ``serial`` vertex keeps its time at every scale
        @vectorized_base_times
        def fn(procs, vid):
            t = base(procs, vid)
            return t if vid == serial else t * (n_procs / n)
        return fn

    t0 = time.perf_counter()
    series = {n: simulate(psg, n, at_scale(n), shards=hosts,
                          inject=({(straggler, target): delay}
                                  if n == n_procs else None)).ppg
              for n in scales}
    ppg = series[n_procs]
    replay_s = time.perf_counter() - t0

    runs = []
    for _ in range(2):                         # cold (compile), then warm
        ops.reset_launch_counts()
        t0 = time.perf_counter()
        ns = detect_non_scalable(series)
        ab = detect_abnormal(ppg)
        runs.append((time.perf_counter() - t0, dict(ops.launch_counts)))
    view = ppg.device_view()
    ns_np = detect_non_scalable(series, backend="numpy")
    ab_np = detect_abnormal(ppg, backend="numpy")

    t0 = time.perf_counter()
    paths = backtrack(ppg, ns, ab)
    rcs = root_causes(paths, psg, ppg=ppg)
    backtrack_s = time.perf_counter() - t0
    report = render_report(ppg, ns, ab, paths)

    np_ns = {d.vid: d for d in ns_np}
    slopes_ok = (set(np_ns) == {d.vid for d in ns} and all(
        _close(d.slope, np_ns[d.vid].slope)
        and _close(d.share, np_ns[d.vid].share)
        and all(_close(t, np_ns[d.vid].times[s]) for s, t in d.times.items())
        for d in ns))
    checks = [
        ("abnormal top-k (vid, proc) == numpy",
         [(a.vid, a.proc) for a in ab] == [(a.vid, a.proc) for a in ab_np]),
        ("abnormal typical ~= numpy", all(
            _close(a.typical, b.typical) for a, b in zip(ab, ab_np))),
        ("non-scalable flagged set == numpy",
         {d.vid for d in ns} == set(np_ns)),
        ("non-scalable slopes, shares, times ~= numpy", slopes_ok),
        ("the non-scaling vertex is flagged", serial in np_ns),
        ("injected root cause recovered",
         any(node == (straggler, target) for node, _, _ in rcs)),
    ]
    facts = {
        "fleet": f"{n_procs} procs on {hosts} hosts, series {list(scales)}",
        "psg_vertices": len(psg.vertices),
        "straggler": (straggler, target, psg.vertices[target].source),
        "delay_s": delay, "non_scaling_vertex": serial,
        "kernel_mode": ops.kernel_mode(),
        "detect_dtype": str(view.time_blocks()[0].dtype),
        "replay_s": replay_s,
        "detect_cold_s": runs[0][0], "detect_warm_s": runs[1][0],
        "launches_cold": runs[0][1], "launches_warm": runs[1][1],
        "upload_bytes": view.total_upload_bytes,
        "backtrack_s": backtrack_s,
        "flagged": {"abnormal": len(ab), "non_scalable": len(ns)},
        "root_causes": [(node, src) for node, _, src in rcs],
    }
    return checks, facts, report


def mesh_phase(cfg, seq: int = SEQ, global_batch: int = MESH_BATCH,
               steps: int = 2, sample_every: int = 2):
    """The train step on a data-parallel mesh over every device, profiled,
    plus its compiled collectives and a one-chip loss comparison.
    Returns (checks, facts)."""
    import jax

    from repro.configs.base import RunConfig, ShapeConfig
    from repro.core import parse_collectives
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shardings import build_cell
    from repro.training import Trainer

    mesh = make_host_mesh()
    shape = ShapeConfig("train_4k/host", seq, global_batch, "train")
    run = RunConfig(arch=cfg.name, total_steps=steps, warmup_steps=1,
                    scalana_sample_every=sample_every)
    # the one-chip reference first, so its state is gone before the mesh
    # trainer's shards share device 0 with it
    one = Trainer(run.replace(scalana=False), arch_cfg=cfg, shape=shape)
    one.train(num_steps=1)
    loss_one = one.metrics_log[0]["loss"]

    t0 = time.perf_counter()
    tr = Trainer(run, mesh=mesh, arch_cfg=cfg, shape=shape)
    state = tr.train(num_steps=steps)
    mesh_s = time.perf_counter() - t0
    spans = {len(x.sharding.device_set) for x in jax.tree.leaves(state)}
    losses = [m["loss"] for m in tr.metrics_log if "loss" in m]

    t0 = time.perf_counter()
    hlo = build_cell(cfg.name, "train_4k", mesh, cfg=cfg, shape=shape,
                     donate=False).lower().compile().as_text()
    kinds = collections.Counter(op.kind for op in parse_collectives(hlo))
    cell_s = time.perf_counter() - t0

    checks = [
        ("loss finite on every mesh step",
         len(losses) == steps and all(map(math.isfinite, losses))),
        ("a sampled step ran on the mesh", tr.profiler.sampled_steps >= 1),
        (f"the state spans all {mesh.size} devices", spans == {mesh.size}),
        ("compiled cell holds an all-reduce", kinds["all-reduce"] >= 1),
        (f"step-1 loss within {LOSS_RTOL:g} of one chip",
         math.isclose(losses[0], loss_one, rel_tol=LOSS_RTOL)),
    ]
    facts = {
        "mesh": dict(mesh.shape), "global_batch": global_batch, "seq": seq,
        "loss_mesh": losses, "loss_one_chip": loss_one,
        "mesh_train_s": mesh_s, "mesh_step_s": tr.step_wall_times,
        "psg": tr.profiler.psg.stats(),
        "collectives": dict(kinds), "cell_compile_s": cell_s,
    }
    return checks, facts


def _cache_events() -> collections.Counter:
    """Counts of JAX's persistent compile-cache events (hits, misses)."""
    from jax import monitoring
    counts: collections.Counter = collections.Counter()

    def on_event(event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            counts[event.rsplit("/", 1)[-1]] += 1

    monitoring.register_event_listener(on_event)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax found platform "
              f"{platform!r} ({len(devices)} device(s)); there is no CPU "
              f"fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    cache = _cache_events()
    cfg = get(ARCH)
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    checks = []
    if args.chips == 4:
        c, facts = mesh_phase(cfg)
        checks += c
        log(f"[mesh] {json.dumps(facts, default=str)}")
    else:
        batch, need, limit = fitting_batch(cfg, SEQ)
        log(f"[train] {ARCH}: per-chip batch {batch} x seq {SEQ}; compiled "
            f"step needs {need / GIB:.2f} GiB of {limit / GIB:.2f} GiB")
        tr, c, facts = train_phase(cfg, SEQ, batch)
        checks += c
        stats = devices[0].memory_stats() or {}
        facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        log(f"[train] {json.dumps(facts, default=str)}")
        c, facts, report = diagnose_phase(
            tr.profiler, 4.0 * facts["params"])
        checks += c
        log(f"[diagnose] {json.dumps(facts, default=str)}")
        log(report)
    log(f"compile cache: {cache.get('cache_hits', 0)} hits, "
        f"{cache.get('cache_misses', 0)} misses")

    for name, ok in checks:
        log(f"{'ok  ' if ok else 'FAIL'} {name}")
    if not all(ok for _, ok in checks):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
