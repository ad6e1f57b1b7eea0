"""ScalAna end-user workflow: measured multi-scale profiling -> root cause.

This is the paper's four-step usage (§V) mapped to JAX:

  1. *ScalAna-static*  — PSG from the train-step jaxpr (compile time).
  2. *ScalAna-prof*    — run the instrumented step at several job scales
     (worker subprocesses with different ``--xla_force_host_platform_
     device_count``; each runs the REAL sharded train step and records
     per-PSG-vertex times via GraphProfiler).

This is a CPU emulation of job scales: the forced device count exists
only on the host platform.  On an accelerator host every worker would
see the real chips, so a worker whose device count differs from the
scale it was asked for exits non-zero instead of writing a profile
under the wrong scale.
  3. *ScalAna-detect*  — fit per-vertex log-log scaling curves across the
     measured series, flag non-scalable + abnormal vertices, run
     backtracking root-cause detection.
  4. *Report*          — source-line report (the ScalAna-viewer analogue).

Example:
    python -m repro.launch.scaling_profile --arch tinyllama-1.1b \
        --scales 1,2,4,8 --steps 12
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict

ARTIFACT_DIR = "artifacts/scaling"


# ---------------------------------------------------------------------------
# worker: one scale, one process
# ---------------------------------------------------------------------------

def worker(args) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.core.profiler import GraphProfiler
    from repro.distributed.axes import use_rules
    from repro.launch.mesh import make_host_mesh
    from repro.models.api import build_model
    from repro.optim.schedule import constant
    from repro.training.trainer import make_train_step, TrainState
    from repro.optim.adamw import adamw_init

    n = jax.device_count()
    if n != args.scale:
        raise SystemExit(f"[worker] asked for scale {args.scale} but jax "
                         f"sees {n} {jax.default_backend()} devices; job "
                         f"scales are emulated on the CPU only "
                         f"(JAX_PLATFORMS=cpu)")
    cfg = get_smoke(args.arch).replace(remat=False)
    run = RunConfig(arch=args.arch)
    model = build_model(cfg)
    mesh = make_host_mesh()              # (n, 1) data-parallel
    shape = ShapeConfig("scale", args.seq, args.batch, "train")
    step_fn = make_train_step(model, run, constant(1e-3))

    with use_rules(mesh):
        params = model.init(jax.random.PRNGKey(0))
        state = TrainState(params=params, opt=adamw_init(params),
                           residual=None, step=jnp.zeros((), jnp.int32))
        batch = {"tokens": jnp.zeros((args.batch, args.seq + 1), jnp.int32)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((args.batch, cfg.frontend_len,
                                         cfg.d_model), cfg.cdtype())
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros((args.batch, cfg.frontend_len,
                                          cfg.d_model), cfg.cdtype())
        prof = GraphProfiler(step_fn, (state, batch),
                             sample_every=args.sample_every)
        for i in range(args.steps):
            state, _ = prof.step(state, batch)

    perf = prof.perf_vectors()
    out = {
        "n_procs": n,
        "psg": prof.psg.to_json(),
        "perf": {str(vid): {"time": v.time, "samples": v.samples,
                            "counters": v.counters}
                 for vid, v in perf.items()},
        "storage_bytes": prof.storage_bytes(),
        "overhead": prof.overhead_estimate(),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(f"[worker n={n}] wrote {args.out}", flush=True)


# ---------------------------------------------------------------------------
# driver: spawn scales, detect, report
# ---------------------------------------------------------------------------

def load_series(arch: str, scales, out_dir: str):
    from repro.core import PSG, PerfVector, build_ppg
    series = {}
    psg = None
    for n in scales:
        path = os.path.join(out_dir, arch, f"scale_{n}.json")
        with open(path) as f:
            raw = json.load(f)
        psg = PSG.from_json(raw["psg"])
        perf = {int(vid): PerfVector(time=d["time"], samples=d["samples"],
                                     counters=d["counters"])
                for vid, d in raw["perf"].items()}
        series[raw["n_procs"]] = build_ppg(psg, raw["n_procs"], perf)
    return psg, series


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--scales", default="1,2,4,8")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--sample-every", type=int, default=4)
    ap.add_argument("--scale", type=int, default=1,
                    help="worker: the device count it must see")
    ap.add_argument("--out", default="")
    ap.add_argument("--out-dir", default=ARTIFACT_DIR)
    args = ap.parse_args()

    if args.worker:
        worker(args)
        return

    scales = [int(s) for s in args.scales.split(",")]
    for n in scales:
        out = os.path.join(args.out_dir, args.arch, f"scale_{n}.json")
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        cmd = [sys.executable, "-m", "repro.launch.scaling_profile",
               "--worker", "--scale", str(n), "--arch", args.arch,
               "--steps", str(args.steps),
               "--batch", str(args.batch), "--seq", str(args.seq),
               "--sample-every", str(args.sample_every), "--out", out]
        print(f"[scaling_profile] scale {n}...", flush=True)
        subprocess.run(cmd, check=True, env=env)

    from repro.core import (backtrack, detect_abnormal, detect_non_scalable,
                            render_report)
    psg, series = load_series(args.arch, scales, args.out_dir)
    ns = detect_non_scalable(series, min_share=0.01)
    top = series[max(series)]
    ab = detect_abnormal(top)
    paths = backtrack(top, ns, ab)
    print(render_report(top, ns, ab, paths))


if __name__ == "__main__":
    main()
