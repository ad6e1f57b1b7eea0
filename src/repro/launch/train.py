"""Training launcher.

Smoke-scale on CPU (reduced config, real training) or full-scale on a pod
(the same code path the dry-run compiles).  ScalAna profiling is on by
default: every run produces a PSG + per-vertex perf vectors, and
``--report`` renders the scaling-loss report at exit.

Examples:
    python -m repro.launch.train --arch tinyllama-1.1b --smoke --steps 20
    python -m repro.launch.train --arch mamba2-130m --smoke --steps 50 \
        --ckpt-dir /tmp/ckpt --report
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import SHAPES, get as get_config, get_smoke
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch.compile_cache import use_compile_cache
from repro.training import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config + small shape (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--no-scalana", action="store_true")
    ap.add_argument("--sample-every", type=int, default=8)
    ap.add_argument("--inject-delay", type=float, default=0.0,
                    help="injected per-step delay on this process (case study)")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args()
    use_compile_cache()

    run = RunConfig(
        arch=args.arch, shape=args.shape, total_steps=args.steps,
        learning_rate=args.lr, microbatch=args.microbatch,
        warmup_steps=max(args.steps // 10, 1),
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every or max(args.steps // 2, 1),
        scalana=not args.no_scalana,
        scalana_sample_every=args.sample_every,
        grad_compress=args.grad_compress,
    )
    if args.smoke:
        cfg = get_smoke(args.arch)
        shape = ShapeConfig("smoke", args.seq, args.batch, "train")
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]

    inject = {0: args.inject_delay} if args.inject_delay else None
    tr = Trainer(run, arch_cfg=cfg, shape=shape, inject_delay=inject)
    t0 = time.time()
    tr.train(num_steps=args.steps, step_timeout_s=run.step_timeout_s)
    wall = time.time() - t0

    losses = [m["loss"] for m in tr.metrics_log if "loss" in m]
    print(f"[train] {args.arch} ({'smoke' if args.smoke else 'full'}): "
          f"{args.steps} steps in {wall:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")

    if run.scalana and tr.profiler is not None:
        psg, perf, storage = tr.scalana_artifacts()
        ov = tr.profiler.overhead_estimate()
        print(f"[scalana] PSG: {psg.stats()}; storage {storage/1024:.1f} KiB; "
              f"overhead {100*ov.get('overhead_frac', 0):.2f}%")
        if args.report:
            from repro.core import build_ppg, detect_abnormal, backtrack, \
                render_report, detect_non_scalable
            ppg = build_ppg(psg, jax.process_count() or 1, perf)
            ab = detect_abnormal(ppg, abnorm_thd=run.abnorm_thd)
            paths = backtrack(ppg, [], ab)
            print(render_report(ppg, [], ab, paths))

    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(tr.metrics_log, f)


if __name__ == "__main__":
    main()
