import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init).  512 placeholder host devices exist ONLY in
# this process so jax.make_mesh can build the production meshes; smoke
# tests and benchmarks see the real single CPU device.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell and both production meshes
(16x16 single-pod, 2x16x16 multi-pod) this driver:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves it fits
    print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

and records per-cell artifacts (memory stats, cost analysis, per-kind
collective payload bytes parsed from the compiled HLO) into JSON files
that EXPERIMENTS.md §Dry-run / §Roofline and benchmarks/bench_roofline.py
read.  A failure here (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the framework.

Usage:
    python -m repro.launch.dryrun --arch all --shape all --mesh both
    python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k \
        --mesh single --hlo-out artifacts/hlo
"""
import argparse
import json
import time
import traceback
from typing import Dict, List, Optional

import jax

from repro.configs import ARCHS, SHAPES, get as get_config, shape_applicable
from repro.core.hlo_walk import analyze_hlo
from repro.launch.mesh import (HBM_BANDWIDTH, ICI_BANDWIDTH, PEAK_FLOPS_BF16,
                               make_production_mesh, mesh_chip_count)
from repro.launch.shardings import build_cell

ARTIFACT_DIR = "artifacts/dryrun"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = ARTIFACT_DIR,
             hlo_out: Optional[str] = None,
             skip_existing: bool = True,
             verbose: bool = True,
             options: Optional[Dict[str, bool]] = None) -> Dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    opts = {k: v for k, v in (options or {}).items() if v}
    suffix = ("__opt-" + "-".join(sorted(opts))) if opts else ""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, SHAPES[shape_name])
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": why}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape_name, mesh, options=opts)
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    hw = analyze_hlo(hlo_text)          # trip-count-exact per-device costs

    # three-term roofline (seconds, per step, per device)
    t_compute = hw.dot_flops / PEAK_FLOPS_BF16
    t_memory = hw.mem_bytes / HBM_BANDWIDTH
    t_collective = hw.total_coll_bytes / ICI_BANDWIDTH
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    bottleneck = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "status": "ok",
        "options": sorted(opts),
        "chips": mesh_chip_count(mesh),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
        },
        "cost": {
            # raw XLA aggregate (counts while bodies once; kept for
            # reference) vs. trip-count-exact hlo_walk numbers
            "xla_flops_raw": float(ca.get("flops", 0.0)),
            "xla_bytes_raw": float(ca.get("bytes accessed", 0.0)),
            "dot_flops_per_device": hw.dot_flops,
            "mem_bytes_per_device": hw.mem_bytes,
            "collective_bytes_per_device": hw.total_coll_bytes,
        },
        "collectives": {
            "bytes_by_kind": hw.coll_bytes,
            "counts_by_kind": hw.coll_counts,
        },
        "roofline": {**terms, "bottleneck": bottleneck},
    }
    if hlo_out:
        os.makedirs(hlo_out, exist_ok=True)
        hp = os.path.join(hlo_out,
                          f"{arch}__{shape_name}__{mesh_name}{suffix}"
                          ".hlo.txt")
        with open(hp, "w") as f:
            f.write(hlo_text)
        rec["hlo_path"] = hp
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        dev_bytes = (rec["memory"]["argument_bytes"]
                     + rec["memory"]["temp_bytes"])
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
              f"({rec['kind']}; {dev_bytes/2**30:.2f} GiB/dev args+temp, "
              f"{hw.dot_flops/1e9:.1f} GFLOP/dev, "
              f"bottleneck={bottleneck}, compile {t_compile:.1f}s)",
              flush=True)
        print(f"  memory_analysis: {ma}", flush=True)
        print(f"  cost_analysis: flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument("--no-skip", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma-separated optimization options "
                         "(gather_weights,seq_shard) — see §Perf")
    args = ap.parse_args()
    options = {name: True for name in args.opt.split(",") if name}

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures: List[str] = []
    n_ok = n_skip = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    rec = run_cell(arch, shape, multi_pod=multi,
                                   out_dir=args.out, hlo_out=args.hlo_out,
                                   skip_existing=not args.no_skip,
                                   options=options)
                    if rec["status"] == "ok":
                        n_ok += 1
                    else:
                        n_skip += 1
                        print(f"[dryrun] {arch} x {shape}: skipped "
                              f"({rec['reason']})", flush=True)
                except Exception:
                    failures.append(f"{arch} x {shape} x multi={multi}")
                    traceback.print_exc()
    print(f"\n[dryrun] {n_ok} ok, {n_skip} skipped, "
          f"{len(failures)} FAILED", flush=True)
    if failures:
        for f in failures:
            print("  FAIL:", f)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
