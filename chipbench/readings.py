"""Readings of the comparison's control and faults, for setting limits.

    python3 chipbench/readings.py --workload <name> --seeds 11,12,13

Not part of a benchmark run.  For each seed, in one process, it sets the
cell up as a run does (a diagnosis cell also runs a short window) and
prints one JSON line of the numbers that ``verify`` compares, read for
what stands in the program's place:

- ``control``: the plain reference computed in the precision below the
  configuration's, against the reference: bfloat16 below a diagnosis
  cell's float32; for training, whose matrix products take
  bfloat16-rounded operands on a TPU at JAX's default precision, the
  reference with float8 (e4m3) operands (``bf16_matmuls`` reads the
  reference with bfloat16 operands, for comparison);
- ``half_batch`` (training): the reference over half of each batch,
  the mean taken over the rest, against the reference.

A training step that hands back the state it was given reads 1 as
``update_gap`` by construction and needs no run.

The program's own readings (the lower ones) are the ``compared``
numbers of ordinary runs.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="window of a diagnosis cell")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    import jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache
    import jax

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(bench, args.workload, seed=seed,
                            seconds=args.seconds, trace=False, t0=T0,
                            root=ROOT)
        cell.devices = harness.require_chips(cell.entry["chips"])
        cell.peaks = harness.peaks_for(cell.devices[0].device_kind)
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        drv = harness.load_module(os.path.join(
            ROOT, "chipbench", "drivers", cell.traffic["driver"] + ".py"),
            "driver_" + cell.traffic["driver"]).Driver(cell)
        drv.setup()
        out = {"workload": args.workload, "seed": seed}
        if cell.traffic["driver"] == "profile":
            drv.release()
            ref = drv.reference_run()
            out["program"] = drv.readings(ref)
            out["control"] = drv.readings(ref, drv.reference_run(
                matmul_dtype=jnp.float8_e4m3fn))
            out["bf16_matmuls"] = drv.readings(ref, drv.reference_run(
                matmul_dtype=jnp.bfloat16))
            out["half_batch"] = drv.readings(ref, drv.reference_run(
                rows=drv.batch // 2))
        else:
            with cell.window():
                drv.window()
            drv.release()
            picks = drv.sample()
            ref = drv.reference_answers(picks)
            out["program"] = drv.readings(ref)
            out["control"] = drv.readings(
                ref, drv.reference_answers(picks, dtype=jnp.bfloat16))
            out["cycles_compared"] = len(picks)
        print(json.dumps(out), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
