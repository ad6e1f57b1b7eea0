"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run loads the cell's files (see
``harness.py``), sets up (weights and inputs from ``--seed``, every shape
warmed, the persistent compilation cache under ``<checkout>/.jax_cache``),
measures for ``--seconds`` seconds, checks what the timed path produced
against the plain reference, and prints as the last line of standard
output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``compared`` (each number compared beside its limit; the same numbers
end standard error).  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, args.workload, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        t0=T0, root=ROOT)
    try:
        cell.devices = harness.require_chips(cell.entry["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}; there is no CPU fallback", file=sys.stderr)
        return 2
    cell.peaks = harness.peaks_for(cell.devices[0].device_kind)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # the sampled step's per-equation programs compile in well under the
    # default one-second threshold; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    line, compared = harness.run_cell(cell)
    for name, value, limit in compared:
        verdict = "ok" if harness.passes(value, limit) else "FAIL"
        print(f"compared {name} {value!r} limit {limit!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
