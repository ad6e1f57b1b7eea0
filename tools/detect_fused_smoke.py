"""Interpret-mode smoke for the fused detection kernels
(`make detect-fused-smoke`, wired into `make check`).

Runs the Pallas kernels in interpret mode (the CPU CI path — the same
kernel code that compiles on TPU) on a small randomized case and checks
them against the pure-numpy oracle (`repro.kernels.detect_fused.ref`):

* `fused_non_scalable` — merged stack / slope / share to 1e-12, flag
  set exact;
* `fused_non_scalable_live` — live blocks + historical columns, same
  bars;
* `fused_abnormal` — winner order, scores, count and typical EXACT,
  full-fleet and degraded (padded live-mask) variants.

Exits 0 with a "skipped" note when jax is absent (the no-jax CI job
runs `make check` too); any parity violation exits 1 with the failing
op named.
"""
from __future__ import annotations

import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def main() -> int:
    try:
        import jax  # noqa: F401
    except ImportError:
        print("detect-fused smoke: jax not installed — skipped")
        return 0
    import jax.numpy as jnp

    from repro.core.detect_jax import precision
    from repro.kernels.detect_fused import ops, ref

    rng = np.random.default_rng(0)
    S, P, V, k = 3, 37, 11, 9
    t = rng.uniform(0, 2, (S, P, V))
    t[t < 0.3] = 0.0
    var = rng.uniform(0, 0.1, (S, P, V))
    present = rng.random((S, V)) > 0.1
    scales = [9, 18, 37]
    top = np.array([2, 7, 3], np.int32)
    kw = dict(ideal_slope=0.0, slope_margin=0.05, min_share=0.01)
    failures = 0

    def check(name, ok):
        nonlocal failures
        print(f"{'ok  ' if ok else 'FAIL'} {name} (interpret)")
        failures += not ok

    _, x64 = precision(np.float64)
    with x64:
        logp = jnp.asarray(np.log(np.asarray(scales, np.float64)))
        tj, vj = jnp.asarray(t), jnp.asarray(var)
        pj, topj = jnp.asarray(present), jnp.asarray(top)

        Mr, slr, _, flr = ref.non_scalable_ref(scales, t, var, present,
                                               top=top, **kw)
        M, sl, _, fl = ops.fused_non_scalable(tj, vj, logp, pj,
                                              top_idx=topj,
                                              interpret=True, **kw)
        check("fused_non_scalable",
              np.abs(np.asarray(M) - Mr).max() < 1e-12
              and np.abs(np.asarray(sl) - slr).max() < 1e-12
              and np.array_equal(np.asarray(fl), flr))

        cuts = [12, 24]
        hist = jnp.asarray(ref.merge_all_ref(t[:-1], var[:-1]))
        M, sl, _, fl = ops.fused_non_scalable_live(
            [jnp.asarray(b) for b in np.split(t[-1], cuts, axis=0)],
            [jnp.asarray(b) for b in np.split(var[-1], cuts, axis=0)],
            hist, logp, pj, topj, interpret=True, **kw)
        check("fused_non_scalable_live",
              np.abs(np.asarray(M) - Mr).max() < 1e-12
              and np.array_equal(np.asarray(fl), flr))

        orr, svr, cr, tyr = ref.abnormal_ref(t[-1], top, 1.5, 0.001, k)
        o, sv, c, ty = ops.fused_abnormal(
            [jnp.asarray(b) for b in np.split(t[-1], cuts, axis=0)],
            topj, 1.5, 0.001, k, interpret=True)
        check("fused_abnormal",
              np.array_equal(np.asarray(o), orr) and int(c) == cr
              and np.array_equal(np.asarray(sv), svr)
              and np.array_equal(np.asarray(ty), tyr))

        live = np.sort(rng.choice(P, size=P - 9, replace=False))
        lpad = np.zeros(P, np.int32)
        lpad[:live.size] = live
        vmask = np.zeros(P, bool)
        vmask[:live.size] = True
        orr, _, cr, tyr = ref.abnormal_ref(t[-1][lpad], top, 1.5, 0.001,
                                           k, valid=vmask)
        o, _, c, ty = ops.fused_abnormal(
            [jnp.asarray(t[-1])], topj, 1.5, 0.001, k,
            live=jnp.asarray(lpad), valid=jnp.asarray(vmask),
            interpret=True)
        check("fused_abnormal (degraded fleet)",
              np.array_equal(np.asarray(o), orr) and int(c) == cr
              and np.array_equal(np.asarray(ty), tyr))

    if failures:
        print(f"{failures} fused op(s) diverged from the oracle")
        return 1
    print("detect-fused smoke: all interpret-mode ops match the oracle")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
