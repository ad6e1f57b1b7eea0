"""Multi-run regression store: persist, align, diff, cluster, report.

The fleet question behind this package is "why did today's run get
slower than yesterday's, and which of my processes behave differently" —
one-shot detection (detect/backtrack over a single PPG) answers neither.
The pieces:

* :class:`~repro.runs.store.RunStore` — persists (PSG, perf store,
  comm index, detect output, scaling curves, metadata) per run through
  the ``to_tree``/``from_tree`` seam and ``repro.checkpoint.store`` —
  the SAME persistence path the monitor's crash snapshots use.
* :func:`~repro.runs.align.align_psgs` — matches vertices across runs
  whose graphs drifted, by stable (kind, name, path-from-root)
  signatures with explicit added/removed sets — never positionally.
* :func:`~repro.runs.diff.diff_runs` — per-vertex scaling-curve deltas
  and regression flags, reusing the detect slope machinery (its
  ``backend=`` picks numpy or jax exactly as detection's does).
* :func:`~repro.runs.cluster.cluster_procs` — groups processes by
  behavior vector (per-vertex time + counter signature) so an 8k–64k
  proc run stores and diffs as K representatives + a membership map.
* :func:`~repro.runs.report.render_regression_report` — names the top
  regressed vertices and the regressed cluster, and backtracks the
  regressed representative through the existing ``backtrack`` path.

Everything here is jax-free at import; the jax detect backend engages
only through ``diff_runs(backend=...)``.
"""
from repro.runs.align import Alignment, align_psgs, vertex_signatures
from repro.runs.cluster import (Clustering, behavior_matrix, cluster_procs,
                                representative_ppg)
from repro.runs.diff import RunDiff, VertexDelta, diff_runs, scaling_curves
from repro.runs.report import regressed_cluster, render_regression_report
from repro.runs.store import (RUN_SCHEMA_VERSION, RunRecord, RunStore,
                              run_metadata)

__all__ = [
    "Alignment", "align_psgs", "vertex_signatures",
    "Clustering", "behavior_matrix", "cluster_procs", "representative_ppg",
    "RunDiff", "VertexDelta", "diff_runs", "scaling_curves",
    "regressed_cluster", "render_regression_report",
    "RUN_SCHEMA_VERSION", "RunRecord", "RunStore", "run_metadata",
]
