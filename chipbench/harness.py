"""The chip benchmark's harness: one cell, one run, one result line.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``:

- the configuration: ``configs/<config>/config.json`` (the entry's
  ``file``), with its plain reference ``reference.py`` beside it where the
  cell trains the model;
- the traffic mix: ``traffic/<traffic>.json``, whose ``driver`` key names
  the general generator in ``drivers/`` that reads it;
- the limits of the comparison that decides ``correct``:
  ``limits/<workload>.json``;
- each per-layer metric: ``metrics/<metric>.py``, a reader of the run's
  spans, counters and trace summary;
- the peaks of the chip: ``peaks.json``, keyed by ``device_kind``.

A driver exposes ``setup()``, ``window()``, ``end_to_end()``,
``release()`` and ``verify()``; the harness times set-up, wraps the
window in the trace, and assembles the result.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# host spans the benchmark puts around each call into a layer; the trace
# reduction labels idle gaps of the device by the one open
SPANS = ("window", "train_step", "apply", "detect", "backtrack", "render")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (names with '-' or '.' are not importable),
    once per process under ``name``."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The devices a cell runs on; raises :class:`NoChip` without them.
    There is no CPU fallback."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"needs a TPU, but jax found platform {platform!r} "
                     f"({len(devices)} device(s))")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, found {len(devices)}")
    return devices[:n]


def peaks_for(kind: str) -> Dict[str, Any]:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def seed_words(seed: int, *tags: int) -> List[int]:
    """Non-negative words for numpy's SeedSequence from any whole seed."""
    return [int(seed) % 2 ** 64, *tags]


class Cell:
    """One run of one cell: its files, seed, spans and raw readings."""

    def __init__(self, bench: Dict[str, Any], workload: str, *, seed: int,
                 seconds: float, trace: bool, t0: float, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        self.bench = bench
        self.entry = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in bench["configs"]}
        cfg_entry = configs[self.entry["config"]]
        self.config_file = os.path.join(root, cfg_entry["file"])
        self.config = load_json(self.config_file)
        self.traffic = load_json(os.path.join(
            root, "chipbench", "traffic", self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(
            root, "chipbench", "limits", workload + ".json"))
        self.root = root
        self.seed, self.seconds, self.trace = int(seed), seconds, trace
        self.t0 = t0
        self.devices: list = []
        self.peaks: Dict[str, Any] = {}
        self.spans: Dict[str, List[float]] = collections.defaultdict(list)
        self.raw: Dict[str, Any] = {}
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_dir = os.path.join(root, ".chipbench", "trace")
        self._in_window = False
        self.window_compiles: List[str] = []

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(os.path.join(os.path.dirname(self.config_file),
                                        "reference.py"),
                           f"reference_{self.entry['config']}")

    def log(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: timed on the host clock, and a TraceAnnotation in
        the profiler's trace when one is recorded."""
        import jax
        with jax.profiler.TraceAnnotation(name):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.spans[name].append(time.perf_counter() - t)

    def _on_compile_event(self, event: str, *_args, **_kw) -> None:
        if self._in_window and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.window_compiles.append(event.rsplit("/", 1)[-1])

    @contextlib.contextmanager
    def window(self):
        """Set-up ends where this opens.  Spans restart, compilations are
        counted (there should be none), and with ``--trace 1`` the
        profiler records exactly this window."""
        import jax
        from jax import monitoring
        self.setup_s = time.perf_counter() - self.t0
        monitoring.register_event_duration_secs_listener(
            self._on_compile_event)
        self.spans.clear()
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # no per-Python-call events
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
        self._in_window = True
        t = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        """Peak bytes on the fullest chip, read before the program's state
        is freed and before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


def per_layer_metrics(bench, cell_name: str, e2e_names) -> List[Dict]:
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_names:
            out.append(m)
    return out


def end_to_end_metrics(bench, cell_name: str) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def read_per_layer(cell: Cell, metrics: List[Dict]) -> Dict[str, Dict]:
    """Run each metric's reader over the run's raw readings; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(cell.root, "chipbench", "metrics",
                                          m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(cell.raw)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell) -> Tuple[Dict[str, Any], List[Tuple[str, float,
                                                              float]]]:
    """Set-up, window, end-to-end readings, release, verification.
    Returns (result line, compared numbers)."""
    from trace_reduce import reduce_trace

    driver_mod = load_module(os.path.join(
        cell.root, "chipbench", "drivers", cell.traffic["driver"] + ".py"),
        "driver_" + cell.traffic["driver"])
    drv = driver_mod.Driver(cell)
    drv.setup()
    with cell.window():
        drv.window()
    cell.read_memory_peak()
    e2e = drv.end_to_end()
    e2e["setup_s"] = cell.setup_s
    attempted, failed = drv.attempted, drv.failed
    drv.release()
    compared = drv.verify()

    cell.raw["e2e"] = dict(e2e)
    cell.raw["spans"] = {k: list(v) for k, v in cell.spans.items()}
    cell.raw["peaks"] = cell.peaks
    device = {"platform": cell.devices[0].platform,
              "kind": cell.devices[0].device_kind,
              "count": len(cell.devices),
              "memory_peak_bytes": cell.memory_peak_bytes}
    e2e_entries = end_to_end_metrics(cell.bench, cell.name)
    breakdown = None
    if cell.trace:
        summary = reduce_trace(cell.trace_dir, SPANS, n_devices=len(
            cell.devices))
        cell.raw["trace"] = summary
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"][:10],
                         "idle_gaps": summary["idle_gaps"][:10]}
        metrics = read_per_layer(cell, per_layer_metrics(
            cell.bench, cell.name, {m["name"] for m in e2e_entries}))
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in e2e_entries}
    correct = all(passes(v, lim) for _, v, lim in compared)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in compared}
    cell.log(f"setup_s {cell.setup_s:.3f}, window_s {cell.window_s:.3f}, "
             f"compilations in the window {cell.window_compiles}")
    return line, compared


def passes(value: float, limit: float) -> bool:
    return math.isfinite(value) and value <= limit

