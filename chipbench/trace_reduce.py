"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The trace holds one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops``
line lists every operation the device ran, and a host plane whose events
include the benchmark's ``jax.profiler.TraceAnnotation`` spans.  From
them:

- ``busy_s``: the union of the device's operation intervals inside the
  traced window, averaged over the chips used; ``window_s`` the window's
  length (the host span ``window``); idle share is ``1 - busy/window``;
- ``device_ops``: device seconds per operation name, as the trace prints
  it, longest first;
- ``idle_gaps``: the device's idle seconds inside the window, summed by
  the benchmark span open on the host during each part of each gap
  (``none`` where no span was open), longest first;
- ``device_s``: the summed durations of every operation in the window,
  averaged over the chips used, so a cell's device work is read whatever
  the kernels that do it are named.

Device timestamps are taken as the trace gives them: on a v5e they lie
within about a millisecond of the host clock (the host launch events of
a module against its device start), which is far below the spans
(tens of milliseconds to seconds) that label the gaps.

Returns None when the trace holds no device plane (a CPU run).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def _op_name(hlo: str) -> str:
    """'%fusion.3 = f32[8]{0} fusion(...)' -> '%fusion.3'."""
    return hlo.split(" = ", 1)[0].strip()


def _module_name(name: str) -> str:
    """'jit_step(12345)' -> 'jit_step'."""
    return name.split("(", 1)[0]


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    return files[-1] if files else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals (sorted output)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] not covered by the merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _SpanIndex:
    """Host spans, to split an interval by the span open in each part.
    The benchmark's spans inside the window follow one another without
    nesting, so sorted by start they are sorted by end too."""

    def __init__(self, spans: Sequence[Tuple[float, float, str]]):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]

    def split(self, lo: float, hi: float, into: collections.Counter) -> None:
        covered = 0.0
        j = bisect.bisect_left(self.starts, hi) - 1
        while j >= 0 and self.spans[j][1] > lo:
            s, e, name = self.spans[j]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                into[name] += part
                covered += part
            j -= 1
        if hi - lo > covered:
            into["none"] += hi - lo - covered


def reduce_events(device_ops: Dict[int, List[Tuple[float, float, str]]],
                  host_spans: Sequence[Tuple[float, float, str]]
                  ) -> Optional[Dict]:
    """The reduction itself, over (start_s, end_s, name) events: device
    operations per chip and the host spans (one named ``window``)."""
    windows = [(s, e) for s, e, n in host_spans if n == "window"]
    if not device_ops or not windows:
        return None
    lo, hi = windows[0]
    named = [(s, e, n) for s, e, n in host_spans if n != "window"]
    index = _SpanIndex(named)
    busy_each, op_time = [], collections.Counter()
    idle_by = collections.Counter()
    for chip in sorted(device_ops):
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in device_ops[chip]
               if e > lo and s < hi]
        merged = union([(s, e) for s, e, _ in ops])
        busy_each.append(sum(e - s for s, e in merged))
        for s, e, n in ops:
            op_time[n] += e - s
        if chip == min(device_ops):
            for g0, g1 in gaps(merged, lo, hi):
                index.split(g0, g1, idle_by)
    return {
        "busy_s": sum(busy_each) / len(busy_each),
        "device_s": sum(op_time.values()) / len(busy_each),
        "window_s": hi - lo,
        "device_ops": [[n, t / len(busy_each)]
                       for n, t in op_time.most_common()],
        "idle_gaps": [[n, t] for n, t in idle_by.most_common()],
    }


def read_xplane(path: str, span_names: Sequence[str]):
    """(device_ops, host_spans) from an ``.xplane.pb`` file, in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    wanted = set(span_names)
    device_ops: Dict[int, List[Tuple[float, float, str]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                              _module_name(ev.name))
                             for ev in (lines[_MODULES_LINE].events
                                        if _MODULES_LINE in lines else ()))
            starts = [s for s, _, _ in modules]
            ops = []
            for ev in (lines[_OPS_LINE].events if _OPS_LINE in lines
                       else ()):
                s = ev.start_ns * 1e-9
                i = bisect.bisect_right(starts, s) - 1
                module = modules[i][2] if i >= 0 and modules[i][1] >= s \
                    else "?"
                ops.append((s, ev.end_ns * 1e-9,
                            f"{module}/{_op_name(ev.name)}"))
            device_ops[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                   ev.name)
                                  for ev in line.events if ev.name in wanted)
    return device_ops, host_spans


def reduce_trace(trace_dir: str, span_names: Sequence[str],
                 n_devices: int = 1) -> Optional[Dict]:
    """Reduce the newest trace under ``trace_dir``; None without a device
    plane.  Only the first ``n_devices`` chips (the cell's) count."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    device_ops, host_spans = read_xplane(path, span_names)
    device_ops = {k: v for k, v in device_ops.items() if k < n_devices}
    return reduce_events(device_ops, host_spans)
