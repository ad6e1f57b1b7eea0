"""The program's own spans in a traced run, beside the device's operations.

The program marks its layer boundaries with ``jax.profiler`` annotations
named ``scalana.<name>`` (``repro.core.spans``; the names in its
``NAMES``), some with stats: the counts the code has at that boundary.
They land on the host plane of the same ``.xplane.pb`` as the device's
``XLA Ops``, on the clock ``trace_reduce.py`` aligns.  This module reads
the newest trace under ``<checkout>/.chipbench/trace`` once per process
and, inside the benchmark's ``window`` span, groups the program's spans

- by diagnosis cycle: the k-th cycle runs from the start of the k-th
  benchmark span ``apply`` to the start of the next one (the last to
  the window's end);
- by trainer iteration: each ``scalana.trainer.step``, and whether it
  holds a sampled (``profiler.sampled_step``) or a compiled
  (``profiler.compiled_step``) step;

and attributes each idle gap of the device (the first chip) to the
innermost program span open during it (program spans nest, the
benchmark's do not), printing that table to standard error.

``load(raw)`` returns None on an untraced run, and where the program
emits no spans (a checkout without ``repro.core.spans``).  Where the trace
has no device plane (a CPU run; ``raw["trace"]`` is None) it still holds
the spans and their stats, but no idle times.
"""
from __future__ import annotations

import bisect
import collections
import os
import re
import sys
from statistics import median
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
PREFIX = "scalana."

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Span = Tuple[float, float, str, Dict[str, float]]     # start_s, end_s, name


def program_names() -> Optional[Tuple[str, ...]]:
    try:
        from repro.core.spans import NAMES
    except ImportError:
        return None
    return tuple(NAMES)


class Spans:
    """One traced window: the program's spans (name without the prefix),
    the cycles, and the device's busy intervals (None without a device
    plane)."""

    def __init__(self, window: Tuple[float, float], applies: Sequence[float],
                 spans: Sequence[Span],
                 busy: Optional[Sequence[Tuple[float, float]]]):
        self.lo, self.hi = window
        self.spans = sorted((s for s in spans
                             if s[0] >= self.lo and s[1] <= self.hi),
                            key=lambda s: (s[0], -s[1]))
        self.cycle_starts = sorted(a for a in applies
                                   if self.lo <= a < self.hi)
        self.busy = None if busy is None else trace_reduce.union(
            [(max(s, self.lo), min(e, self.hi)) for s, e in busy
             if e > self.lo and s < self.hi])
        if self.busy is not None:
            self._starts = [s for s, _ in self.busy]
            self._before = [0.0]
            for s, e in self.busy:
                self._before.append(self._before[-1] + e - s)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s[2] == name]

    # -- diagnosis cycles ----------------------------------------------
    @property
    def n_cycles(self) -> int:
        return len(self.cycle_starts)

    def per_cycle_s(self, name: str) -> List[float]:
        """Each cycle's summed seconds of the spans ``name`` that start
        in it."""
        out = [0.0] * self.n_cycles
        for s, e, _, _ in self.named(name):
            k = bisect.bisect_right(self.cycle_starts, s) - 1
            if k >= 0:
                out[k] += e - s
        return out

    def stat_total(self, name: str, stat: str) -> float:
        """The stat ``stat`` summed over the spans ``name`` that start
        in a cycle."""
        first = self.cycle_starts[0] if self.cycle_starts else self.hi
        return sum(st.get(stat, 0.0) for s, _, _, st in self.named(name)
                   if s >= first)

    def inside_s(self, name: str, lo: float, hi: float) -> float:
        """Summed seconds of the spans ``name`` that lie in [lo, hi]."""
        return sum(e - s for s, e, _, _ in self.named(name)
                   if s >= lo and e <= hi)

    # -- device idle ---------------------------------------------------
    def busy_in(self, lo: float, hi: float) -> float:
        """Device-busy seconds inside [lo, hi]."""
        def upto(t):
            i = bisect.bisect_right(self._starts, t)
            done = self._before[i]
            if i and self.busy[i - 1][1] > t:
                done -= self.busy[i - 1][1] - t
            return done
        return upto(hi) - upto(lo)

    def idle_in(self, lo: float, hi: float) -> Optional[float]:
        if self.busy is None:
            return None
        return (hi - lo) - self.busy_in(lo, hi)

    def innermost(self) -> List[Tuple[float, float, str]]:
        """The window cut into pieces, each labelled with the innermost
        program span open over it ('none' where none is)."""
        out, open_, t = [], [], self.lo

        def emit(upto):
            nonlocal t
            if upto > t:
                label = max(open_)[2] if open_ else "none"
                out.append((t, upto, label))
                t = upto

        ends = []
        for s, e, name, _ in self.spans:
            while ends and min(ends)[0] <= s:
                end = min(ends)
                emit(end[0])
                ends.remove(end)
                open_.remove(end[1])
            emit(s)
            item = (s, -e, name)
            open_.append(item)
            ends.append((e, item))
        while ends:
            end = min(ends)
            emit(end[0])
            ends.remove(end)
            open_.remove(end[1])
        emit(self.hi)
        return out

    def idle_by_span(self) -> Optional[Dict[str, float]]:
        """Device idle seconds by the innermost program span open."""
        if self.busy is None:
            return None
        out: Dict[str, float] = collections.Counter()
        pieces = self.innermost()
        for g0, g1 in trace_reduce.gaps(self.busy, self.lo, self.hi):
            i = bisect.bisect_right(pieces, (g0, float("inf"), "")) - 1
            i = max(i, 0)
            while i < len(pieces) and pieces[i][0] < g1:
                p0, p1, label = pieces[i]
                part = min(p1, g1) - max(p0, g0)
                if part > 0:
                    out[label] += part
                i += 1
        return dict(out)

    # -- trainer iterations --------------------------------------------
    def steps(self, inner: str) -> List[Tuple[float, float]]:
        """The ``trainer.step`` spans that hold a span ``inner``."""
        marks = sorted(s for s, _, _, _ in self.named(inner))
        out = []
        for s, e, _, _ in self.named("trainer.step"):
            i = bisect.bisect_left(marks, s)
            if i < len(marks) and marks[i] < e:
                out.append((s, e))
        return out

    def report(self) -> str:
        lines = [f"program spans: window {self.hi - self.lo:.3f} s, "
                 f"{self.n_cycles} diagnosis cycles, "
                 f"{len(self.named('trainer.step'))} trainer steps"]
        idle = self.idle_by_span()
        totals = collections.Counter()
        counts = collections.Counter()
        for s, e, name, _ in self.spans:
            totals[name] += e - s
            counts[name] += 1
        if idle is not None:
            whole = sum(idle.values())
            inside = whole - idle.get("none", 0.0)
            lines.append(f"device idle {whole:.4f} s; inside a program span "
                         f"{inside:.4f} s "
                         f"({100.0 * inside / whole if whole else 0.0:.2f} %)")
        lines.append(f"{'span':<24}{'count':>8}{'span_s':>12}"
                     f"{'idle_s (innermost)':>20}")
        for name in sorted(set(totals) | set(idle or {}),
                           key=lambda n: -(idle or totals).get(n, 0.0)):
            idle_s = "" if idle is None else f"{idle.get(name, 0.0):.4f}"
            lines.append(f"{name:<24}{counts.get(name, 0):>8}"
                         f"{totals.get(name, 0.0):>12.4f}{idle_s:>20}")
        return "\n".join(lines)


def read_trace(path: str, names: Sequence[str]):
    """(window, apply starts, program spans, device busy intervals of
    the first chip or None) from an ``.xplane.pb`` file, in seconds."""
    from jax.profiler import ProfileData
    wanted = {PREFIX + n for n in names}
    data = ProfileData.from_file(path)
    windows, applies, spans = [], [], []
    chips: Dict[int, List[Tuple[float, float]]] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops = chips.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name in wanted:
                        spans.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                      name[len(PREFIX):],
                                      {k: float(v) for k, v in ev.stats
                                       if isinstance(v, (int, float))}))
                    elif name == "window":
                        windows.append((ev.start_ns * 1e-9,
                                        ev.end_ns * 1e-9))
                    elif name == "apply":
                        applies.append(ev.start_ns * 1e-9)
    busy = chips[min(chips)] if chips else None
    return (windows[0] if windows else None), applies, spans, busy


_cache: Dict[str, Optional[Spans]] = {}


def load(raw) -> Optional[Spans]:
    """This run's :class:`Spans`; None on an untraced run, or where the
    program emits no spans.  Read once per process; the idle table goes
    to standard error."""
    if "trace" not in raw:
        return None
    names = program_names()
    path = trace_reduce.find_xplane(TRACE_DIR)
    if names is None or path is None:
        return None
    key = f"{path}:{os.path.getmtime(path)}"
    if key not in _cache:
        window, applies, spans, busy = read_trace(path, names)
        if raw.get("trace") is None:
            busy = None
        found = None
        if window is not None and spans:
            found = Spans(window, applies, spans, busy)
            print(found.report(), file=sys.stderr, flush=True)
        _cache[key] = found
    return _cache[key]


def timed(raw) -> Optional[Spans]:
    """:func:`load`, only where the trace has a device plane: the times
    of a run on the device."""
    spans = load(raw)
    return spans if spans is not None and spans.busy is not None else None


def cycle_median_ms(raw, name: str) -> Optional[float]:
    """Median over the diagnosis cycles of the summed ``name`` spans."""
    spans = timed(raw)
    if spans is None or not spans.n_cycles:
        return None
    return 1e3 * median(spans.per_cycle_s(name))


def step_idle_median_s(raw, inner: str) -> Optional[float]:
    """Median device idle seconds of the trainer steps that hold ``inner``."""
    spans = timed(raw)
    steps = spans.steps(inner) if spans is not None else []
    if not steps:
        return None
    return median(spans.idle_in(s, e) for s, e in steps)
