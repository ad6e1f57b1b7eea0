"""Median over the window's sampled steps of the host time between the
per-equation fences: each program span ``profiler.sampled_step`` minus
its ``profiler.fence`` spans (where the host waits for the device), so
the equation-by-equation dispatch.  See ``program_spans.py``."""
from statistics import median

from program_spans import timed


def read(raw):
    spans = timed(raw)
    if spans is None:
        return None
    out = [e - s - spans.inside_s("profiler.fence", s, e)
           for s, e, _, _ in spans.named("profiler.sampled_step")]
    return median(out) if out else None
