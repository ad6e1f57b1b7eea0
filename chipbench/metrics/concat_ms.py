"""Median over the window's diagnosis cycles of the host time in the
eager concatenations of a scale's per-host device blocks before a
fused kernel: the program's spans ``detect.concat``, summed per cycle.
See ``program_spans.py``."""
from program_spans import cycle_median_ms


def read(raw):
    return cycle_median_ms(raw, "detect.concat")
