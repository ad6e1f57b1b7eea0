"""Median over the window's diagnosis cycles of the host time in the
sharded store's stacked reads (the per-host blocks stacked into one
(P, V) matrix for backtracking and the report): the program's spans
``store.stack``, summed per cycle.  See ``program_spans.py``."""
from program_spans import cycle_median_ms


def read(raw):
    return cycle_median_ms(raw, "store.stack")
