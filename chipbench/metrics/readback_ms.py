"""Median over the window's diagnosis cycles of the host time spent
reading the detection kernels' results back (waiting for the device
included): the program's spans ``detect.readback``, summed per cycle.
See ``program_spans.py``."""
from program_spans import cycle_median_ms


def read(raw):
    return cycle_median_ms(raw, "detect.readback")
