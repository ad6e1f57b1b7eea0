"""Median over the window's diagnosis cycles of the host time in the
device feed's refreshes: the program's spans ``feed.refresh``
(``DeviceShardView.refresh``; each detection refreshes the views it
reads), summed per cycle.  See ``program_spans.py``."""
from program_spans import cycle_median_ms


def read(raw):
    return cycle_median_ms(raw, "feed.refresh")
