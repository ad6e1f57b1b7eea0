"""Per-host blocks the device feed's refreshes walk per diagnosis cycle:
the stat ``blocks`` of the program's spans ``feed.refresh`` summed over
the window's cycles, over their number.  See ``program_spans.py``."""
from program_spans import load


def read(raw):
    spans = load(raw)
    if spans is None or not spans.n_cycles:
        return None
    return spans.stat_total("feed.refresh", "blocks") / spans.n_cycles
