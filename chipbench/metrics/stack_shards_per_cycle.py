"""Per-host blocks the sharded store's stacked reads copy per diagnosis
cycle: the stat ``restacked`` of the program's spans ``store.stack``
summed over the window's cycles, over their number.  None where those
spans carry no such stat (a program that stacks every block on every
read).  See ``program_spans.py``."""
from program_spans import load


def read(raw):
    spans = load(raw)
    if spans is None or not spans.n_cycles:
        return None
    if not any("restacked" in st for *_, st in spans.named("store.stack")):
        return None
    return spans.stat_total("store.stack", "restacked") / spans.n_cycles
