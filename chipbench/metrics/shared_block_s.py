"""Median device seconds per compiled step (program span
``trainer.step`` holding ``profiler.compiled_step``) in the ops whose name
stack holds the named scope ``hybrid.shared_block``: the shared blocks'
forward, recompute and backward.  See ``op_scopes.py``; None where no op
carries the scope."""
from statistics import median

import op_scopes
import program_spans

SCOPE = "hybrid.shared_block"


def read(raw):
    spans = program_spans.timed(raw)
    ops = op_scopes.load(raw)
    if spans is None or ops is None:
        return None
    per_step = [ops.seconds_in(SCOPE, s, e)
                for s, e in spans.steps("profiler.compiled_step")]
    if not any(per_step):
        return None
    return median(per_step)
