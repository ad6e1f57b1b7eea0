"""Median device idle seconds inside the trainer iterations (program
span ``trainer.step``) that hold a compiled step.  See
``program_spans.py``."""
from program_spans import step_idle_median_s


def read(raw):
    return step_idle_median_s(raw, "profiler.compiled_step")
