"""Median host time of the window's sampled steps: the eager run, one
top-level equation at a time, that ``GraphProfiler`` times."""
from statistics import median


def read(raw):
    xs = raw["spans"].get("sampled_step")
    return median(xs) if xs else None
