"""Median host time of the window's compiled (unprofiled) train steps."""
from statistics import median


def read(raw):
    xs = raw["spans"].get("compiled_step")
    return median(xs) if xs else None
