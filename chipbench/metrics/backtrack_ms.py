"""Median host time per cycle of backtracking and reporting:
``backtrack`` + ``root_causes``, then ``render_report``."""
from statistics import median


def read(raw):
    back, render = raw["spans"].get("backtrack"), raw["spans"].get("render")
    if not back or not render:
        return None
    return 1e3 * median(b + r for b, r in zip(back, render))
