"""Median host time of the two detector calls of a cycle
(``detect_non_scalable`` + ``detect_abnormal``): the dirty rows' upload,
the fused launches and the readback of their results."""
from statistics import median


def read(raw):
    xs = raw["spans"].get("detect")
    return 1e3 * median(xs) if xs else None
