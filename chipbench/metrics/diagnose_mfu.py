"""The whole diagnosis loop's share of the chip's peak: the least time
the chip needs for the window's detections (``detect_input_bytes`` per
cycle at the HBM peak of ``peaks.json``; detection is bound by bytes,
not operations) over the window's host time."""


def read(raw):
    if not raw.get("cycles"):
        return None
    bound_s = raw["detect_input_bytes"] * raw["cycles"] \
        / raw["peaks"]["hbm_bytes_per_s"]
    return 100.0 * bound_s / raw["cycle_s_total"]
