"""Share of the traced window in which no operation ran on the chip,
while diagnosis cycles run (trace reduction: 1 - busy / window)."""


def read(raw):
    trace = raw.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
