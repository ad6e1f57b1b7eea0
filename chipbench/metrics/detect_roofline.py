"""Detection's share of its roofline: the least time the chip could take
to read what detection must read (the live scale's (P, V) time and
variance blocks and the finished scales' merged columns, by the shapes;
``detect_input_bytes``) for every cycle of the window, at the HBM peak of
``peaks.json``, over the device time of every operation in the traced
window.  In the diagnosis cells all of it is detection's (the row
scatters that feed it, the merges and the kernels; backtracking and
rendering run on the host), so the share reads the same work whatever
the kernels doing it are named."""


def read(raw):
    trace = raw.get("trace")
    if not trace or trace["device_s"] <= 0.0 or not raw.get("cycles"):
        return None
    bound_s = raw["detect_input_bytes"] * raw["cycles"] \
        / raw["peaks"]["hbm_bytes_per_s"]
    return 100.0 * bound_s / trace["device_s"]
