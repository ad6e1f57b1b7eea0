"""Detection kernel launches per cycle, from the program's counter
``repro.kernels.detect_fused.ops.launch_counts`` over the window."""


def read(raw):
    return raw["launches"] / raw["cycles"] if raw.get("cycles") else None
