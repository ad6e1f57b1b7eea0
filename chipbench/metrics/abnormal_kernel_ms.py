"""Device milliseconds per diagnosis cycle of the Pallas abnormal-detection
kernel (the trace's op ``%detect_abnormal``, in ``jit_ab_fused_kernel``):
one 128-column tile of vertices per grid step, so a PSG of more than 128
vertices takes two.  From the trace reduction's per-op device time over
the window's cycles."""
import re

_KERNEL = re.compile(r"/[%_]detect_abnormal(\.\d+)?$")


def read(raw):
    trace = raw.get("trace")
    if not trace or not raw.get("cycles"):
        return None
    seconds = sum(t for name, t in trace["device_ops"]
                  if _KERNEL.search(name))
    return 1e3 * seconds / raw["cycles"] if seconds else None
