"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in %. Layer: device."""
from lib import trace


def read(facts):
    if not trace.device_planes(facts.space):
        return None
    lo, hi = facts.window_ns
    return 100.0 * (1.0 - trace.busy_share(facts.space, lo, hi))
