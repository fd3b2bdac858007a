"""Model FLOP utilization of the whole step, in %: the FLOPs that the
window's live local steps need (``lib/counting.py``, live steps replayed
from the state key by ``lib/replay.py``) over the window's seconds times
the chip's bf16 peak. Masked steps of saturated clients are not counted.
Silent when the replay disagrees with the engine's own counters, or off
a chip with published peaks."""


def read(facts):
    if facts.live_flops is None or facts.peaks is None:
        return None
    return 100.0 * facts.live_flops / (facts.window_s
                                       * facts.peaks["bf16_flops"])
