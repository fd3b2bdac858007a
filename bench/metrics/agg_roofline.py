"""The aggregation kernel's share of its roofline, in %: the bytes one
round's aggregation needs, (2 s + 2) D float32 words (``lib/counting.py``),
over the kernel's time per round (``agg_kernel_ms``) times the chip's HBM
bandwidth. The kernel does no matrix product, so memory bounds it."""


def read(facts):
    ms = facts.metric("agg_kernel_ms")
    if ms is None or facts.peaks is None:
        return None
    least_s = facts.agg_bytes_per_round / facts.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
