"""Device time of the streamed FAVAS aggregation kernel per round, in ms:
the summed durations of the kernel's events in the traced window over the
rounds in it. The kernel is ``kernels/favas_agg.py``'s ``_stream_kernel``,
reached through ``round_engine.stream_bucket_update``. Its ``pallas_call``
has no ``name``, so the trace does not carry the function's name; the
event is picked by its signature instead: a Pallas kernel
(``tpu_custom_call``) that maps the (1, D) server row, its first operand,
to a new (1, D) row — no other kernel of the round has that shape. An
event that does carry the name counts too. Silent where none is found."""
import re

from lib import trace

KERNEL = "_stream_kernel"
SIGNATURE = re.compile(r"= f32\[1,(\d+)\]\{[^}]*\} custom-call\(f32\[1,\1\]\{")


def is_kernel(name: str) -> bool:
    return KERNEL in name or ("tpu_custom_call" in name
                              and SIGNATURE.search(name) is not None)


def read(facts):
    lo, hi = facts.window_ns
    ns = trace.kernel_ns(facts.space, is_kernel, lo, hi)
    if ns <= 0:
        return None
    return ns / 1e6 / facts.rounds
