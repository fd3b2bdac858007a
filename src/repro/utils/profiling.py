"""The names the round engine gives its layers in a profiler trace.

Device scopes are ``jax.named_scope`` names: they only write the HLO op
metadata (``op_name``), so the compiled program is the same with or
without them, and a trace's device ops carry them in their scope path.
Host spans are ``jax.profiler.TraceAnnotation`` names on the profiler's
clock; they record nothing while no trace is active. What each one
covers: docs/architecture.md, "Profiling a run"."""
from __future__ import annotations

import functools

import jax

SCOPES = ("favas_sample", "favas_local_training", "favas_relayout",
          "favas_encode_progress", "favas_aggregate", "favas_reset",
          "favas_evict", "favas_promote")


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
