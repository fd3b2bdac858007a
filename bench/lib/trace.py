"""Reduction of a profiler trace to the per-layer numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small neutral form — planes, lines, events ``[name, start_ns, dur_ns,
{stats}]`` — keeping only the device planes and the host line that holds
the harness's own ``TraceAnnotation`` spans. Everything else here reads
that form, so the reduction is tested on a recorded excerpt
(``tests/data/trace_excerpt.json``) without a chip.

* Device busy time is the union of the intervals of the device's op
  events (the ``XLA Ops`` line of each ``/device:TPU:N`` plane) inside the
  window; the idle share is 1 minus busy over the window.
* A kernel's time is the sum of the durations of the op events a metric's
  predicate picks. On a TPU an op event is named by its HLO instruction's
  text (``%closed_call.85 = f32[1,D]{...} custom-call(...), custom_call_
  target="tpu_custom_call", ...``); a Pallas kernel carries its function's
  name there only when its ``pallas_call`` is given a ``name``.
* Ops nest (a ``while`` spans its body), so the ops that took most time are
  ranked by self time: an event's duration less its direct children's.
* An idle gap is a stretch of the window with no op on the device; it is
  named by the innermost harness span that covers its midpoint.

Usage: ``python bench/lib/trace.py <file.xplane.pb>`` prints the planes,
lines and most frequent event names, to see how a new kernel is named."""
from __future__ import annotations

import collections
import json
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"


def load(path: str, host_spans=()) -> dict:
    """Neutral form of one trace file. ``host_spans``: names of the host
    events to keep (the harness's spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [{"name": ln.name,
                      "events": [[e.name, e.start_ns, e.duration_ns,
                                  {k: str(v) for k, v in e.stats}]
                                 for e in ln.events]}
                     for ln in plane.lines]
        elif plane.name == HOST_PLANE:
            keep = set(host_spans)
            lines = []
            for ln in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns, {}]
                       for e in ln.events if e.name in keep]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(space: dict) -> list:
    return [p for p in space["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def op_events(plane: dict) -> list:
    return [e for ln in plane["lines"] if ln["name"] == OPS_LINE
            for e in ln["events"]]


def host_events(space: dict) -> list:
    return [e for p in space["planes"] if p["name"] == HOST_PLANE
            for ln in p["lines"] for e in ln["events"]]


def window_bounds(space: dict):
    """(start_ns, end_ns) of the harness's ``window`` span."""
    spans = [e for e in host_events(space) if e[0] == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one '{WINDOW}' span, found {len(spans)}")
    return spans[0][1], spans[0][1] + spans[0][2]


def merged(intervals, lo: float, hi: float) -> list:
    """Union of [start, end) intervals clipped to [lo, hi), sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(plane: dict, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(
        ((ev[1], ev[1] + ev[2]) for ev in op_events(plane)), lo, hi))


def busy_share(space: dict, lo: float, hi: float) -> float:
    """Device busy time over the window, averaged over the device planes."""
    planes = device_planes(space)
    if not planes or hi <= lo:
        raise ValueError("no device plane or an empty window")
    return sum(busy_ns(p, lo, hi) for p in planes) / len(planes) / (hi - lo)


def kernel_ns(space: dict, match, lo: float, hi: float) -> float:
    """Summed device time of the op events ``match(event name)`` picks,
    starting inside the window, averaged over the device planes."""
    planes = device_planes(space)
    tot = sum(ev[2] for p in planes for ev in op_events(p)
              if lo <= ev[1] < hi and match(ev[0]))
    return tot / max(len(planes), 1)


_KIND = re.compile(r"([a-z][\w-]*)\(")


def short_name(text: str) -> str:
    """``%fusion.12 = bf16[..]{..} fusion(...)`` -> ``fusion.12 (fusion)``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    kind = _KIND.search(rest)
    return f"{head.lstrip('%')} ({kind.group(1)})" if kind else head.lstrip("%")


def self_ns(events) -> list:
    """(event, self time) for the events of one line: an event that lies
    wholly inside an earlier one is its child."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [ev[2] for ev in evs]
    stack = []
    for i, ev in enumerate(evs):
        while stack and (evs[stack[-1]][1] + evs[stack[-1]][2]
                         < ev[1] + ev[2]):
            stack.pop()
        if stack:
            own[stack[-1]] -= ev[2]
        stack.append(i)
    return list(zip(evs, own))


def top_ops(space: dict, lo: float, hi: float, k: int = 10) -> list:
    """[name, seconds] of the k ops with the most self time in the window,
    summed over their executions."""
    acc = collections.Counter()
    planes = device_planes(space)
    for p in planes:
        for ev, own in self_ns(op_events(p)):
            if lo <= ev[1] < hi:
                acc[short_name(ev[0])] += own
    return [[n, t / 1e9 / max(len(planes), 1)] for n, t in acc.most_common(k)]


def idle_gaps(space: dict, lo: float, hi: float, k: int = 10) -> list:
    """[name, seconds] of the k longest stretches of the window in which the
    first device plane ran nothing, each named by the innermost harness
    span (not the window itself) covering its midpoint."""
    planes = device_planes(space)
    if not planes:
        return []
    plane = planes[0]
    busy = merged(((ev[1], ev[1] + ev[2]) for ev in op_events(plane)), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = [e for e in host_events(space) if e[0] != WINDOW]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
        name = (min(cover, key=lambda sp: sp[2])[0] if cover
                else "no harness span")
        named.append([name, (e - s) / 1e9])
    named.sort(key=lambda g: -g[1])
    return named[:k]


def summary(space: dict) -> dict:
    """Planes, lines, event counts and the most frequent names with the
    stat keys they carry — for reading a new trace by hand."""
    out = []
    for p in space["planes"]:
        for ln in p["lines"]:
            names = collections.Counter(e[0] for e in ln["events"])
            stats = sorted({k for e in ln["events"][:200] for k in e[3]})
            out.append({"plane": p["name"], "line": ln["name"],
                        "events": len(ln["events"]),
                        "top": names.most_common(15), "stat_keys": stats})
    return {"lines": out}




def excerpt(space: dict, lo: float, n_events: int = 60,
            near: str = "tpu_custom_call", n_near: int = 8,
            cut: int = 120) -> dict:
    """The first ``n_events`` device op events from ``lo``, the first
    ``n_near`` ops whose name holds ``near`` (Pallas kernels) with the ops
    that enclose them, and the harness spans around them — a sample of the
    trace's real names, nesting and timing for the reduction's tests.
    Names are cut to ``cut`` characters, except those that hold
    ``near``."""
    planes = []
    hi = lo
    for p in device_planes(space):
        evs = sorted((e for e in op_events(p) if e[1] >= lo),
                     key=lambda e: e[1])
        keep = evs[:n_events]
        hits = [e for e in evs if near in e[0]][:n_near]
        keep += hits + [e for e in evs for h in hits
                        if e[1] <= h[1] and e[1] + e[2] >= h[1] + h[2]]
        keep = sorted({id(e): e for e in keep}.values(), key=lambda e: e[1])
        if keep:
            hi = max(e[1] + e[2] for e in keep)
        planes.append({"name": p["name"], "lines": [
            {"name": OPS_LINE, "events": [
                [e[0] if near in e[0] else e[0][:cut], e[1], e[2], {}]
                for e in keep]}]})
    host = [e for e in host_events(space)
            if e[1] < hi and e[1] + e[2] > lo]
    planes.append({"name": HOST_PLANE,
                   "lines": [{"name": "harness", "events": host}]})
    return {"planes": planes}


if __name__ == "__main__":
    print(json.dumps(summary(load(sys.argv[1], host_spans=sys.argv[2:])),
                     indent=1))
