"""Device time by the program's layer scopes, read from a profiler trace.

The program names its layers with ``jax.named_scope``
(``src/repro/utils/profiling.py``). The name lands in each device op's
HLO ``op_name``, a scope path such as ``jit(<unknown>)/while/body/
closed_call/favas_local_training/vmap()/while/body/closed_call/
transpose(jvp(...))/dot_general``. On a TPU the trace keeps that path as
the ``tf_op`` stat of the op's event metadata. ``jax.profiler.
ProfileData`` gives an event's own stats only, so ``trace.load`` does not
see it: ``load`` here reads the ``.xplane.pb`` a second time for the
device planes' event metadata and puts each op's path in its stats under
``tf_op``.

An op belongs to the innermost ``favas_`` scope on its path; an op with
none is unscoped. Times are self times (``trace.self_ns``), so a
``while`` that encloses a scope's ops does not count them a second time,
and the scopes' times and the unscoped time add up to the ops' summed
self time.

Usage: ``python bench/lib/scopes.py <file.xplane.pb> [--rounds N]
[--excerpt out.json]`` prints, for the trace's ``window`` span (or the
whole trace without one), each scope's device time and share and the
largest unscoped ops, and writes an excerpt that keeps the scope paths
(``excerpt``) for the tests."""
from __future__ import annotations

import argparse
import collections
import json
import re
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import trace  # noqa: E402

SCOPES = ("favas_sample", "favas_local_training", "favas_relayout",
          "favas_encode_progress", "favas_aggregate", "favas_reset",
          "favas_evict", "favas_promote")
UNSCOPED = "unscoped"
PATH_STAT = "tf_op"         # the event-metadata stat that holds op_name
_SCOPE = re.compile(r"\b(" + "|".join(SCOPES) + r")\b")


# The XSpace protobuf, read by field number (tsl/profiler/protobuf/
# xplane.proto): XSpace.planes 1; XPlane.name 2, .event_metadata 4,
# .stat_metadata 5 (map entries: key 1, value 2); XEventMetadata.name 2,
# .stats 5; XStatMetadata.name 2; XStat.metadata_id 1, .str_value 5,
# .ref_value 7 (the id of a stat metadata whose name is the string).

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    a varint, a (start, end) span for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    return [v for f, v in _fields(buf, *span) if f == 2]


def op_paths(path: str) -> dict:
    """{device plane name: {op event name: tf_op path}} of a trace file."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                events += _map_values(buf, v)
            elif g == 5:
                for md in _map_values(buf, v):
                    fs = dict(_fields(buf, *md))
                    stat_names[fs.get(1, 0)] = (_text(buf, fs[2]) if 2 in fs
                                                else "")
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        ids = {k for k, n in stat_names.items() if n == PATH_STAT}
        paths = {}
        for ev in events:
            ev_name, op = "", ""
            for g, v in _fields(buf, *ev):
                if g == 2:
                    ev_name = _text(buf, v)
                elif g == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1) in ids:
                        op = (_text(buf, st[5]) if 5 in st
                              else stat_names.get(st.get(7), ""))
            if op:
                paths.setdefault(ev_name, op)
        out[name] = paths
    return out


def load(path: str, host_spans=()) -> dict:
    """``trace.load``'s neutral form, each device op event carrying its
    scope path under ``tf_op``."""
    space = trace.load(path, host_spans=host_spans)
    paths = op_paths(path)
    for p in trace.device_planes(space):
        by_name = paths.get(p["name"], {})
        for ev in trace.op_events(p):
            ev[3][PATH_STAT] = by_name.get(ev[0], "")
    return space


def scope_of(ev) -> str:
    """The innermost program scope on the op's path, or ``UNSCOPED``."""
    found = _SCOPE.findall(ev[3].get(PATH_STAT, ""))
    return found[-1] if found else UNSCOPED


def by_scope(space: dict, lo: float, hi: float) -> tuple:
    """({scope: self ns}, {unscoped op: self ns}) of the op events that
    start inside [lo, hi), averaged over the device planes."""
    planes = trace.device_planes(space)
    scoped = collections.Counter()
    loose = collections.Counter()
    for p in planes:
        for ev, own in trace.self_ns(trace.op_events(p)):
            if lo <= ev[1] < hi:
                s = scope_of(ev)
                scoped[s] += own
                if s == UNSCOPED:
                    loose[trace.short_name(ev[0])] += own
    k = max(len(planes), 1)
    return ({s: t / k for s, t in scoped.items()},
            {n: t / k for n, t in loose.items()})


def excerpt(space: dict, lo: float, per_scope: int = 6,
            cut: int = 160) -> dict:
    """A sample of the trace from ``lo`` that keeps the scope paths: per
    device plane the first ``per_scope`` ops of every scope (unscoped
    too), every Pallas kernel among the first thousand ops, and the ops
    that enclose what is kept; names cut to ``cut`` characters (kernels
    whole), of the stats the scope path alone; and the host spans around
    them."""
    planes = []
    hi = lo
    for p in trace.device_planes(space):
        evs = sorted((e for e in trace.op_events(p) if e[1] >= lo),
                     key=lambda e: e[1])
        count = collections.Counter()
        keep = []
        for e in evs:
            s = scope_of(e)
            if count[s] < per_scope:
                count[s] += 1
                keep.append(e)
        keep += [e for e in evs[:1000] if "tpu_custom_call" in e[0]]
        keep += [e for e in evs for k in keep if e is not k
                 and e[1] <= k[1] and e[1] + e[2] >= k[1] + k[2]]
        keep = sorted({id(e): e for e in keep}.values(), key=lambda e: e[1])
        if keep:
            hi = max(hi, max(e[1] + e[2] for e in keep))
        planes.append({"name": p["name"], "lines": [{
            "name": trace.OPS_LINE, "events": [
                [e[0] if "tpu_custom_call" in e[0] else e[0][:cut], e[1],
                 e[2], {PATH_STAT: e[3].get(PATH_STAT, "")}]
                for e in keep]}]})
    host = [e for e in trace.host_events(space)
            if e[1] < hi and e[1] + e[2] > lo]
    planes.append({"name": trace.HOST_PLANE,
                   "lines": [{"name": "harness", "events": host}]})
    return {"planes": planes}


def report(space: dict, rounds: int | None = None, k: int = 12) -> dict:
    """Each scope's device seconds (and ms per round, given ``rounds``)
    and share of the summed self time in the ``window`` span, or in the
    whole trace without one; the ``k`` largest unscoped ops."""
    try:
        lo, hi = trace.window_bounds(space)
    except ValueError:
        lo, hi = 0, float("inf")
    ns, loose = by_scope(space, lo, hi)
    total = sum(ns.values()) or 1.0

    def row(t):
        r = {"s": t / 1e9, "share": t / total}
        if rounds:
            r["ms_per_round"] = t / 1e6 / rounds
        return r
    return {"scopes": {s: row(t) for s, t in
                       sorted(ns.items(), key=lambda x: -x[1])},
            "unscoped_top": [[n, row(t)] for n, t in
                             sorted(loose.items(), key=lambda x: -x[1])[:k]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("--rounds", type=int, default=None,
                    help="rounds in the window, for ms per round")
    ap.add_argument("--excerpt", default=None,
                    help="write an excerpt of the window to this file")
    args = ap.parse_args(argv)
    space = load(args.xplane, host_spans=(trace.WINDOW, "dispatch",
                                          "fetch_losses", "drain"))
    print(json.dumps(report(space, args.rounds), indent=1))
    if args.excerpt:
        lo = trace.window_bounds(space)[0]
        Path(args.excerpt).write_text(json.dumps(excerpt(space, lo)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
