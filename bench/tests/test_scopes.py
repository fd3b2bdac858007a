"""Device time by the program's layer scopes (``lib/scopes.py``): the
XSpace reader on a hand-encoded trace file, the per-scope self time on a
hand-made trace in the TPU's naming with nested ops, and an excerpt
recorded from a traced run of ``mamba2-1.3b.fanin`` on a TPU v5e
(``data/trace_excerpt_scoped.json``)."""
import importlib.util
import json
from pathlib import Path

import pytest

from lib import scopes, trace

DATA = Path(__file__).parent / "data" / "trace_excerpt_scoped.json"
ROOT = "jit(<unknown>)/while/body/closed_call"
KERNEL = ("%favas_stream.3 = f32[1,4096]{1,0:T(1,128)} custom-call(f32[1,4096]"
          "{1,0:T(1,128)} %copy.670, f32[8,4096]{1,0:T(8,128)} %pad.184), "
          'custom_call_target="tpu_custom_call"')


def _op(name, start, dur, path=""):
    return [name, start, dur, {scopes.PATH_STAT: path}]


def _space(ops):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["window", 0, 100, {}]]}]}]}


# a chunk's while [0, 100) holds one round: local training's inner while
# [0, 30) with a fusion of its own inside, the relayout, the aggregation
# kernel, the reset scatter and a carry copy that XLA left without
# op_name; one op falls after the window
HAND = _space([
    _op("%while.1 = (s32[]) while((s32[]) %t)", 0, 100,
        "jit(<unknown>)/while"),
    _op("%while.7 = (s32[]) while((s32[]) %u)", 0, 30,
        f"{ROOT}/favas_local_training/vmap()/while"),
    _op("%fusion.2 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p)", 5, 10,
        f"{ROOT}/favas_local_training/vmap()/while/body/closed_call/"
        "transpose(jvp(...d,vd->...v))/dot_general"),
    _op("%pad.851 = f32[8,4096]{1,0} pad(f32[8,4000]{1,0} %a, f32[] %z)", 30,
        20, f"{ROOT}/favas_relayout/jit(_pad)/pad"),
    _op(KERNEL, 50, 10, f"{ROOT}/favas_aggregate/favas_stream/pallas_call"),
    _op("%dynamic-update-slice.90 = f32[8,4096]{1,0} dynamic-update-slice("
        "f32[8,4096]{1,0} %c, f32[1,4096]{1,0} %r)", 60, 5,
        f"{ROOT}/favas_reset/scatter"),
    _op("%copy.5 = f32[8,4096]{1,0} copy(f32[8,4096]{1,0} %d)", 65, 5),
    _op("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %e)", 120, 50,
        f"{ROOT}/favas_relayout/pad"),
])


def _agg_metric():
    path = Path(__file__).parents[1] / "metrics" / "agg_kernel_ms.py"
    spec = importlib.util.spec_from_file_location("agg_kernel_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- a trace file written by hand, field by field ---------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _plane(name, events, stats):
    """An XPlane: ``events`` {id: (name, [(stat id, str or ref)])},
    ``stats`` {id: stat name}."""
    out = [(2, name)]
    for i, (ev_name, ev_stats) in events.items():
        st = [_msg((1, sid), (7, v) if isinstance(v, int) else (5, v))
              for sid, v in ev_stats]
        md = _msg((1, i), (2, ev_name), *[(5, s) for s in st])
        out.append((4, _msg((1, i), (2, md))))
    for i, n in stats.items():
        out.append((5, _msg((1, i), (2, _msg((1, i), (2, n))))))
    return _msg(*out)


def test_op_paths_reads_the_event_metadata(tmp_path):
    stats = {1: "hlo_category", 2: "tf_op", 3: f"{ROOT}/favas_reset/scatter"}
    dev = _plane("/device:TPU:0", {
        7: ("%scatter.4 = f32[8]{0} scatter()",
            [(1, "scatter"), (2, f"{ROOT}/favas_sample/x")]),
        8: ("%dus.9 = f32[8]{0} dynamic-update-slice()", [(2, 3)]),  # a ref
        9: ("%copy.1 = f32[8]{0} copy()", [(1, "data formatting")])},
        stats)
    host = _plane("/host:CPU", {1: ("window", [(2, "jit(f)/favas_reset")])},
                  stats)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, dev), (1, host)))
    assert scopes.op_paths(str(path)) == {"/device:TPU:0": {
        "%scatter.4 = f32[8]{0} scatter()": f"{ROOT}/favas_sample/x",
        "%dus.9 = f32[8]{0} dynamic-update-slice()":
            f"{ROOT}/favas_reset/scatter"}}


# --- the reduction -----------------------------------------------------------

def test_self_time_by_innermost_scope():
    ns, loose = scopes.by_scope(HAND, 0, 100)
    # local training: its while's 20 ns of own time and the 10 ns fusion;
    # the chunk's while keeps 100 - 30 - 20 - 10 - 5 - 5 = 30 ns
    assert ns == {"favas_local_training": 30, "favas_relayout": 20,
                  "favas_aggregate": 10, "favas_reset": 5,
                  scopes.UNSCOPED: 35}
    assert loose == {"while.1 (while)": 30, "copy.5 (copy)": 5}
    # the scopes and the unscoped rest add up to the busy time
    assert sum(ns.values()) == trace.busy_ns(trace.device_planes(HAND)[0],
                                             0, 100)


def test_innermost_scope_wins():
    assert scopes.scope_of(_op("%f = f32[] fusion()", 0, 1,
                               f"{ROOT}/favas_encode_progress/"
                               "favas_relayout/concatenate")) == \
        "favas_relayout"
    assert scopes.scope_of(_op("%f = f32[] fusion()", 0, 1,
                               f"{ROOT}/favas_aggregate/favas_stream/"
                               "pallas_call")) == "favas_aggregate"
    # a file name is no scope
    assert scopes.scope_of(_op("%f = f32[] fusion()", 0, 1,
                               "kernels/favas_agg.py")) == scopes.UNSCOPED


def test_report_per_round_and_shares():
    r = scopes.report(HAND, rounds=2)
    assert list(r["scopes"]) == [scopes.UNSCOPED, "favas_local_training",
                                 "favas_relayout", "favas_aggregate",
                                 "favas_reset"]
    assert r["scopes"]["favas_relayout"] == pytest.approx(
        {"s": 20e-9, "share": 0.2, "ms_per_round": 10e-6})
    assert sum(v["share"] for v in r["scopes"].values()) == pytest.approx(1)
    assert [n for n, _ in r["unscoped_top"]] == ["while.1 (while)",
                                                 "copy.5 (copy)"]


def test_excerpt_keeps_the_scope_path():
    ex = scopes.excerpt(HAND, 0, per_scope=1, cut=20)
    evs = trace.op_events(trace.device_planes(ex)[0])
    assert [scopes.scope_of(e) for e in evs] == [
        scopes.UNSCOPED, "favas_local_training", "favas_relayout",
        "favas_aggregate", "favas_reset"]
    assert evs[3][0] == KERNEL                   # the kernel kept whole
    assert all(len(e[0]) <= 20 for e in evs if e[0] != KERNEL)
    assert [e[0] for e in trace.host_events(ex)] == ["window"]


def test_recorded_scoped_excerpt():
    space = json.loads(DATA.read_text())
    ops = trace.op_events(trace.device_planes(space)[0])
    lo = min(e[1] for e in ops)
    hi = max(e[1] + e[2] for e in ops)
    ns, _ = scopes.by_scope(space, lo, hi + 1)
    assert {"favas_sample", "favas_local_training", "favas_relayout",
            "favas_aggregate", "favas_reset"} <= set(ns)
    assert sum(ns.values()) == pytest.approx(
        sum(own for _, own in trace.self_ns(ops)))
    # the aggregation kernel, named favas_stream, is the one op the
    # agg_kernel_ms signature picks, and it sits in favas_aggregate
    agg = _agg_metric()
    kernels = [e for e in ops if agg.is_kernel(e[0])]
    assert len(kernels) >= 1
    assert all(e[0].startswith("%favas_stream.") for e in kernels)
    assert all(scopes.scope_of(e) == "favas_aggregate" for e in kernels)
    assert [e for e in ops if "tpu_custom_call" in e[0]] == kernels
