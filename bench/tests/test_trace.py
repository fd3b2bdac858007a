"""The trace reduction: union-of-intervals idle share, kernel events
matched by name, idle gaps named by the harness span around them — on a
hand-made trace with known answers, and on an excerpt recorded from a
traced run of ``mamba2-1.3b.fanin`` on a TPU v5e
(``data/trace_excerpt.json``: its first 60 op events, names cut to 120
characters)."""
import json
from pathlib import Path

import pytest

from lib import trace

DATA = Path(__file__).parent / "data" / "trace_excerpt.json"


def _space(ops, spans):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]},
            {"name": "XLA Modules", "events": [["jit_step", 0, 40, {}]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [[n, s, d, {}] for n, s, d in spans]}]},
    ]}


AGG = ("%closed_call.85 = f32[1,4096]{1,0:T(1,128)} custom-call(f32[1,4096]"
       "{1,0:T(1,128)} %copy.670, f32[2,4096]{1,0:T(2,128)} %pad.184), "
       'custom_call_target="tpu_custom_call"')
OTHER = ("%closed_call.9 = u8[2,2048]{1,0} custom-call(f32[2,4096]{1,0} %x), "
         'custom_call_target="tpu_custom_call"')
HAND = _space(
    ops=[("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 10, {}),
         (AGG, 5, 10, {}), ("%while.3 = (s32[]) while((s32[]) %t)", 20, 10, {}),
         (OTHER, 22, 3, {}), ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                              45, 5, {})],
    spans=[("window", 0, 40), ("dispatch", 14, 8), ("fetch_losses", 16, 3),
           ("drain", 28, 12)])


def test_idle_share_is_one_minus_the_union():
    lo, hi = trace.window_bounds(HAND)
    assert (lo, hi) == (0, 40)
    # ops cover [0, 15) and [20, 30): 25 of 40; the op at 45 is outside
    assert trace.busy_share(HAND, lo, hi) == pytest.approx(25 / 40)


def _agg_metric():
    import importlib.util
    path = Path(__file__).parents[1] / "metrics" / "agg_kernel_ms.py"
    spec = importlib.util.spec_from_file_location("agg_kernel_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_events_matched_by_the_metrics_predicate():
    agg = _agg_metric()
    assert trace.kernel_ns(HAND, agg.is_kernel, 0, 40) == 10
    assert not agg.is_kernel(OTHER)            # another Pallas kernel
    assert agg.is_kernel("%_stream_kernel.4 = f32[8] custom-call()")


def test_gaps_named_by_innermost_span():
    gaps = trace.idle_gaps(HAND, 0, 40)
    assert gaps == [["drain", 10e-9], ["fetch_losses", 5e-9]]


def test_top_ops_by_self_time():
    top = trace.top_ops(HAND, 0, 40, k=4)
    # fusion.1 [0, 10) holds nothing; closed_call.85 [5, 15) starts inside
    # it but ends after, so it is no child; while.3 [20, 30) holds the
    # 3 ns kernel, so 7 ns are its own
    assert top == [["fusion.1 (fusion)", 10e-9],
                   ["closed_call.85 (custom-call)", 10e-9],
                   ["while.3 (while)", 7e-9], ["closed_call.9 (custom-call)",
                                               3e-9]]


def test_excerpt_keeps_kernels_whole_with_what_encloses_them():
    ex = trace.excerpt(HAND, 0, n_events=1, cut=12)
    names = [e[0] for e in trace.op_events(trace.device_planes(ex)[0])]
    # the first op cut; both kernels whole; the while that holds one kept
    assert names == ["%fusion.1 = ", AGG, "%while.3 = (", OTHER]
    assert [e[0] for e in trace.host_events(ex)] == [
        "window", "dispatch", "fetch_losses", "drain"]


@pytest.mark.skipif(not DATA.exists(), reason="no recorded excerpt")
def test_recorded_excerpt():
    space = json.loads(DATA.read_text())
    assert trace.device_planes(space), "the excerpt holds a device plane"
    ops = trace.op_events(trace.device_planes(space)[0])
    lo = min(e[1] for e in ops)
    hi = max(e[1] + e[2] for e in ops)
    share = trace.busy_share(space, lo, hi)
    # by hand: a plain sweep over the sorted events
    busy, end = 0, lo
    for _, s, d, _ in sorted(ops, key=lambda e: e[1]):
        if s + d > end:
            busy += s + d - max(s, end)
            end = s + d
    assert share == pytest.approx(busy / (hi - lo))
    assert 0 < share <= 1
    # the recorded excerpt holds no Pallas kernel event; the kernel match
    # is checked on the hand-made trace, whose kernel names are in the
    # TPU's form
    gaps = trace.idle_gaps(space, lo, hi)
    assert sum(g[1] for g in gaps) <= (hi - lo) * (1 - share) / 1e9 + 1e-12
    spans = {e[0] for e in trace.host_events(space)}
    assert {g[0] for g in gaps} <= spans | {"no harness span"}
    assert all(n.count("(") <= 1 for n, _ in trace.top_ops(space, lo, hi))
