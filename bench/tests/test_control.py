"""The control comes out not correct, and the program does not.

At toy widths on the CPU (``lib/tiny.py``): the plain reference computed in
scaled fp8, put in the program's place, fails at least one of each cell's
numbers, as do the planted faults, while the program's own runs pass —
the same readings ``bench/control.py`` takes on the chip at each cell's
size to set its limits."""
import pytest

from lib import check, tiny

SEEDS = [3, 2 ** 31 + 7]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    import control
    from lib import cell as cell_mod, harness
    bench = tiny.make(tmp_path_factory.mktemp("tiny"), limits=tiny.LIMITS)
    orig = harness.prepare

    def prepare(workload, b=None, **kw):
        return orig(workload, bench, **kw)
    harness.prepare = prepare
    try:
        return {w: control.readings(w, SEEDS, set(SEEDS), allow_cpu=True,
                                    witness={"gated_norm_epsilon": 1e-5})
                for w in tiny.CELLS}
    finally:
        harness.prepare = orig


def _fails(nums: dict) -> bool:
    return not check.judge(nums, tiny.LIMITS)[0]


@pytest.mark.parametrize("workload", list(tiny.CELLS))
def test_program_passes_and_control_fails(readings, workload):
    r = readings[workload]
    for seed in SEEDS:
        assert not _fails(r["program"][seed]), r["program"][seed]
        assert _fails(r["fp8"][seed]), r["fp8"][seed]


@pytest.mark.parametrize("workload", list(tiny.CELLS))
@pytest.mark.parametrize("fault", ["half_batch", "agg_div_s", "unchanged"])
def test_each_fault_fails(readings, workload, fault):
    for seed, nums in readings[workload][fault].items():
        assert _fails(nums), (seed, nums)


@pytest.mark.parametrize("workload", list(tiny.CELLS))
def test_control_reads_three_times_the_program(readings, workload):
    """The control's smallest reading is at least three times the
    program's largest on some number — what makes a limit between them."""
    r = readings[workload]
    assert any(min(r["fp8"][s][k] for s in SEEDS)
               >= 3 * max(r["program"][s][k] for s in SEEDS)
               for k in check.NUMBERS)


@pytest.mark.parametrize("workload", list(tiny.CELLS))
def test_verdicts_are_judged_against_the_cells_limits(readings, workload):
    """``control.py`` judges every reading as a run does, with the cell's
    own limits: each program seed correct, each control and fault not."""
    import control
    v = readings[workload]["verdicts"]
    assert set(v) == {"program", "program_witness", *control.CONTROLS}
    assert all(v[name] for name in control.CONTROLS)
    assert control.misjudged(v) == []
    flipped = dict(v, fp8={SEEDS[0]: True})
    assert control.misjudged(flipped) == [("fp8", SEEDS[0])]


def test_unchanged_state_reads_one():
    from lib import check as c
    import numpy as np
    w0 = [np.zeros(3, np.float32), np.ones(4, np.float32)]
    moved = [w + 0.5 for w in w0]
    got = c.compare({"losses": [1.0], "servers": {1: w0, 3: w0}},
                    {"losses": [1.0], "live": [1],
                     "servers": {1: moved, 3: moved}}, w0, ["a", "b"])
    assert got["first_change"] == 1.0 and got["change_3"] == 1.0
