"""A run with the timed path broken underneath comes out not correct.

Each test runs the harness end to end on the CPU over the tiny copy of the
benchmark (``lib/tiny.py``: the look for a chip is skipped, nothing else
is), with one fault a one-chip training cell can have planted in the
program, and sees ``correct`` false. The unbroken run beside them comes
out correct under the same limits, so the faults, not the limits, decide.
The exchange between chips is not among them: no cell here has one."""
import time

import jax
import jax.numpy as jnp
import pytest

from lib import harness, tiny

CELLS = list(tiny.CELLS)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"), limits=tiny.LIMITS)


def _run(bench, workload):
    return harness.run(workload, 2 ** 31 + 29, 0.5, False,
                       t_start=time.perf_counter(), bench=bench,
                       allow_cpu=True)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(bench, workload):
    r = _run(bench, workload)
    assert r["correct"], r["compared"]


@pytest.mark.parametrize("workload", CELLS)
def test_state_returned_unchanged(bench, workload, monkeypatch):
    from repro.core.round_engine import RoundEngine
    orig = RoundEngine.run_device

    def unchanged(self, state, corpus, n_rounds):
        copy = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = orig(self, copy, corpus, n_rounds)
        return state, metrics
    monkeypatch.setattr(RoundEngine, "run_device", unchanged)
    assert not _run(bench, workload)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out(bench, workload, monkeypatch):
    from repro.models import model
    orig = model.loss_fn

    def half(params, cfg, batch):
        toks = batch["tokens"]
        return orig(params, cfg, {"tokens": toks[:, :toks.shape[1] // 2]})
    monkeypatch.setattr(model, "loss_fn", half)
    assert not _run(bench, workload)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_aggregated_answer_altered(bench, workload, monkeypatch):
    """The new server row divided by s instead of s + 1 where the
    aggregation produces it."""
    from repro.kernels import ref
    orig = ref.favas_stream_ref

    def altered(server, clients, inits, alpha, mask, s, *, progress=None):
        out = orig(server, clients, inits, alpha, mask, s, progress=progress)
        return (out * ((s + 1.0) / s)).astype(out.dtype)
    monkeypatch.setattr(ref, "favas_stream_ref", altered)
    assert not _run(bench, workload)["correct"]
