"""The round engine names its layers in the compiled program and counts
its own steps (docs/architecture.md, "Profiling a run").

* Every layer scope of ``repro.utils.profiling.SCOPES`` reaches the op
  metadata (``op_name``) of the compiled device-plane superstep: on the
  dense streamed round and on the paged round, ``favas_evict`` and
  ``favas_promote`` on the paged round only.
* The scopes write metadata and nothing else: the superstep compiled with
  ``jax.named_scope`` turned into a no-op is the same program, op for op,
  once the metadata is stripped.
* ``live_steps`` equals the live-step count replayed from the state key
  with the sampler's own draws; ``computed_steps`` is n·R on the dense
  round and s_max·R on the paged round.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import round_engine, sampler
from repro.core.favas import FavasConfig, client_lambdas
from repro.data.device_corpus import make_classification_corpus
from repro.models.classifier import classifier_loss, mlp_apply, mlp_init
from repro.utils.profiling import SCOPES

D_IN, N_CLASSES, N, T = 6, 3, 6, 3
PAGED_ONLY = {"favas_evict", "favas_promote"}
# dense: tree-space quantized progress; paged: codes-in progress and a
# 4-bit cold tier, so that every scope has work to name
ROUNDS = {
    "dense": dict(quant_bits=4),
    "paged": dict(quant_bits=4, quant_fused=True, residency="paged",
                  s_max=4, cold_bits=4),
}


def _corpus(n=N, batch=2):
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (200, D_IN)).astype(np.float32)
    y = rng.integers(0, N_CLASSES, 200).astype(np.int32)
    parts = [rng.choice(200, 30, replace=False) for _ in range(n)]
    return make_classification_corpus(x, y, parts, batch=batch)


def _engine(quant_bits=0, n=N, **kw):
    params = mlp_init(jax.random.PRNGKey(0), D_IN, 8, N_CLASSES)
    fcfg = FavasConfig(n_clients=n, s_selected=2, local_steps=3, eta=0.1,
                       quant_bits=quant_bits)

    def lfn(p, b):
        return classifier_loss(p, mlp_apply, b["x"], b["y"], N_CLASSES)

    eng = round_engine.RoundEngine(
        params, fcfg, lfn, lambdas=jnp.asarray(client_lambdas(fcfg)), **kw)
    return eng, fcfg, eng.init_state(params, jax.random.PRNGKey(7))


def _compiled_text(eng, state, corpus) -> str:
    return (eng._multi_device.lower(state, corpus=corpus, n_rounds=T)
            .compile().as_text())


@pytest.mark.parametrize("kind", sorted(ROUNDS))
def test_every_scope_reaches_the_compiled_metadata(kind):
    eng, _, state = _engine(**ROUNDS[kind])
    text = _compiled_text(eng, state, _corpus())
    paths = "\n".join(re.findall(r'op_name="([^"]*)"', text))
    expected = set(SCOPES) - (set() if kind == "paged" else PAGED_ONLY)
    missing = {s for s in expected if f"/{s}/" not in paths}
    assert not missing, f"scopes missing from the {kind} superstep: {missing}"
    for s in set(SCOPES) - expected:
        assert f"/{s}/" not in paths


_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _program(text: str) -> list:
    """The HLO text without op metadata, debug tables or the module's
    name, with every instruction and computation name replaced by its
    order of first appearance."""
    ids, out, skip = {}, [], False
    for ln in text.splitlines():
        if ln in _DEBUG_TABLES:
            skip = True
        if skip:
            skip = bool(ln.strip())
            continue
        ln = re.sub(r"^HloModule \S+", "HloModule", ln)
        ln = re.sub(r", metadata=\{[^{}]*\}", "", ln)
        ln = re.sub(r"%[\w.\-]+",
                    lambda m: ids.setdefault(m.group(0), f"%{len(ids)}"), ln)
        out.append(ln)
    return out


@pytest.mark.parametrize("kind", sorted(ROUNDS))
def test_scopes_change_only_metadata(kind, monkeypatch):
    eng, _, state = _engine(**ROUNDS[kind])
    corpus = _corpus()
    scoped = _compiled_text(eng, state, corpus)
    assert "favas_" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    eng, _, state = _engine(**ROUNDS[kind])      # a fresh jit cache
    plain = _compiled_text(eng, state, corpus)
    # op_name alone: the debug tables may list kernels/favas_agg.py
    assert "/favas_" not in "\n".join(re.findall(r'op_name="([^"]*)"', plain))
    assert _program(scoped) == _program(plain)


def _replayed_live_steps(key, lambdas, *, n, s, K, n_rounds):
    """Per-round live steps of the device-plane superstep, from the
    sampler's draws alone: the batch key, then the round's four-way split,
    the increments and the selection."""
    q = jnp.zeros((n,), jnp.int32)
    out = []
    for _ in range(n_rounds):
        key, _k_batch = jax.random.split(key)
        key, k_inc, k_sel, _k_q = jax.random.split(key, 4)
        new = jnp.minimum(q + sampler.sample_increments(k_inc, lambdas), K)
        out.append(int(jnp.sum(new - q)))
        _, m = sampler.sample_selection_indices(k_sel, n, s)
        q = jnp.where(m > 0, 0, new)
    return out


@pytest.mark.parametrize("kind,s_max", [("dense", None), ("paged", N)])
def test_live_steps_match_the_replayed_draws(kind, s_max):
    kw = {} if kind == "dense" else dict(residency="paged", s_max=s_max)
    eng, fcfg, state = _engine(**kw)
    key0 = jnp.asarray(np.asarray(state.key))   # the state is donated
    corpus = _corpus()
    live, computed = [], []
    for _ in range(2):
        state, m = eng.run_device(state, corpus, T)
        live += np.asarray(m["live_steps"]).tolist()
        computed += np.asarray(m["computed_steps"]).tolist()
    assert live == _replayed_live_steps(
        key0, eng.lambdas, n=N, s=fcfg.s_selected, K=fcfg.local_steps,
        n_rounds=2 * T)
    assert computed == [N * fcfg.R] * (2 * T)
    assert 0 < sum(live) < sum(computed)


def test_paged_round_counts_only_its_hot_rows():
    eng, fcfg, state = _engine(residency="paged", s_max=3)
    state, m = eng.run_device(state, _corpus(), T)
    computed = np.asarray(m["computed_steps"])
    live = np.asarray(m["live_steps"])
    np.testing.assert_array_equal(computed, 3 * fcfg.R)
    assert np.all((0 <= live) & (live <= computed))


def _host_spans(trace_dir) -> set:
    from jax.profiler import ProfileData
    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    return {e.name for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name.startswith("favas_")}


def test_host_spans_reach_the_trace(tmp_path):
    from repro.data.pipeline import BatchPrefetcher
    eng, _, state = _engine()
    corpus = _corpus()
    state, _ = eng.run_device(state, corpus, T)         # compiles untraced
    prefetch = BatchPrefetcher(lambda i: {"x": np.zeros(2)}, n_steps=1)
    with jax.profiler.trace(str(tmp_path)):
        state, m = eng.run_device(state, corpus, T)
        jax.block_until_ready(m)
        prefetch.get()
    prefetch.close()
    assert {"favas_run_device", "favas_prefetch_wait"} <= _host_spans(
        tmp_path)
