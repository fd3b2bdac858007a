"""Streamed rounds + the host-offloaded cold tier (docs/architecture.md §13).

What is proven:

* **kernel parity** — the streamed round (single-sweep ``favas_stream``
  aggregation + the selected rows' reset; unselected rows never
  rewritten) on the Pallas kernel (interpret mode) is BIT-EXACT against
  the oracle path for the dense engine (quant_bits {0,4} x quant_fused x
  {fp32, bf16}, masked and scatter resets) and the paged engine
  (cold_bits {0,4}), states, metrics, cold-pool bytes and RNG key chain
  included, at these small shapes on the CPU. The reset itself writes
  exactly what a scatter of the new server row writes, on either path.
* **placement parity** — ``cold_placement="host"`` (LUQ cold pools in host
  numpy via ``HostColdPool``, rounds fed from a device-resident slab) is
  BIT-EXACT against device placement across cold_bits {0,4} x
  s_max {churn, ==n} on both data planes, sequential steps and supersteps,
  plus the forced-8-device mesh leg; checkpoints of host pools round-trip.
* **overlap correctness** — ``engine_run_stream`` (double-buffered
  :class:`~repro.core.streaming.PageStreamer`) equals sequential chunk
  dispatch exactly: the producer's writeback gate (chunk j waits on
  writebacks through j-2) plus the on-device ``_patch_slab`` read-after-
  write repair make prefetch invisible to the math. The streamer keeps the
  BatchPrefetcher contract: strict order, errors surface in stream
  position, hardened close.
* **write-traffic regression gate** — the compiled streamed round emits
  ZERO full (rows, D) client/init rewrites where its stacks take the
  scatter reset (``roofline.pass_through_copies`` over the ENTRY root; a
  hand-written ``jnp.where`` blend of an (n, D) stack is the positive
  control), also at n=1024, D=2^20 (AOT-compiled, never executed), and
  exactly the two masked reset passes and no per-row loop where they are
  small (``round_engine.reset_path``, itself pinned bit-exact to the
  scatter).
* **tier accounting** — ``engine_resident_bytes_by_tier`` splits device vs
  host bytes: host pools never count against the device budget.
"""
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpointing import load_engine_checkpoint, save_engine_checkpoint
from repro.core import round_engine
from repro.core.round_engine import engine_resident_bytes, \
    engine_resident_bytes_by_tier
from repro.core.favas import FavasConfig, client_lambdas
from repro.core.streaming import HostColdPool, PageStreamer, engine_run_stream
from repro.data.device_corpus import make_classification_corpus
from repro.launch.mesh import make_model_mesh
from repro.launch.roofline import pass_through_copies

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# --------------------------------------------------------------------------
# helpers (the test_paged_engine fixtures, kept local: test modules here are
# self-contained by convention)
# --------------------------------------------------------------------------

def _params(dtype=jnp.float32):
    w = jnp.asarray(np.linspace(-1.0, 1.0, 48).reshape(8, 6), dtype)
    b = jnp.asarray(np.linspace(0.5, 1.5, 5), jnp.float32)
    return {"w": w, "b": b}


def _loss(p, batch):
    return sum(jnp.mean((l.astype(jnp.float32) - batch["t"]) ** 2)
               for l in jax.tree_util.tree_leaves(p))


def _batches(fcfg, T, seed=0):
    vals = np.linspace(0.0, 1.0, T * fcfg.n_clients * fcfg.R) + 0.01 * seed
    return {"t": jnp.asarray(vals.reshape(T, fcfg.n_clients, fcfg.R),
                             jnp.float32)}


def _engine(dtype, quant_bits=0, n=5, **kw):
    params = _params(dtype)
    fcfg = FavasConfig(n_clients=n, s_selected=2, local_steps=2, eta=0.1,
                       quant_bits=quant_bits)
    eng = round_engine.RoundEngine(
        params, fcfg, _loss, lambdas=jnp.asarray(client_lambdas(fcfg)), **kw)
    return eng, fcfg, params


def _assert_states_equal(a, b):
    for x, y in zip(a.server + a.clients + a.inits,
                    b.server + b.clients + b.inits):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    np.testing.assert_array_equal(np.asarray(a.counters), np.asarray(b.counters))
    np.testing.assert_array_equal(np.asarray(a.stale), np.asarray(b.stale))
    np.testing.assert_array_equal(np.asarray(a.key), np.asarray(b.key))
    assert int(a.t) == int(b.t)


def _assert_metrics_equal(ma, mb):
    assert set(ma) == set(mb)
    for k in ma:
        np.testing.assert_array_equal(np.asarray(ma[k]), np.asarray(mb[k]),
                                      err_msg=k)


def _cold_bytes(cold):
    """Raveled uint8 view of every cold leaf (bf16 via f32) for exact
    byte-level pool comparison across placements."""
    out = []
    for l in jax.tree_util.tree_leaves(cold):
        a = np.asarray(l, np.float32) if np.asarray(l).dtype.name == "bfloat16" \
            else np.asarray(l)
        out.append(a.ravel().view(np.uint8))
    return np.concatenate(out)


# --------------------------------------------------------------------------
# kernel parity: the streamed round on the kernel == on the oracle
# --------------------------------------------------------------------------

_QUANT = [(0, False, "plain"), (4, False, "quant4"), (4, True, "quant4_fused")]
_DTYPES = [(jnp.float32, "fp32"), (jnp.bfloat16, "bf16")]


# n=7 resets every stack with the masked pass, n=64 with the row scatter
@pytest.mark.parametrize("dtype,qb,qf,n", [
    pytest.param(dt, qb, qf, n, id=f"{qid}-{did}{sfx}")
    for n, sfx in [(7, ""), (64, "-n64")]
    for qb, qf, qid in _QUANT for dt, did in _DTYPES])
def test_dense_streamed_kernel_bit_exact_vs_oracle(dtype, qb, qf, n):
    T = 5
    e1, fcfg, params = _engine(dtype, quant_bits=qb, n=n, quant_fused=qf,
                               use_kernel=True)
    e2, _, _ = _engine(dtype, quant_bits=qb, n=n, quant_fused=qf,
                       use_kernel=False)
    path = "masked" if n == 7 else "scatter"
    assert e1.reset_paths == e2.reset_paths == (path,) * e1.spec.n_buckets
    key = jax.random.PRNGKey(3)
    s1, m1 = e1.run(e1.init_state(params, key), _batches(fcfg, T))
    s2, m2 = e2.run(e2.init_state(params, key), _batches(fcfg, T))
    _assert_states_equal(s1, s2)
    _assert_metrics_equal(m1, m2)


@pytest.mark.parametrize("cold_bits", [0, 4])
def test_paged_streamed_kernel_bit_exact_vs_oracle(cold_bits):
    """s_max < n: real churn every round; hot stacks, cold-pool BYTES and
    the full metric set agree between the kernel path (aggregation and
    cold-pool codec) and the oracle path."""
    T = 6
    e1, fcfg, params = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                               cold_bits=cold_bits, use_kernel=True)
    e2, _, _ = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                       cold_bits=cold_bits, use_kernel=False)
    key = jax.random.PRNGKey(7)
    s1, m1 = e1.run(e1.init_state(params, key), _batches(fcfg, T))
    s2, m2 = e2.run(e2.init_state(params, key), _batches(fcfg, T))
    _assert_states_equal(s1, s2)
    _assert_metrics_equal(m1, m2)
    np.testing.assert_array_equal(_cold_bytes(s1.cold), _cold_bytes(s2.cold))


@pytest.mark.parametrize("rows,s,dtype,sel,path", [
    (8, 2, jnp.float32, [5, 2], "masked"),
    (16, 2, jnp.bfloat16, [0, 15], "masked"),
    (64, 2, jnp.float32, [63, 17], "scatter"),
    (64, 4, jnp.float32, [3, 40, 9, 62], "scatter"),
    (64, 2, jnp.bfloat16, [31, 32], "scatter"),
    # the paged round pads its nonzero() positions with row 0
    (8, 3, jnp.float32, [6, 0, 0], "masked"),
], ids=["f32-8-masked", "bf16-16-masked", "f32-64-s2-scatter",
        "f32-64-s4-scatter", "bf16-64-scatter", "f32-8-duplicate"])
def test_streamed_reset_matches_scatter(rows, s, dtype, sel, path):
    """The reset writes exactly what ``buf.at[sel_idx].set(row)`` writes,
    bit for bit, on whichever path ``reset_path`` picks for the stack."""
    assert round_engine.reset_path(rows, s, dtype) == path
    D = 384
    rng = np.random.default_rng(rows * 10 + s)
    buf = jnp.asarray(rng.standard_normal((rows, D)), dtype)
    inits = jnp.asarray(rng.standard_normal((rows, D)), dtype)
    row = jnp.asarray(rng.standard_normal(D), jnp.float32)
    sel_idx = jnp.asarray(sel, jnp.int32)
    out = jax.jit(lambda b, i, r, x: round_engine._streamed_reset(
        None, None, (b, i), x, (r, r)))(buf, inits, row, sel_idx)
    for got, x in zip(out, (buf, inits)):
        want = x.at[sel_idx].set(row.astype(dtype))
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("kw", [{}, {"residency": "paged", "s_max": 4}],
                         ids=["dense", "paged"])
def test_reset_paths_cover_every_bucket(kw):
    """``RoundEngine.reset_paths`` names the reset path of every bucket —
    one per dtype of a mixed-precision tree — as ``reset_path`` picks it
    for the stacks the round writes."""
    params = {"w": _params()["w"], "h": _params(jnp.bfloat16)["w"],
              "b": _params()["b"]}
    fcfg = FavasConfig(n_clients=9, s_selected=2, local_steps=2, eta=0.1)
    e = round_engine.RoundEngine(
        params, fcfg, _loss, lambdas=jnp.asarray(client_lambdas(fcfg)), **kw)
    assert e.spec.n_buckets == 2
    assert e.reset_paths == tuple(
        round_engine.reset_path(e.spec.stacked_rows, 2, dt)
        for dt in e.spec.bucket_dtypes)
    assert set(e.reset_paths) <= {"masked", "scatter"}


# --------------------------------------------------------------------------
# placement parity: host cold tier == device cold tier, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cold_bits", [0, 4])
@pytest.mark.parametrize("s_max", [4, 9], ids=["churn", "smax_eq_n"])
def test_host_placement_bit_exact_vs_device(cold_bits, s_max):
    T = 6
    ed, fcfg, params = _engine(jnp.float32, n=9, residency="paged",
                               s_max=s_max, cold_bits=cold_bits)
    eh, _, _ = _engine(jnp.float32, n=9, residency="paged", s_max=s_max,
                       cold_bits=cold_bits, cold_placement="host")
    key = jax.random.PRNGKey(11)
    sd = ed.init_state(params, key)
    sh = eh.init_state(params, key)
    assert isinstance(sh.cold, HostColdPool)
    # tier accounting: host pools never count against the device budget
    bt_h, bt_d = engine_resident_bytes_by_tier(sh), \
        engine_resident_bytes_by_tier(sd)
    assert bt_h["host"] > 0 and bt_d["host"] == 0
    assert engine_resident_bytes(sh) == bt_h["device"]
    assert bt_h["device"] + bt_h["host"] == bt_d["device"] + bt_d["host"]
    assert bt_h["device"] < bt_d["device"]
    batches = _batches(fcfg, T)
    sd, md = ed.run(sd, batches)
    sh, mh = eh.run(sh, batches)
    _assert_states_equal(sd, sh)
    _assert_metrics_equal(md, mh)
    np.testing.assert_array_equal(_cold_bytes(sd.cold), _cold_bytes(sh.cold))


def test_host_placement_sequential_steps():
    T = 5
    ed, fcfg, params = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                               cold_bits=4)
    eh, _, _ = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                       cold_bits=4, cold_placement="host")
    key = jax.random.PRNGKey(13)
    sd = ed.init_state(params, key)
    sh = eh.init_state(params, key)
    batches = _batches(fcfg, T)
    for t in range(T):
        b = jax.tree_util.tree_map(lambda x: x[t], batches)
        sd, md = ed.step(sd, b)
        sh, mh = eh.step(sh, b)
        _assert_metrics_equal(md, mh)
    _assert_states_equal(sd, sh)


def test_host_placement_requires_paged():
    with pytest.raises(ValueError, match="host"):
        _engine(jnp.float32, n=5, cold_placement="host")


def test_checkpoint_roundtrip_host_pool(tmp_path):
    """Host pools ride the pytree protocol through save/load; the restored
    state is bit-equal AND continues bit-exactly."""
    eh, fcfg, params = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                               cold_bits=4, cold_placement="host")
    sh = eh.init_state(params, jax.random.PRNGKey(5))
    sh, _ = eh.run(sh, _batches(fcfg, 4))
    p = save_engine_checkpoint(str(tmp_path), 4, sh)
    eh2, _, _ = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                        cold_bits=4, cold_placement="host")
    tmpl = eh2.init_state(params, jax.random.PRNGKey(0))
    restored = load_engine_checkpoint(p, tmpl)
    _assert_states_equal(sh, restored)
    assert isinstance(restored.cold, HostColdPool)
    np.testing.assert_array_equal(_cold_bytes(sh.cold),
                                  _cold_bytes(restored.cold))
    sh2, _ = eh.run(sh, _batches(fcfg, 3, seed=1))
    sh3, _ = eh2.run(restored, _batches(fcfg, 3, seed=1))
    _assert_states_equal(sh2, sh3)


# --------------------------------------------------------------------------
# the page streamer: overlap == sequential, on both data planes
# --------------------------------------------------------------------------

def test_run_stream_matches_sequential_chunks():
    n_chunks, T = 4, 3
    e1, fcfg, params = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                               cold_bits=4, cold_placement="host")
    e2, _, _ = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                       cold_bits=4, cold_placement="host")
    key = jax.random.PRNGKey(17)
    s1 = e1.init_state(params, key)
    s2 = e2.init_state(params, key)
    chunk_batches = [_batches(fcfg, T, seed=i) for i in range(n_chunks)]
    s1, m1 = engine_run_stream(e1, s1, n_chunks=n_chunks, chunk_rounds=T,
                               chunk_batches=chunk_batches)
    ms = []
    for cb in chunk_batches:
        s2, m = e2.run(s2, cb)
        ms.append(m)
    _assert_states_equal(s1, s2)
    np.testing.assert_array_equal(_cold_bytes(s1.cold), _cold_bytes(s2.cold))
    m2 = jax.tree_util.tree_map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *ms)
    _assert_metrics_equal(m1, m2)


def test_run_stream_zero_churn_smax_eq_n():
    """s_max == n: every chunk's churn plan is empty, the slab is the
    all-dummy row, and the streamer still matches a device-placed engine
    (dense-passthrough leg of the §13 matrix)."""
    e1, fcfg, params = _engine(jnp.float32, n=7, residency="paged",
                               cold_placement="host")       # s_max -> n
    key = jax.random.PRNGKey(9)
    s1 = e1.init_state(params, key)
    cbs = [_batches(fcfg, 2, seed=i) for i in range(3)]
    s1, _ = engine_run_stream(e1, s1, n_chunks=3, chunk_rounds=2,
                              chunk_batches=cbs)
    e2, _, _ = _engine(jnp.float32, n=7, residency="paged")
    s2 = e2.init_state(params, key)
    for cb in cbs:
        s2, _ = e2.run(s2, cb)
    _assert_states_equal(s1, s2)


def _corpus(n):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = rng.integers(0, 3, size=(64,)).astype(np.int64)
    parts = [np.arange(i, 64, n) for i in range(n)]
    return make_classification_corpus(x, y, parts, batch=2)


def _corpus_loss(p, batch):
    out = batch["x"] @ p["w"][:6, :5].astype(jnp.float32)
    return jnp.mean((out - batch["y"][:, None]) ** 2)


def test_run_stream_device_plane():
    """Device data plane: host placement == device placement under
    ``run_device``, and ``engine_run_stream(corpus=...)`` == sequential
    ``run_device`` chunks."""
    n, T = 9, 6
    params = _params()
    fcfg = FavasConfig(n_clients=n, s_selected=2, local_steps=2, eta=0.1)
    corpus = _corpus(n)

    def mk(**kw):
        return round_engine.RoundEngine(
            params, fcfg, _corpus_loss,
            lambdas=jnp.asarray(client_lambdas(fcfg)),
            residency="paged", s_max=4, cold_bits=4, **kw)

    ed, eh = mk(), mk(cold_placement="host")
    key = jax.random.PRNGKey(3)
    sd = ed.init_state(params, key)
    sh = eh.init_state(params, key)
    sd, md = ed.run_device(sd, corpus, T)
    sh, mh = eh.run_device(sh, corpus, T)
    _assert_states_equal(sd, sh)
    _assert_metrics_equal(md, mh)

    e3, e4 = mk(cold_placement="host"), mk(cold_placement="host")
    s3 = e3.init_state(params, key)
    s4 = e4.init_state(params, key)
    s3, _ = engine_run_stream(e3, s3, n_chunks=3, chunk_rounds=2,
                              corpus=corpus)
    for _ in range(3):
        s4, _ = e4.run_device(s4, corpus, 2)
    _assert_states_equal(s3, s4)


def test_run_stream_validates_planes():
    eh, fcfg, params = _engine(jnp.float32, n=9, residency="paged", s_max=4,
                               cold_placement="host")
    sh = eh.init_state(params, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="exactly one"):
        engine_run_stream(eh, sh, n_chunks=2, chunk_rounds=2)
    ed, _, _ = _engine(jnp.float32, n=9, residency="paged", s_max=4)
    sd = ed.init_state(params, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="host"):
        engine_run_stream(ed, sd, n_chunks=2, chunk_rounds=2,
                          chunk_batches=[_batches(fcfg, 2)] * 2)


# --------------------------------------------------------------------------
# PageStreamer contract (the BatchPrefetcher contract + the writeback gate)
# --------------------------------------------------------------------------

def test_page_streamer_strict_order():
    """Chunks arrive in index order; the consumer acknowledges each chunk
    with mark_written (the gate contract — a consumer that never writes
    back would starve the producer at chunk 2, by design)."""
    with PageStreamer(lambda i: i * i, n_chunks=5, depth=2) as ps:
        out = []
        for i, v in enumerate(ps):
            out.append(v)
            ps.mark_written(i)
        assert out == [0, 1, 4, 9, 16]


def test_page_streamer_error_in_stream_position():
    """Chunk 2 raises in the producer: chunks 0 and 1 still arrive, the
    error surfaces exactly at get() #2, and close() stays clean."""
    def make(i):
        if i == 2:
            raise RuntimeError("boom at 2")
        return i

    with PageStreamer(make, n_chunks=5, depth=2) as ps:
        assert ps.get() == 0
        ps.mark_written(0)
        assert ps.get() == 1
        ps.mark_written(1)
        with pytest.raises(RuntimeError, match="boom at 2"):
            ps.get()


def test_page_streamer_close_unblocks_producer():
    """close() while the producer is parked on the writeback gate must not
    hang: the gate polls the stop flag (hardened-close contract)."""
    started = threading.Event()

    def make(i):
        started.set()
        return i

    ps = PageStreamer(make, n_chunks=10, depth=2)
    assert started.wait(5.0)
    assert ps.get() == 0
    t0 = time.monotonic()
    ps.close(timeout=10.0)
    assert time.monotonic() - t0 < 10.0


def test_page_streamer_writeback_gate():
    """The producer may run at most 2 chunks ahead of the consumer's
    writebacks: chunk j is not MADE until mark_written(j-2) — the overlap-
    correctness invariant (the slab for chunk j is gathered from pool
    state that already includes chunk j-2's writeback; the j-1 overlap is
    repaired on device by _patch_slab)."""
    made = []
    lock = threading.Lock()

    def make(i):
        with lock:
            made.append(i)
        return i

    with PageStreamer(make, n_chunks=6, depth=4) as ps:
        # without any writebacks the producer may build chunks 0 and 1
        # (gate: wb >= i - 2 with wb starting at -1) but never chunk 2
        assert ps.get() == 0
        assert ps.get() == 1
        time.sleep(0.4)
        with lock:
            assert made == [0, 1], made
        ps.mark_written(0)
        time.sleep(0.4)
        with lock:
            assert made == [0, 1, 2], made
        for i in range(1, 5):
            ps.mark_written(i)
        assert [ps.get() for _ in range(4)] == [2, 3, 4, 5]


# --------------------------------------------------------------------------
# write-traffic regression gates (roofline audits, §13 acceptance)
# --------------------------------------------------------------------------

def test_streamed_round_no_pass_through_rewrites():
    """The compiled streamed round's entry outputs contain ZERO full
    (n, D) client/init rewrites — every touched output is an in-place
    scatter/DUS on the donated buffer. The positive control: a
    hand-written ``jnp.where`` blend over an (n, D) stack, compiled alone,
    is flagged (one output, one full rewrite)."""
    n = 64
    w = jnp.asarray(np.linspace(-1, 1, 48 * 40).reshape(48, 40), jnp.float32)
    params = {"w": w}
    fcfg = FavasConfig(n_clients=n, s_selected=4, local_steps=2, eta=0.1)
    batch = {"t": jnp.zeros((n, fcfg.R), jnp.float32)}
    eng = round_engine.RoundEngine(
        params, fcfg, _loss, lambdas=jnp.asarray(client_lambdas(fcfg)))
    st = eng.init_state(params, jax.random.PRNGKey(0))
    text = eng._round.lower(st, batch).compile().as_text()
    assert pass_through_copies(text, rows=n, min_cols=1024) == []

    D = eng.spec.bucket_padded[0]
    blend = jax.jit(lambda x, m, row: jnp.where(m[:, None] > 0, row[None], x),
                    donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((n, D), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32)).compile()
    flagged = pass_through_copies(blend.as_text(), rows=n, min_cols=1024)
    assert len(flagged) == 1, flagged


def test_streamed_round_small_stack_masked_reset():
    """At n=8, s=2 each stack is reset by one masked pass, not by XLA's
    per-row loop: the compiled streamed round holds no while loop,
    scatter or dynamic-update-slice over an (8, Dp) stack, and the
    pass-through audit flags exactly the two masked passes (clients +
    inits)."""
    n = 8
    w = jnp.asarray(np.linspace(-1, 1, 48 * 40).reshape(48, 40), jnp.float32)
    params = {"w": w}
    fcfg = FavasConfig(n_clients=n, s_selected=2, local_steps=2, eta=0.1)
    eng = round_engine.RoundEngine(
        params, fcfg, _loss, lambdas=jnp.asarray(client_lambdas(fcfg)))
    assert eng.reset_paths == ("masked",)
    st = eng.init_state(params, jax.random.PRNGKey(0))
    text = eng._round.lower(
        st, {"t": jnp.zeros((n, fcfg.R), jnp.float32)}).compile().as_text()
    stack = f"[{n},{eng.spec.bucket_padded[0]}]"
    op_re = re.compile(r"=\s*(.*?)\s(while|scatter|dynamic-update-slice)\(")
    writes = [m.group(2) for m in map(op_re.search, text.splitlines())
              if m and stack in m.group(1)]
    assert writes == [], writes
    flagged = pass_through_copies(text, rows=n, min_cols=1024)
    assert len(flagged) == 2, flagged


def test_streamed_round_no_rewrites_at_scale():
    """Write audit at the §13 acceptance shape (n=1024, D=2^20,
    AOT-compiled only — never executed): the aggregation plus a scatter
    of the new server row into the s selected rows of the donated stacks
    emits no pass-through rewrite of either (n, D) stack."""
    from repro.kernels.ops import favas_stream_flat
    n, D, s = 1024, 2 ** 20, 4
    srv = jax.ShapeDtypeStruct((D,), jnp.float32)
    stack = jax.ShapeDtypeStruct((n, D), jnp.float32)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    idx = jax.ShapeDtypeStruct((s,), jnp.int32)

    def streamed(server, clients, inits, alpha, mask, sel_idx):
        s_new = favas_stream_flat(server, clients, inits, alpha, mask, s,
                                  use_kernel=False)
        return (s_new, clients.at[sel_idx].set(s_new.astype(clients.dtype)),
                inits.at[sel_idx].set(s_new.astype(inits.dtype)))

    text = jax.jit(streamed, donate_argnums=(1, 2)).lower(
        srv, stack, stack, vec, vec, idx).compile().as_text()
    assert pass_through_copies(text, rows=n, min_cols=1024) == []


# --------------------------------------------------------------------------
# forced-8-device mesh leg (the CI ``streaming`` job runs under the flag;
# the slow subprocess self-run covers plain environments)
# --------------------------------------------------------------------------

def _mesh_params():
    def f(*shape, seed=0):
        size = int(np.prod(shape))
        v = np.linspace(-1.0, 1.0, size).reshape(shape) * (1.0 + 0.1 * seed)
        return jnp.asarray(v, jnp.float32)
    return {"embed": {"table": f(16, 6, seed=1)},
            "blk": {"wq": {"w": f(6, 16, seed=2), "b": f(16, seed=3)}},
            "mlp": {"down": {"w": f(16, 5, seed=6)}}}


@needs8
@pytest.mark.parametrize("cold_bits", [0, 4])
def test_mesh_host_placement_bit_exact(cold_bits):
    """8-device mesh: host cold placement == device placement (the slab is
    device_put with the cold codec's per-bucket shardings), and the
    streamer matches sequential chunks on the mesh."""
    mesh = make_model_mesh(8)
    n, T = 9, 6
    params = _mesh_params()
    fcfg = FavasConfig(n_clients=n, s_selected=2, local_steps=2, eta=0.1)

    def mk(**kw):
        return round_engine.RoundEngine(
            params, fcfg, _loss, lambdas=jnp.asarray(client_lambdas(fcfg)),
            mesh=mesh, residency="paged", s_max=4, cold_bits=cold_bits, **kw)

    ed, eh = mk(), mk(cold_placement="host")
    key = jax.random.PRNGKey(3)
    sd = ed.init_state(params, key)
    sh = eh.init_state(params, key)
    assert isinstance(sh.cold, HostColdPool)
    batches = _batches(fcfg, T)
    sd, md = ed.run(sd, batches)
    sh, mh = eh.run(sh, batches)
    _assert_states_equal(sd, sh)
    _assert_metrics_equal(md, mh)
    np.testing.assert_array_equal(_cold_bytes(sd.cold), _cold_bytes(sh.cold))

    e1, e2 = mk(cold_placement="host"), mk(cold_placement="host")
    s1 = e1.init_state(params, key)
    s2 = e2.init_state(params, key)
    cbs = [_batches(fcfg, 2, seed=i) for i in range(3)]
    s1, _ = engine_run_stream(e1, s1, n_chunks=3, chunk_rounds=2,
                              chunk_batches=cbs)
    for cb in cbs:
        s2, _ = e2.run(s2, cb)
    _assert_states_equal(s1, s2)
    np.testing.assert_array_equal(_cold_bytes(s1.cold), _cold_bytes(s2.cold))


@pytest.mark.slow
def test_streaming_subprocess_8dev():
    """Self-run this file under the forced-8-device flag so plain
    environments still exercise the mesh leg (the CI ``streaming`` job
    runs the same command directly)."""
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
         "tests/test_streaming.py", "-k", "mesh"],
        capture_output=True, text=True, env=env, cwd=root, timeout=1200)
    assert out.returncode == 0, out.stdout + out.stderr
