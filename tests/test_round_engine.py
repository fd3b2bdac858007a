"""Flat-buffer round engine tests:

* parity of the streamed round step (``round_engine.aggregate_and_reset``
  on the ``favas_stream`` Pallas kernel, interpret mode) against the
  whole-round jnp oracle ``favas_fused_ref`` — server, clients and inits,
  to 1 fp32 ULP across odd D (padding path), n in {1, 4, 64}, and bf16
  params — and of the kernel's server row against ``favas_stream_ref``;
* flatten/unflatten round-trips (mixed-dtype buckets included);
* regression: ``favas_round`` on the engine reproduces the seed's per-leaf
  tree_map implementation (``favas_round_reference``) exactly.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (FavasConfig, favas_init, favas_round,
                        favas_round_reference, client_lambdas)
from repro.core import round_engine
from repro.kernels import ref
from repro.kernels.favas_agg import favas_stream_pallas
from repro.models.model import init_params, loss_fn
from repro.utils.tree import tree_map, tree_sq_dist


def _fused_inputs(n, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    server = jax.random.normal(ks[0], (D,), dtype)
    clients = jax.random.normal(ks[1], (n, D), dtype)
    inits = jax.random.normal(ks[2], (n, D), dtype)
    alpha = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=8.0)
    mask = (jax.random.uniform(ks[4], (n,)) > 0.5).astype(jnp.float32)
    return server, clients, inits, alpha, mask, float(mask.sum())


def _stream_step(server, clients, inits, alpha, mask, s, *, progress=None,
                 use_kernel=True):
    """The engine's round step on one bucket of raw flat buffers: the
    selection indices are the rows ``mask`` selects."""
    n = clients.shape[0]
    spec = round_engine.make_flat_spec({"w": server}, n_clients=n)
    sel_idx = jnp.asarray(np.flatnonzero(np.asarray(mask)), jnp.int32)
    (srv,), (cli,), (ini,) = round_engine.aggregate_and_reset(
        spec, (server,), (clients,), (inits,), alpha, mask, sel_idx, s,
        progress=None if progress is None else (progress,), n_logical=n,
        use_kernel=use_kernel)
    return srv, cli, ini


@pytest.mark.parametrize("n", [1, 4, 64])
@pytest.mark.parametrize("D", [17, 1000, 2048, 4097])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_step_matches_oracle(n, D, dtype):
    args = _fused_inputs(n, D, dtype, seed=n * 1000 + D)
    got = _stream_step(*args)
    want = ref.favas_fused_ref(*args)
    # the kernel body and the oracle are the same jnp expressions, but
    # XLA compiles them separately (FMA contraction, blocked n-row
    # reductions), so "bit-for-bit" holds only up to 1 fp32 ULP
    tol = dict(rtol=2e-7, atol=2e-7) if dtype == jnp.float32 else \
        dict(rtol=8e-3, atol=8e-3)
    for name, g, w in zip(("server", "clients", "inits"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), err_msg=name,
                                   **tol)
    # the kernel alone against its own oracle
    srv = favas_stream_pallas(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(srv, np.float32),
                               np.asarray(ref.favas_stream_ref(*args),
                                          np.float32), **tol)


def test_stream_step_zero_selection():
    """s = 0 (no client selected): server' = server / 1, clients untouched."""
    n, D = 4, 300
    server, clients, inits, alpha, _, _ = _fused_inputs(n, D, jnp.float32, 3)
    mask = jnp.zeros((n,), jnp.float32)
    srv, cli, ini = _stream_step(server, clients, inits, alpha, mask, 0.0)
    np.testing.assert_array_equal(np.asarray(srv), np.asarray(server))
    np.testing.assert_array_equal(np.asarray(cli), np.asarray(clients))
    np.testing.assert_array_equal(np.asarray(ini), np.asarray(inits))


def test_flat_spec_roundtrip_mixed_dtypes():
    tree = {
        "w": jnp.arange(7 * 5, dtype=jnp.float32).reshape(7, 5),
        "b": jnp.ones((13,), jnp.bfloat16),
        "scale": jnp.full((3, 2, 2), 2.5, jnp.float32),
    }
    spec = round_engine.make_flat_spec(tree)
    assert spec.n_buckets == 2
    assert all(p % round_engine.TILE == 0 for p in spec.bucket_padded)
    bufs = round_engine.flatten_tree(spec, tree)
    back = round_engine.unflatten_tree(spec, bufs)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # stacked round-trip
    n = 3
    stacked = tree_map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)
    sbufs = round_engine.flatten_stacked(spec, stacked)
    sback = round_engine.unflatten_stacked(spec, sbufs)
    for a, b in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(sback)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_flat_spec_sharded_layout_roundtrip():
    """The shard-major (dtype, sharding group) bucket layout is pure
    metadata — flatten/unflatten must be bit-exact with explicit shard axes
    and no mesh, including the stacked (client-padded) path."""
    tree = {
        "wq": {"w": jnp.arange(6 * 16, dtype=jnp.float32).reshape(6, 16)},
        "wo": {"w": jnp.arange(16 * 5, dtype=jnp.float32).reshape(16, 5) * .5},
        "scale": jnp.arange(7, dtype=jnp.float32),
        "bf": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
    }
    # leaves (sorted keys): bf, scale, wo/w (row-sharded), wq/w (col-sharded)
    spec = round_engine.make_flat_spec(tree, tile=8, n_clients=5,
                                       client_tile=4, shard_axes=[None, None, 0, 1],
                                       model_shards=4)
    assert spec.bucket_shards == (1, 1, 4)
    assert all(p == spec.shards(b) * spec.bucket_shard_padded[b]
               for b, p in enumerate(spec.bucket_padded))
    back = round_engine.unflatten_tree(spec, round_engine.flatten_tree(spec, tree))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    n = 5
    stacked = tree_map(lambda x: jnp.stack([x * (i + 1) for i in range(n)]), tree)
    sback = round_engine.unflatten_stacked(
        spec, round_engine.flatten_stacked(spec, stacked))
    for a, b in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(sback)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _setup(n=4, s=2, K=4, **fkw):
    cfg = get_reduced_config("qwen3-4b")
    fcfg = FavasConfig(n_clients=n, s_selected=s, local_steps=K, eta=0.05,
                       seed=0, **fkw)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    state = favas_init(params, fcfg, key)
    lambdas = jnp.asarray(client_lambdas(fcfg))

    def lfn(p, b):
        return loss_fn(p, cfg, b)
    return cfg, fcfg, state, lfn, lambdas


def test_favas_round_matches_reference_impl():
    """The engine-backed favas_round must reproduce the seed's per-leaf
    tree_map implementation: same PRNG stream, same arithmetic — the server
    update (and the client/init resets) agree exactly in fp32."""
    cfg, fcfg, state, lfn, lambdas = _setup()
    step_new = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                         lambdas=lambdas))
    step_ref = jax.jit(functools.partial(favas_round_reference, cfg=fcfg,
                                         loss_fn=lfn, lambdas=lambdas))
    rng = np.random.default_rng(0)
    s_new, s_ref = state, state
    for _ in range(3):
        toks = rng.integers(0, cfg.vocab_size_raw,
                            (fcfg.n_clients, fcfg.R, 2, 32)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks)}
        s_new, m_new = step_new(s_new, batch)
        s_ref, m_ref = step_ref(s_ref, batch)
        assert float(tree_sq_dist(s_new.server, s_ref.server)) == 0.0
        assert float(tree_sq_dist(s_new.clients, s_ref.clients)) == 0.0
        assert float(tree_sq_dist(s_new.inits, s_ref.inits)) == 0.0
        np.testing.assert_array_equal(np.asarray(s_new.counters),
                                      np.asarray(s_ref.counters))
        assert float(m_new["loss"]) == float(m_ref["loss"])
        assert float(m_new["stale_rounds"]) == float(m_ref["stale_rounds"])


def test_favas_round_matches_reference_impl_quantized():
    """FAVAS[QNN]: quantization is communication-only — the engine must
    quantize the transmitted progress with the seed's per-leaf keys/scales
    while unselected clients keep full-precision local state, reproducing
    the reference exactly."""
    cfg, fcfg, state, lfn, lambdas = _setup(K=2, quant_bits=4)
    step_new = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                         lambdas=lambdas))
    step_ref = jax.jit(functools.partial(favas_round_reference, cfg=fcfg,
                                         loss_fn=lfn, lambdas=lambdas))
    rng = np.random.default_rng(5)
    s_new, s_ref = state, state
    for _ in range(2):
        toks = rng.integers(0, cfg.vocab_size_raw,
                            (fcfg.n_clients, fcfg.R, 2, 16)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks)}
        s_new, _ = step_new(s_new, batch)
        s_ref, _ = step_ref(s_ref, batch)
        assert float(tree_sq_dist(s_new.server, s_ref.server)) == 0.0
        assert float(tree_sq_dist(s_new.clients, s_ref.clients)) == 0.0
        assert float(tree_sq_dist(s_new.inits, s_ref.inits)) == 0.0


def test_stream_step_explicit_progress_matches_oracle():
    """The QNN kernel variant (explicit progress operand) matches the
    oracle, and the reset keeps full-precision clients."""
    n, D = 4, 3001
    server, clients, inits, alpha, mask, s = _fused_inputs(n, D, jnp.float32, 9)
    prog = jax.random.normal(jax.random.PRNGKey(10), (n, D))
    got = _stream_step(server, clients, inits, alpha, mask, s, progress=prog)
    want = ref.favas_fused_ref(server, clients, inits, alpha, mask, s,
                               progress=prog)
    for name, g, w in zip(("server", "clients", "inits"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-7, atol=2e-7, err_msg=name)
    # unselected rows of clients_new must be the original full-precision
    # clients, untouched by the progress operand
    unsel = np.asarray(mask) == 0.0
    np.testing.assert_array_equal(np.asarray(got[1])[unsel],
                                  np.asarray(clients)[unsel])


def test_favas_round_forced_kernel_path():
    """use_kernel=True (interpret on CPU) stays numerically close to the
    oracle path through a full round on a real model."""
    cfg, fcfg, state, lfn, lambdas = _setup(K=2)
    step_o = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                       lambdas=lambdas, use_kernel=False))
    step_k = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                       lambdas=lambdas, use_kernel=True))
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size_raw,
                        (fcfg.n_clients, fcfg.R, 2, 16)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}
    s_o, _ = step_o(state, batch)
    s_k, _ = step_k(state, batch)
    assert float(tree_sq_dist(s_o.server, s_k.server)) < 1e-10


def test_engine_state_held_across_rounds():
    """RoundEngine: flat buffers persist, donation works, metrics flow, and
    the exported server pytree matches the buffers."""
    cfg, fcfg, state, lfn, lambdas = _setup(K=2)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    eng = round_engine.RoundEngine(params, fcfg, lfn, lambdas=lambdas)
    est = eng.init_state(params, key)
    rng = np.random.default_rng(2)
    for t in range(2):
        toks = rng.integers(0, cfg.vocab_size_raw,
                            (fcfg.n_clients, fcfg.R, 2, 16)).astype(np.int32)
        est, m = eng.step(est, {"tokens": jnp.asarray(toks)})
        assert np.isfinite(float(m["loss"]))
        assert int(est.t) == t + 1
    out = eng.server_params(est)
    flat_again = round_engine.flatten_tree(eng.spec, out)
    for a, b in zip(flat_again, est.server):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(float(eng.variance(est)))
