"""Sharded flat-buffer engine acceptance tests (docs/architecture.md §6).

These run on a forced 8-device CPU topology:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m pytest -q tests/test_sharded_engine.py -m "not slow"

which is exactly what the CI ``sharded`` job executes. Without >= 8 visible
devices every device-gated test here SKIPS (the tier-1 suite must keep
seeing the real 1-device topology — see tests/conftest.py); the slow-marked
``test_sharded_engine_subprocess`` self-runs this file under the flag so
plain environments still exercise the suite end-to-end.

What is proven:

* the sharded engine is BIT-EXACT against the single-device engine (and
  against ``favas_round_reference``) across n in {7, 257} x {fp32, bf16},
  for both the pjit oracle path and the shard_map + Pallas-interpret kernel
  path. Bit-exactness holds because every per-lane operation of the round
  is elementwise over the lane axis and the client reduction is not
  model-sharded — partitioning the lanes cannot reorder any float sum. The
  test loss is elementwise-gradient (mean of squares per leaf) so local SGD
  is shard-invariant too; only the scalar *loss metric* may differ in
  summation order and is compared approximately.
* the SUPERSTEP scan (``engine_multi_round``) on the mesh is bit-exact vs
  sequential sharded rounds and vs the single-device superstep, for the
  oracle, kernel, and quantized paths — the mesh leg of the
  tests/test_superstep.py parity matrix.
* per-shard padded lane tails and padded client rows stay exactly zero.
* the compiled round contains NO all-gather at full-flat-buffer size
  (``launch.roofline.collective_ops`` census over ``compiled.as_text()``),
  and ``launch.dryrun.normalize_cost_analysis`` stays usable on the
  sharded executable.
"""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import round_engine
from repro.core.favas import FavasConfig, client_lambdas, favas_init, \
    favas_round_reference
from repro.launch.mesh import make_model_mesh

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def make_params(dtype, *, extra_f32_leaf: bool = True):
    """Small pytree whose paths hit the sharding/rules.py regexes: column-,
    row-, and vocab-sharded leaves (dims divisible by 8), one replicated
    leaf, and optionally a second-dtype leaf to force a mixed bucket set."""
    def f(*s, seed=0, dt=dtype):
        size = int(np.prod(s))
        v = np.linspace(-1.0, 1.0, size).reshape(s) * (1.0 + 0.1 * seed)
        return jnp.asarray(v, dt)
    tree = {
        "embed": {"table": f(16, 6, seed=1)},            # ("model", None)
        "blk": {"wq": {"w": f(6, 16, seed=2),            # (None, "model")
                       "b": f(16, seed=3)},              # ("model",)
                "wo": {"w": f(16, 6, seed=4)},           # ("model", None)
                "q_norm": {"scale": f(6, seed=5)}},      # replicated
        "mlp": {"down": {"w": f(16, 5, seed=6)}},        # ("model", None)
    }
    if extra_f32_leaf and dtype != jnp.float32:
        tree["blk"]["q_norm"]["scale"] = f(6, seed=5, dt=jnp.float32)
    return tree


def quad_loss(p, b):
    """Elementwise-gradient loss: d/dp_i mean_j (p_j - t)^2 = 2 (p_i - t)/N
    touches no cross-shard reduction, so the SGD trajectory is bit-exact
    under model sharding (the scalar loss VALUE is reduction-ordered and
    only compared approximately)."""
    t = b["t"]
    return sum(jnp.mean((l.astype(jnp.float32) - t) ** 2)
               for l in jax.tree_util.tree_leaves(p))


def _trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def _setup(n, dtype, *, quant_bits=0, params=None):
    mesh = make_model_mesh(8)
    params = make_params(dtype) if params is None else params
    fcfg = FavasConfig(n_clients=n, s_selected=min(3, n), local_steps=2,
                       eta=0.1, quant_bits=quant_bits)
    lambdas = jnp.asarray(client_lambdas(fcfg))
    spec_s = round_engine.make_flat_spec(params, n_clients=n, mesh=mesh)
    spec_r = round_engine.make_flat_spec(params, n_clients=n)
    assert max(spec_s.bucket_shards) == 8, "mesh spec must shard something"
    assert spec_s.mesh_axis == "model"
    key = jax.random.PRNGKey(0)
    st_s = jax.device_put(round_engine.engine_init(spec_s, params, fcfg, key),
                          round_engine.engine_sharding(spec_s, mesh))
    st_r = round_engine.engine_init(spec_r, params, fcfg, key)
    batch = {"t": jnp.linspace(0.0, 1.0, n * fcfg.R).reshape(n, fcfg.R)}
    return mesh, params, fcfg, lambdas, spec_s, spec_r, st_s, st_r, batch, key


def _steps(spec_s, spec_r, mesh, fcfg, lambdas, use_kernel):
    step_s = jax.jit(functools.partial(
        round_engine.engine_round, spec_s, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=use_kernel))
    step_r = jax.jit(functools.partial(
        round_engine.engine_round, spec_r, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, use_kernel=use_kernel))
    return step_s, step_r


@needs8
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("n", [7, 257])
def test_sharded_engine_bit_exact_vs_single_device(n, dtype):
    """Oracle (pjit) path: 3 rounds sharded vs single-device, all state
    bit-exact, plus a reference-implementation cross-check."""
    (mesh, params, fcfg, lambdas, spec_s, spec_r,
     st_s, st_r, batch, key) = _setup(n, dtype)
    step_s, step_r = _steps(spec_s, spec_r, mesh, fcfg, lambdas, False)
    ref_state = favas_init(params, fcfg, key)
    step_ref = jax.jit(functools.partial(
        favas_round_reference, cfg=fcfg, loss_fn=quad_loss, lambdas=lambdas))
    # reference needs the batch stacked like _local_training feeds it
    for _ in range(3):
        st_s, m_s = step_s(st_s, batch)
        st_r, m_r = step_r(st_r, batch)
        ref_state, m_f = step_ref(ref_state, batch)
        np.testing.assert_allclose(float(m_s["loss"]), float(m_r["loss"]),
                                   rtol=1e-6)
        assert float(m_s["selected"]) == float(m_r["selected"])
    _trees_equal(round_engine.engine_server_params(spec_s, st_s),
                 round_engine.engine_server_params(spec_r, st_r))
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_s.clients),
                 round_engine.unflatten_stacked(spec_r, st_r.clients))
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_s.inits),
                 round_engine.unflatten_stacked(spec_r, st_r.inits))
    np.testing.assert_array_equal(np.asarray(st_s.counters),
                                  np.asarray(st_r.counters))
    # and the seed reference agrees with both
    _trees_equal(round_engine.engine_server_params(spec_s, st_s),
                 ref_state.server)
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_s.clients),
                 ref_state.clients)


@needs8
@pytest.mark.parametrize("n", [7, 40])
def test_sharded_kernel_path_bit_exact(n):
    """shard_map + Pallas interpret kernel per shard vs the single-device
    kernel path — n=40 exercises the tiled (n > CLIENT_TILE) client axis."""
    (mesh, params, fcfg, lambdas, spec_s, spec_r,
     st_s, st_r, batch, key) = _setup(n, jnp.float32)
    step_s, step_r = _steps(spec_s, spec_r, mesh, fcfg, lambdas, True)
    for _ in range(2):
        st_s, _ = step_s(st_s, batch)
        st_r, _ = step_r(st_r, batch)
    _trees_equal(round_engine.engine_server_params(spec_s, st_s),
                 round_engine.engine_server_params(spec_r, st_r))
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_s.clients),
                 round_engine.unflatten_stacked(spec_r, st_r.clients))


@needs8
def test_sharded_wide_stream_kernel_bit_exact(monkeypatch):
    """The plain streamed round's kernel on the mesh with lane blocks wider
    than TILE: each shard's f32 bucket spans 3 TILEs and the blocks 2, so
    every shard runs a partial last block. shard_map + interpret kernel vs
    the single-device kernel (whose one segment is blocked differently)
    and vs TILE-wide blocks on the mesh, bit for bit."""
    from repro.kernels import favas_agg
    params = make_params(jnp.float32)
    params["embed"]["table"] = jnp.asarray(
        np.linspace(-1.0, 1.0, 640 * 64).reshape(640, 64), jnp.float32)

    def run(cap):
        monkeypatch.setattr(favas_agg, "STREAM_MAX_WIDTH", cap)
        (mesh, _, fcfg, lambdas, spec_s, spec_r,
         st_s, st_r, batch, _) = _setup(7, jnp.float32, params=params)
        assert [spec_s.bucket_shard_padded[b] for b in range(spec_s.n_buckets)
                if spec_s.shards(b) == 8] == [3 * favas_agg.TILE]
        step_s, step_r = _steps(spec_s, spec_r, mesh, fcfg, lambdas, True)
        for _ in range(2):
            st_s, _ = step_s(st_s, batch)
            st_r, _ = step_r(st_r, batch)
        return spec_s, st_s, spec_r, st_r

    spec_s, wide, spec_r, single = run(2 * favas_agg.TILE)
    _, narrow, _, _ = run(favas_agg.TILE)
    for other, spec in ((single, spec_r), (narrow, spec_s)):
        _trees_equal(round_engine.engine_server_params(spec_s, wide),
                     round_engine.engine_server_params(spec, other))
        _trees_equal(round_engine.unflatten_stacked(spec_s, wide.clients),
                     round_engine.unflatten_stacked(spec, other.clients))


@needs8
def test_sharded_quantized_progress_bit_exact():
    """FAVAS[QNN] on the sharded engine: LUQ scales are max-based (order-
    insensitive) and the PRNG draws are sharding-invariant, so even the
    quantized round is bit-exact vs single-device."""
    (mesh, params, fcfg, lambdas, spec_s, spec_r,
     st_s, st_r, batch, key) = _setup(7, jnp.float32, quant_bits=4)
    step_s, step_r = _steps(spec_s, spec_r, mesh, fcfg, lambdas, False)
    for _ in range(2):
        st_s, _ = step_s(st_s, batch)
        st_r, _ = step_r(st_r, batch)
    _trees_equal(round_engine.engine_server_params(spec_s, st_s),
                 round_engine.engine_server_params(spec_r, st_r))
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_s.inits),
                 round_engine.unflatten_stacked(spec_r, st_r.inits))


@needs8
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("n", [7, 257])
def test_sharded_superstep_bit_exact(n, dtype):
    """engine_multi_round on the mesh: a 5-round superstep scan equals 5
    sequential sharded rounds AND the single-device superstep — the mesh leg
    of the tests/test_superstep.py parity matrix (scan composes with the
    shard_map/pjit per-bucket dispatch without re-dispatching per round)."""
    (mesh, params, fcfg, lambdas, spec_s, spec_r,
     st_s, st_r, batch, key) = _setup(n, dtype)
    step_s, _step_r = _steps(spec_s, spec_r, mesh, fcfg, lambdas, False)
    multi_s = jax.jit(functools.partial(
        round_engine.engine_multi_round, spec_s, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=False))
    multi_r = jax.jit(functools.partial(
        round_engine.engine_multi_round, spec_r, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, use_kernel=False))
    T = 5
    batches = {"t": jnp.stack([batch["t"] * (1.0 + 0.1 * t)
                               for t in range(T)])}
    st_seq = st_s
    for t in range(T):
        st_seq, _ = step_s(st_seq, {"t": batches["t"][t]})
    st_sup, m_sup = multi_s(st_s, batches)
    st_rep, m_rep = multi_r(st_r, batches)
    assert m_sup["loss"].shape == (T,)
    for getter in (lambda s: round_engine.engine_server_params(spec_s, s),
                   lambda s: round_engine.unflatten_stacked(spec_s, s.clients),
                   lambda s: round_engine.unflatten_stacked(spec_s, s.inits)):
        _trees_equal(getter(st_seq), getter(st_sup))
    # sharded superstep == single-device superstep, tree-for-tree
    _trees_equal(round_engine.engine_server_params(spec_s, st_sup),
                 round_engine.engine_server_params(spec_r, st_rep))
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_sup.clients),
                 round_engine.unflatten_stacked(spec_r, st_rep.clients))
    np.testing.assert_array_equal(np.asarray(st_sup.counters),
                                  np.asarray(st_rep.counters))


@needs8
def test_sharded_superstep_quantized_and_kernel_paths():
    """The superstep scan composes with FAVAS[QNN] quantization and with the
    shard_map + interpret-Pallas kernel path, staying bit-exact vs the
    sequential sharded rounds."""
    for quant, use_kernel in ((4, False), (0, True)):
        (mesh, params, fcfg, lambdas, spec_s, spec_r,
         st_s, _st_r, batch, key) = _setup(7, jnp.float32, quant_bits=quant)
        step_s, _ = _steps(spec_s, spec_r, mesh, fcfg, lambdas, use_kernel)
        multi_s = jax.jit(functools.partial(
            round_engine.engine_multi_round, spec_s, cfg=fcfg,
            loss_fn=quad_loss, lambdas=lambdas, mesh=mesh,
            use_kernel=use_kernel))
        T = 3
        batches = {"t": jnp.stack([batch["t"]] * T)}
        st_seq = st_s
        for t in range(T):
            st_seq, _ = step_s(st_seq, {"t": batches["t"][t]})
        st_sup, _ = multi_s(st_s, batches)
        _trees_equal(round_engine.engine_server_params(spec_s, st_seq),
                     round_engine.engine_server_params(spec_s, st_sup))
        _trees_equal(round_engine.unflatten_stacked(spec_s, st_seq.clients),
                     round_engine.unflatten_stacked(spec_s, st_sup.clients))


@needs8
def test_sharded_padded_tails_stay_zero():
    """Per-shard lane tails and padded client rows must remain exactly zero
    after rounds — the invariant that makes per-shard padding safe."""
    (mesh, params, fcfg, lambdas, spec_s, _spec_r,
     st_s, _st_r, batch, key) = _setup(257, jnp.float32)
    step_s, _ = _steps(spec_s, _spec_r, mesh, fcfg, lambdas, False)
    for _ in range(2):
        st_s, _ = step_s(st_s, batch)
    n = spec_s.n_clients
    for b in range(spec_s.n_buckets):
        S = spec_s.shards(b)
        used = spec_s.bucket_shard_sizes[b]
        srv = np.asarray(st_s.server[b], np.float32).reshape(
            S, spec_s.bucket_shard_padded[b])
        assert np.all(srv[:, used:] == 0.0), f"server tail bucket {b}"
        cli = np.asarray(st_s.clients[b], np.float32)
        assert np.all(cli[n:] == 0.0), f"padded client rows bucket {b}"
        cli3 = cli.reshape(cli.shape[0], S, spec_s.bucket_shard_padded[b])
        assert np.all(cli3[:, :, used:] == 0.0), f"client lane tails bucket {b}"


@needs8
def test_sharded_round_has_no_full_buffer_gather():
    """Acceptance check: the compiled sharded round's collective census has
    no all-gather at (or above) full-flat-buffer size, and the normalized
    cost analysis remains readable."""
    (mesh, params, fcfg, lambdas, spec_s, _spec_r,
     st_s, _st_r, batch, key) = _setup(7, jnp.float32)
    step_s, _ = _steps(spec_s, _spec_r, mesh, fcfg, lambdas, False)
    compiled = step_s.lower(st_s, batch).compile()
    hlo = compiled.as_text()
    from repro.launch.roofline import collective_ops
    full_bytes = min(
        p * jnp.dtype(dt).itemsize
        for p, dt, S in zip(spec_s.bucket_padded, spec_s.bucket_dtypes,
                            spec_s.bucket_shards) if S > 1)
    gathers = [b for kind, b in collective_ops(hlo) if kind == "all-gather"]
    assert all(b < full_bytes for b in gathers), (
        f"full-buffer all-gather in the round: {gathers} >= {full_bytes}")
    # the jax-version-portable cost accessor must work on this executable
    from repro.launch.dryrun import normalize_cost_analysis
    cost = normalize_cost_analysis(compiled.cost_analysis())
    assert isinstance(cost, dict)


@needs8
def test_device_corpus_gather_stays_shard_local():
    """Device data plane on the mesh (docs/architecture.md §8): with a
    REPLICATED corpus, the in-scan minibatch gather is shard-local — the
    compiled device-plane superstep contains NO all-gather at (or above)
    full-corpus size — and the sharded device plane stays bit-exact
    against the single-device device plane (same key chain, same sampled
    indices, elementwise-gradient loss)."""
    import functools as ft
    from repro.data.device_corpus import make_classification_corpus
    (mesh, params, fcfg, lambdas, spec_s, spec_r,
     st_s, st_r, _batch, key) = _setup(7, jnp.float32)
    rng = np.random.default_rng(0)
    N = 2048
    x = rng.normal(0, 1, (N, 8)).astype(np.float32)
    y = rng.integers(0, 4, N).astype(np.int32)
    parts = [rng.choice(N, rng.integers(5, 200), replace=False)
             for _ in range(fcfg.n_clients)]
    corpus_s = make_classification_corpus(x, y, parts, batch=2, mesh=mesh)
    corpus_r = make_classification_corpus(x, y, parts, batch=2)

    def corpus_loss(p, b):
        # elementwise gradient (see quad_loss); the batch enters only
        # through a replicated scalar, so sharding cannot reorder sums
        t = jnp.mean(b["x"]) + 0.01 * jnp.mean(b["y"].astype(jnp.float32))
        return sum(jnp.mean((l.astype(jnp.float32) - t) ** 2)
                   for l in jax.tree_util.tree_leaves(p))

    multi_s = jax.jit(ft.partial(
        round_engine.engine_multi_round, spec_s, cfg=fcfg,
        loss_fn=corpus_loss, lambdas=lambdas, mesh=mesh, use_kernel=False),
        static_argnames=("n_rounds",))
    multi_r = jax.jit(ft.partial(
        round_engine.engine_multi_round, spec_r, cfg=fcfg,
        loss_fn=corpus_loss, lambdas=lambdas, use_kernel=False),
        static_argnames=("n_rounds",))
    st_sup, m_s = multi_s(st_s, corpus=corpus_s, n_rounds=4)
    st_rep, m_r = multi_r(st_r, corpus=corpus_r, n_rounds=4)
    assert m_s["loss"].shape == (4,)
    _trees_equal(round_engine.engine_server_params(spec_s, st_sup),
                 round_engine.engine_server_params(spec_r, st_rep))
    _trees_equal(round_engine.unflatten_stacked(spec_s, st_sup.clients),
                 round_engine.unflatten_stacked(spec_r, st_rep.clients))
    # collective census: nothing may gather the corpus (or more) per chunk
    hlo = multi_s.lower(st_s, corpus=corpus_s,
                        n_rounds=4).compile().as_text()
    from repro.launch.roofline import collective_ops
    corpus_bytes = x.nbytes
    gathers = [b for kind, b in collective_ops(hlo) if kind == "all-gather"]
    assert all(b < corpus_bytes for b in gathers), (
        f"full-corpus all-gather in the device-plane superstep: "
        f"{gathers} >= {corpus_bytes}")


@needs8
@pytest.mark.parametrize("n", [7, 257])
def test_sharded_paged_passthrough_bit_exact(n):
    """Paged residency on the mesh (docs/architecture.md §9): with the
    passthrough codec at s_max == n, the sharded paged superstep is
    bit-exact against BOTH the sharded dense superstep and the
    single-device paged superstep — the cold pools shard like the §6
    buckets, and evict/promote adds no cross-shard reduction."""
    (mesh, params, fcfg, lambdas, spec_s, spec_r,
     st_s, st_r, batch, key) = _setup(n, jnp.float32)
    spec_p = round_engine.make_flat_spec(params, n_clients=n, mesh=mesh,
                                         residency="paged")
    assert spec_p.paged and spec_p.s_max == n
    st_p = jax.device_put(round_engine.engine_init(spec_p, params, fcfg, key),
                          round_engine.engine_sharding(spec_p, mesh))
    multi_p = jax.jit(functools.partial(
        round_engine.engine_multi_round, spec_p, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=False))
    multi_s = jax.jit(functools.partial(
        round_engine.engine_multi_round, spec_s, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=False))
    T = 4
    batches = {"t": jnp.stack([batch["t"] * (1.0 + 0.1 * t)
                               for t in range(T)])}
    st_pp, m_p = multi_p(st_p, batches)
    st_ss, m_s = multi_s(st_s, batches)
    _trees_equal(round_engine.engine_server_params(spec_p, st_pp),
                 round_engine.engine_server_params(spec_s, st_ss))
    _trees_equal(round_engine.unflatten_stacked(spec_p, st_pp.clients),
                 round_engine.unflatten_stacked(spec_s, st_ss.clients))
    _trees_equal(round_engine.unflatten_stacked(spec_p, st_pp.inits),
                 round_engine.unflatten_stacked(spec_s, st_ss.inits))
    np.testing.assert_array_equal(np.asarray(st_pp.counters),
                                  np.asarray(st_ss.counters))
    np.testing.assert_array_equal(np.asarray(st_pp.key),
                                  np.asarray(st_ss.key))
    np.testing.assert_array_equal(np.asarray(m_p["loss"]),
                                  np.asarray(m_s["loss"]))
    # ... and against the single-device paged engine
    spec_p1 = round_engine.make_flat_spec(params, n_clients=n,
                                          residency="paged")
    st_p1 = round_engine.engine_init(spec_p1, params, fcfg, key)
    multi_p1 = jax.jit(functools.partial(
        round_engine.engine_multi_round, spec_p1, cfg=fcfg,
        loss_fn=quad_loss, lambdas=lambdas, use_kernel=False))
    st_p1, _ = multi_p1(st_p1, batches)
    _trees_equal(round_engine.unflatten_stacked(spec_p, st_pp.clients),
                 round_engine.unflatten_stacked(spec_p1, st_p1.clients))
    np.testing.assert_array_equal(np.asarray(st_pp.hot_ids),
                                  np.asarray(st_p1.hot_ids))


@needs8
def test_sharded_paged_luq_cold_pool_no_full_gather():
    """s_max < n with 4-bit cold pools on the mesh: the round runs, stays
    finite, cold codes stay uint8, and the compiled paged superstep has no
    all-gather at (or above) full-cold-pool size — evict (requant+scatter)
    and promote (gather+dequant) are shard-local."""
    from repro.core.paging import LuqCodec, encoded_nbytes
    n, s_max = 40, 8
    mesh = make_model_mesh(8)
    params = make_params(jnp.float32)
    fcfg = FavasConfig(n_clients=n, s_selected=3, local_steps=2, eta=0.1)
    lambdas = jnp.asarray(client_lambdas(fcfg))
    spec = round_engine.make_flat_spec(params, n_clients=n, mesh=mesh,
                                       residency="paged", s_max=s_max,
                                       cold_codec=LuqCodec(bits=4))
    key = jax.random.PRNGKey(1)
    st = jax.device_put(round_engine.engine_init(spec, params, fcfg, key),
                        round_engine.engine_sharding(spec, mesh))
    cold_bytes = min(encoded_nbytes(st.cold[b])
                     for b in range(spec.n_buckets) if spec.shards(b) > 1)
    multi = jax.jit(functools.partial(
        round_engine.engine_multi_round, spec, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=False))
    batch = {"t": jnp.linspace(0.0, 1.0, n * fcfg.R).reshape(n, fcfg.R)}
    batches = {"t": jnp.stack([batch["t"]] * 6)}
    lowered = multi.lower(st, batches)
    st, ms = multi(st, batches)
    assert np.all(np.isfinite(np.asarray(ms["loss"])))
    assert st.cold[0]["init"]["codes"].dtype == jnp.uint8
    assert np.asarray(st.hot_ids).shape == (s_max,)
    from repro.launch.roofline import collective_ops
    hlo = lowered.compile().as_text()
    gathers = [b for kind, b in collective_ops(hlo) if kind == "all-gather"]
    assert all(b < cold_bytes for b in gathers), (
        f"cold-pool-sized all-gather in the paged superstep: "
        f"{gathers} >= {cold_bytes}")


@needs8
def test_sharded_codes_in_progress_parity():
    """Codes-in transport on the mesh (docs/architecture.md §10): the
    transmitted progress reaches ``stream_bucket_update`` as packed LUQ
    codes + per-(row, shard) scales. The oracle branch is element-EXACT vs
    the ``luq_decode_rows`` -> ``favas_stream_ref`` composition (it IS that
    composition, with its output sharding pinned), and the shard_map +
    interpret-Pallas codes-in branch — each device dequantizing its own
    lane segment against its own scale column, no collectives — matches
    within 2 fp32 ULPs of the per-lane accumulator magnitude (the
    tests/test_quant_fused.py budget: in-VMEM dequant contraction plus the
    client-reduction order)."""
    from repro.core.paging import luq_decode_rows
    from repro.kernels import ref
    from repro.kernels.ops import cold_requant_rows
    n, bits = 7, 4
    mesh = make_model_mesh(8)
    params = make_params(jnp.float32)
    fcfg = FavasConfig(n_clients=n, s_selected=3, local_steps=1, eta=0.1,
                       quant_bits=bits)
    spec = round_engine.make_flat_spec(params, n_clients=n, mesh=mesh)
    b = next(i for i in range(spec.n_buckets) if spec.shards(i) == 8)
    key = jax.random.PRNGKey(0)
    st = jax.device_put(round_engine.engine_init(spec, params, fcfg, key),
                        round_engine.engine_sharding(spec, mesh))
    rows, Dp = st.clients[b].shape
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    trained = st.clients[b] + 0.1 * jax.random.normal(ks[0], (rows, Dp))
    alpha = jax.random.uniform(ks[1], (rows,), minval=1.0, maxval=8.0)
    mask = jnp.where(jnp.arange(rows) < n,
                     (jax.random.uniform(ks[2], (rows,)) > 0.5)
                     .astype(jnp.float32), 0.0)
    s = float(mask.sum())
    delta = trained.astype(jnp.float32) - st.inits[b].astype(jnp.float32)
    enc = cold_requant_rows(delta, bits, jax.random.PRNGKey(2),
                            shards=8, use_kernel=False)
    prog = luq_decode_rows(enc, bits, jnp.float32, shards=8)
    want = ref.favas_stream_ref(st.server[b], trained, st.inits[b],
                                alpha, mask, s, progress=prog)
    got_o = round_engine.stream_bucket_update(
        spec, b, st.server[b], trained, st.inits[b], alpha, mask, s,
        progress_codes_b=enc, progress_bits=bits, mesh=mesh,
        use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(want))
    got_k = round_engine.stream_bucket_update(
        spec, b, st.server[b], trained, st.inits[b], alpha, mask, s,
        progress_codes_b=enc, progress_bits=bits, mesh=mesh,
        use_kernel=True)
    msg = (np.asarray(st.inits[b], np.float64)
           + np.asarray(prog, np.float64)
           / np.asarray(alpha, np.float64)[:, None])
    acc = (np.abs(np.asarray(st.server[b], np.float64))
           + np.sum(np.abs(np.asarray(mask, np.float64)[:, None] * msg),
                    axis=0))
    ulp = 2.0 * np.spacing(acc.astype(np.float32)) / (s + 1.0)
    d = np.abs(np.asarray(got_k, np.float64) - np.asarray(want, np.float64))
    assert np.all(d <= ulp), float((d / ulp).max())


@needs8
def test_sharded_engine_quant_fused_round():
    """The full quant_fused round on the mesh: the per-bucket encodes use
    shards=spec.shards(b) so both dispatch paths consume the SAME codes;
    kernel vs oracle states agree to kernel-ULP level after two rounds,
    and the compiled codes-in round still has no full-flat-buffer
    all-gather — the codes and their scale columns stay shard-local."""
    (mesh, params, fcfg, lambdas, spec_s, _spec_r,
     st_o, _st_r, batch, key) = _setup(7, jnp.float32, quant_bits=4)
    st_k = jax.device_put(round_engine.engine_init(spec_s, params, fcfg, key),
                          round_engine.engine_sharding(spec_s, mesh))
    step_o = jax.jit(functools.partial(
        round_engine.engine_round, spec_s, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=False, quant_fused=True))
    step_k = jax.jit(functools.partial(
        round_engine.engine_round, spec_s, cfg=fcfg, loss_fn=quad_loss,
        lambdas=lambdas, mesh=mesh, use_kernel=True, quant_fused=True))
    for _ in range(2):
        st_o, m_o = step_o(st_o, batch)
        st_k, m_k = step_k(st_k, batch)
    assert np.all(np.isfinite(np.asarray(m_o["loss"])))
    for a, b in zip(st_o.server + st_o.clients, st_k.server + st_k.clients):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)
    from repro.launch.roofline import collective_ops
    hlo = step_o.lower(st_o, batch).compile().as_text()
    full_bytes = min(
        p * jnp.dtype(dt).itemsize
        for p, dt, S in zip(spec_s.bucket_padded, spec_s.bucket_dtypes,
                            spec_s.bucket_shards) if S > 1)
    gathers = [b for kind, b in collective_ops(hlo) if kind == "all-gather"]
    assert all(b < full_bytes for b in gathers), (
        f"full-buffer all-gather in the codes-in round: "
        f"{gathers} >= {full_bytes}")


def test_flat_spec_invariants_without_devices():
    """Sharding-aware layout metadata needs no devices: explicit shard_axes
    + model_shards give the same bucket structure tier-1 can verify."""
    tree = {"a": jnp.zeros((8, 6)), "b": jnp.zeros((5,)),
            "c": jnp.zeros((4, 4), jnp.bfloat16)}
    spec = round_engine.make_flat_spec(tree, tile=8, n_clients=3,
                                       shard_axes=[0, None, 1],
                                       model_shards=4)
    for b in range(spec.n_buckets):
        assert (spec.bucket_padded[b]
                == spec.shards(b) * spec.bucket_shard_padded[b])
        assert spec.bucket_shard_padded[b] % 8 == 0
    # non-dividing nominated dim falls back to the replicated bucket
    spec2 = round_engine.make_flat_spec(tree, tile=8, shard_axes=[0, 0, 1],
                                        model_shards=4)
    b_of_b = spec2.bucket_of[1]          # leaf "b": (5,) % 4 != 0
    assert spec2.shards(b_of_b) == 1 and spec2.shard_axes[1] is None


@pytest.mark.slow
def test_sharded_engine_subprocess():
    """Self-run this file under the forced-8-device flag so environments
    without the flag still get full sharded coverage (the CI ``sharded``
    job runs the same command directly)."""
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
         "tests/test_sharded_engine.py"],
        capture_output=True, text=True, env=env, cwd=root, timeout=1200)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "skipped" not in out.stdout.lower() or "passed" in out.stdout
