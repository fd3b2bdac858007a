"""The tiled-client-axis test tier (PR 2).

The fused FAVAS round kernel streams (CLIENT_TILE, TILE) client blocks
through a VMEM scratch accumulator so n scales to thousands. This file
proves that regime:

* parity of the tiled kernels (interpret mode) against the shape-agnostic
  jnp oracles across n x dtype x progress sweeps, including n not a
  multiple of CLIENT_TILE and D not a multiple of TILE;
* a 1-ULP-at-accumulator-scale bound at the production client count
  (n=1024) — the tiled kernel reorders the client reduction (per-block
  partial sums accumulated sequentially), so parity is bounded by ULPs of
  |server| + sum_i |mask_i * msg_i| per lane, before the 1/(s+1) division;
* the VMEM budget of the production shape (n=1024, D=2^20), asserted from
  the declared block shapes — the tiled footprint is independent of n and D;
* a hypothesis property: FlatSpec flatten/unflatten round-trips mixed-dtype
  stacked pytrees bit-exactly for arbitrary n (client-axis padding on);
* engine semantics at large n (slow tier): engine_round with n=512 / n=500
  on a tiny model matches favas_round_reference exactly, padded bucket
  tails stay zero after 3 rounds, and stale/selected metrics match the mask;
* regression: the unified guarded LUQ scale maps all-zero inputs to zero
  output (no 0/0) on every path.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FavasConfig, favas_init, favas_round,
                        favas_round_reference, client_lambdas)
from repro.core import round_engine
from repro.core.quant import luq_quantize as quant_luq
from repro.kernels import ops, ref
from repro.kernels.favas_agg import (CLIENT_TILE, TILE, favas_agg_pallas,
                                     favas_fused_pallas,
                                     fused_block_vmem_bytes)


def _fused_inputs(n, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    server = jax.random.normal(ks[0], (D,), dtype)
    clients = jax.random.normal(ks[1], (n, D), dtype)
    inits = jax.random.normal(ks[2], (n, D), dtype)
    alpha = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=8.0)
    mask = (jax.random.uniform(ks[4], (n,)) > 0.5).astype(jnp.float32)
    return server, clients, inits, alpha, mask, float(mask.sum())


# ---------------------------------------------------------------------------
# Tiled kernel parity vs the shape-agnostic oracle
# ---------------------------------------------------------------------------

# D=2500 is not a multiple of TILE (lane padding path) and spans two lane
# tiles; n=257/1000 are not multiples of CLIENT_TILE (row padding path);
# n=64/257/1000 exceed CLIENT_TILE=32 (tiled two-phase path); n=1/7 keep
# the resident single-sweep path so both dispatches stay covered.
@pytest.mark.parametrize("n", [1, 7, 64, 257, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("quantized", [False, True])
def test_fused_tiled_matches_oracle(n, dtype, quantized):
    D = 2500
    server, clients, inits, alpha, mask, s = _fused_inputs(
        n, D, dtype, seed=n + 17 * quantized)
    progress = None
    if quantized:
        # FAVAS[QNN]: the transmitted progress is LUQ-quantized
        progress = ops.luq_quantize(
            (clients - inits).astype(jnp.float32), 4,
            jax.random.PRNGKey(n), use_kernel=False).astype(dtype)
    got = favas_fused_pallas(server, clients, inits, alpha, mask, s,
                             progress=progress, interpret=True)
    want = ref.favas_fused_ref(server, clients, inits, alpha, mask, s,
                               progress=progress)
    tol = (dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32
           else dict(rtol=8e-3, atol=8e-3))
    for name, g, w in zip(("server", "clients", "inits"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   err_msg=name, **tol)
    if quantized:
        # resets keep the full-precision client state (Remark 1)
        unsel = np.asarray(mask) == 0.0
        np.testing.assert_array_equal(
            np.asarray(got[1], np.float32)[unsel],
            np.asarray(clients, np.float32)[unsel])


@pytest.mark.parametrize("n,D", [(64, 4097), (257, 3000)])
def test_agg_tiled_matches_ref(n, D):
    """The single-output aggregation kernel's tiled path (one sweep, scratch
    accumulator + @pl.when epilogue)."""
    server, clients, inits, alpha, mask, s = _fused_inputs(n, D, jnp.float32,
                                                           seed=n)
    out_k = favas_agg_pallas(server, clients, inits, alpha, mask, s,
                             interpret=True)
    out_r = ref.favas_agg_ref(server, clients, inits, alpha, mask, s)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


def test_fused_tiled_one_ulp_at_accumulator_scale():
    """Production client count: the tiled kernel reorders the client-axis
    reduction, so the only daylight vs the oracle is summation order. Bound
    it by 1 fp32 ULP of the accumulator magnitude per lane
    (|server| + sum_i |mask_i * msg_i|), scaled by the 1/(s+1) division."""
    n, D = 1024, 6144
    server, clients, inits, alpha, mask, s = _fused_inputs(n, D, jnp.float32,
                                                           seed=11)
    got = favas_fused_pallas(server, clients, inits, alpha, mask, s,
                             interpret=True)
    want = ref.favas_fused_ref(server, clients, inits, alpha, mask, s)
    msg = (np.asarray(inits, np.float64)
           + (np.asarray(clients, np.float64) - np.asarray(inits, np.float64))
           / np.asarray(alpha, np.float64)[:, None])
    acc_scale = (np.abs(np.asarray(server, np.float64))
                 + np.sum(np.abs(np.asarray(mask, np.float64)[:, None] * msg),
                          axis=0))
    ulp = np.spacing(acc_scale.astype(np.float32)) / (s + 1.0)   # per lane
    srv_diff = np.abs(np.asarray(got[0], np.float64)
                      - np.asarray(want[0], np.float64))
    assert np.all(srv_diff <= ulp), float((srv_diff / ulp).max())
    # the reset outputs blend s_new with untouched state, so the same
    # per-lane bound applies to every row
    for g, w in zip(got[1:], want[1:]):
        d = np.abs(np.asarray(g, np.float64) - np.asarray(w, np.float64))
        assert np.all(d <= ulp[None, :]), float((d / ulp[None, :]).max())


def test_fused_vmem_budget_production_shape():
    """Acceptance: n=1024, D=2^20 per-grid-step VMEM <= 2 MiB, asserted from
    the declared block shapes. The tiled footprint must be independent of
    both n and D — that is what lets the engine scale."""
    budget = 2 * 1024 * 1024
    got = fused_block_vmem_bytes(1024, jnp.float32)
    assert got <= budget, got
    assert fused_block_vmem_bytes(1024, jnp.float32, progress=True) <= budget
    # block shapes carry no D term at all, and no n term beyond CLIENT_TILE:
    # n=2^20 clients costs the same VMEM as n=1024 (only HBM grows)
    assert fused_block_vmem_bytes(1 << 20, jnp.float32) == got
    # the declared blocks: (1,T) server in/out + 2x(CT,T) rows in/out
    # + 2x(CT,1) f32 scalars + 2x(1,T) f32 scratch
    expect = (2 * TILE * 4 + 4 * CLIENT_TILE * TILE * 4
              + 2 * CLIENT_TILE * 4 + 2 * TILE * 4)
    assert got == expect


def test_fused_tiled_zero_selection():
    """s = 0, n > CLIENT_TILE: server passes through, clients untouched."""
    n, D = CLIENT_TILE * 3 + 5, 300
    server, clients, inits, alpha, _, _ = _fused_inputs(n, D, jnp.float32, 3)
    mask = jnp.zeros((n,), jnp.float32)
    srv, cli, ini = favas_fused_pallas(server, clients, inits, alpha, mask,
                                       0.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(srv), np.asarray(server))
    np.testing.assert_array_equal(np.asarray(cli), np.asarray(clients))
    np.testing.assert_array_equal(np.asarray(ini), np.asarray(inits))


# ---------------------------------------------------------------------------
# FlatSpec client-axis padding: deterministic round-trip cases
# (the hypothesis fuzz over arbitrary n/layouts lives in
#  tests/test_flat_spec_properties.py — hypothesis is an optional dep)
# ---------------------------------------------------------------------------

_LEAF_DTYPES = (np.float32, np.float16, np.int32)


def check_stacked_roundtrip_bit_exact(n, client_tile, seed, layout):
    """flatten_stacked -> unflatten_stacked is bit-exact for arbitrary n and
    mixed-dtype trees, with the client axis padded to the client tile.
    ``layout``: sequence of (leaf_shape, dtype_index into _LEAF_DTYPES)."""
    rng = np.random.default_rng(seed)
    tree = {}
    for k, (shape, di) in enumerate(layout):
        dt = _LEAF_DTYPES[di]
        # +-2^10 is exactly representable in every tested dtype (fp16 incl.)
        raw = rng.integers(-(2 ** 10), 2 ** 10,
                           size=(n,) + tuple(shape)).astype(dt)
        tree[f"leaf{k}"] = jnp.asarray(raw)
    template = jax.tree_util.tree_map(lambda x: x[0], tree)
    spec = round_engine.make_flat_spec(template, n_clients=n,
                                       client_tile=client_tile)
    if n > client_tile:
        assert spec.n_padded % client_tile == 0 and spec.n_padded >= n
    else:
        assert spec.n_padded == n
    bufs = round_engine.flatten_stacked(spec, tree)
    for b, buf in enumerate(bufs):
        assert buf.shape == (spec.n_padded, spec.bucket_padded[b])
        # padded rows are zero — the invariant the round update preserves
        np.testing.assert_array_equal(np.asarray(buf)[n:], 0)
    back = round_engine.unflatten_stacked(spec, bufs)
    for key in tree:
        a, b = np.asarray(tree[key]), np.asarray(back[key])
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("n,client_tile", [(1, 4), (3, 4), (5, 4), (23, 8),
                                           (64, 8)])
def test_flat_spec_stacked_roundtrip_cases(n, client_tile):
    layout = [((2, 3), 0), ((7,), 1), ((), 2), ((4,), 0), ((1, 1, 5), 1)]
    check_stacked_roundtrip_bit_exact(n, client_tile, seed=n, layout=layout)


# ---------------------------------------------------------------------------
# Engine semantics at large n (slow tier — tier-1 stays fast)
# ---------------------------------------------------------------------------

def _tiny_setup(n, s, seed=0):
    fcfg = FavasConfig(n_clients=n, s_selected=s, local_steps=2, eta=0.05,
                       seed=seed)
    key = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(key, (8, 16)),
              "b": jnp.zeros((16,))}

    def lfn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    lambdas = jnp.asarray(client_lambdas(fcfg))
    return fcfg, params, lfn, lambdas


def _tiny_batch(rng, n, R):
    return {"x": jnp.asarray(rng.normal(size=(n, R, 4, 8)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(n, R, 4, 16)), jnp.float32)}


@pytest.mark.slow
@pytest.mark.parametrize("n", [512, 500])   # 500: n % CLIENT_TILE != 0
def test_engine_large_n_matches_reference(n):
    """engine_round at production n reproduces the seed's per-leaf reference
    exactly — through the client-padded flat buffers — and the metrics
    (selected, stale_rounds) match the selection mask."""
    fcfg, params, lfn, lambdas = _tiny_setup(n, s=64)
    state = favas_init(params, fcfg, jax.random.PRNGKey(0))
    step_new = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                         lambdas=lambdas))
    step_ref = jax.jit(functools.partial(favas_round_reference, cfg=fcfg,
                                         loss_fn=lfn, lambdas=lambdas))
    rng = np.random.default_rng(1)
    s_new = s_ref = state
    for _ in range(3):
        batch = _tiny_batch(rng, n, fcfg.R)
        s_new, m_new = step_new(s_new, batch)
        s_ref, m_ref = step_ref(s_ref, batch)
        for leaf_a, leaf_b in zip(
                jax.tree_util.tree_leaves((s_new.server, s_new.clients,
                                           s_new.inits)),
                jax.tree_util.tree_leaves((s_ref.server, s_ref.clients,
                                           s_ref.inits))):
            np.testing.assert_array_equal(np.asarray(leaf_a),
                                          np.asarray(leaf_b))
        np.testing.assert_array_equal(np.asarray(s_new.counters),
                                      np.asarray(s_ref.counters))
        np.testing.assert_array_equal(np.asarray(s_new.stale),
                                      np.asarray(s_ref.stale))
        # stale/selected metrics vs the mask (selection resets stale to 0;
        # Gumbel top-s selects exactly s clients)
        mask = np.asarray(s_ref.stale) == 0
        assert float(m_new["selected"]) == float(mask.sum()) == fcfg.s_selected
        assert float(m_new["stale_rounds"]) == float(np.asarray(s_new.stale).max())
        assert float(m_new["loss"]) == float(m_ref["loss"])


@pytest.mark.slow
def test_engine_large_n_padded_tails_stay_zero():
    """RoundEngine with n=500 (padded to 512 rows): after 3 rounds every
    padded client row and every padded lane tail is still exactly zero, and
    the kernel path agrees with the oracle path."""
    n = 500
    fcfg, params, lfn, lambdas = _tiny_setup(n, s=64)
    eng = round_engine.RoundEngine(params, fcfg, lfn, lambdas=lambdas)
    assert eng.spec.n_padded == 512 and eng.spec.client_tile == CLIENT_TILE
    key = jax.random.PRNGKey(0)
    est = eng.init_state(params, key)
    rng = np.random.default_rng(2)
    for _ in range(3):
        est, m = eng.step(est, _tiny_batch(rng, n, fcfg.R))
        assert np.isfinite(float(m["loss"]))
    for b in range(eng.spec.n_buckets):
        np.testing.assert_array_equal(np.asarray(est.clients[b][n:]), 0)
        np.testing.assert_array_equal(np.asarray(est.inits[b][n:]), 0)
        np.testing.assert_array_equal(
            np.asarray(est.server[b][eng.spec.bucket_sizes[b]:]), 0)
    assert np.isfinite(float(eng.variance(est)))
    # one more round through the forced interpret-kernel path (the tiled
    # kernel inside a real jitted round) stays numerically with the oracle.
    # NOTE the order: eng.step donates its input state, so the non-donating
    # kernel-path step must consume ``est`` first.
    step_k = jax.jit(functools.partial(
        round_engine.engine_round, eng.spec, cfg=fcfg, loss_fn=lfn,
        lambdas=lambdas, det_alpha=None, use_kernel=True))
    batch = _tiny_batch(rng, n, fcfg.R)
    est_k, _ = step_k(est, batch)
    est_o, _ = eng.step(est, batch)
    for bo, bk in zip(est_o.server, est_k.server):
        np.testing.assert_allclose(np.asarray(bo), np.asarray(bk),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# LUQ guarded scale — all-zero input regression
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["ops_oracle", "ops_kernel", "core_sim"])
def test_luq_all_zero_input_is_exact_zero(path):
    """The unified guarded scale (core.quant.luq_scale) maps all-zero leaves
    to scale 1.0, so every LUQ path returns exact zeros with no NaN/inf."""
    x = jnp.zeros((513,), jnp.float32)
    key = jax.random.PRNGKey(7)
    if path == "ops_oracle":
        q = ops.luq_quantize(x, 4, key, use_kernel=False)
    elif path == "ops_kernel":
        q = ops.luq_quantize(x, 4, key, use_kernel=True)
    else:
        q = quant_luq(x, 4, key)
    q = np.asarray(q)
    assert np.all(np.isfinite(q))
    np.testing.assert_array_equal(q, 0.0)
