"""The quantized-transport (codes-in) test tier (docs/architecture.md §10).

FAVAS[QNN]'s transmitted progress now lives as bit-packed LUQ codes +
per-(row, shard) scales all the way into the round: this file pins

* **dispatch regression** — ``cold_requant_rows`` / ``cold_dequant_rows``
  with ``use_kernel=True`` actually EXECUTE the code-emitting Pallas
  kernels (this dispatch used to be a silent no-op that fell through to
  the jnp path), and the kernel output is bit-identical to the oracle
  under the same PRNG key;
* **oracle composition** — the codes-in aggregation
  (``favas_stream_flat(progress_codes=...)``) is element-EXACT against
  ``luq_decode_rows`` -> ``favas_stream_ref`` across
  n in {7, 257} x {fp32, bf16} x bits in {2, 4, 8};
* **kernel-path parity** — the streamed kernel that dequantizes per VMEM
  tile matches the same composition to 1 fp32 ULP at accumulator scale
  (the tests/test_tiled_kernel.py bound: the kernel body compiles as one
  fused XLA computation, so FMA contraction and — on the tiled path —
  the client-reduction reorder cost at most 1 ULP of
  |server| + sum |mask * msg| per lane), including shard-segmented
  scales, lane padding, and the n=257 row-padded tiled path;
* **no dense materialization** — the compiled paged quantized round
  (``quant_fused=True``) and an isolated cold evict/promote cycle never
  define an f32/bf16 ``[population, D]`` array in their HLO
  (``launch.roofline.dense_materializations``, the §10 acceptance gate);
* **VMEM budget** — the codec term of ``stream_tile_vmem_bytes`` keeps
  the per-grid-step footprint under 2 MiB at n=1024 / D=2^20.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import round_engine
from repro.core.favas import FavasConfig, client_lambdas
from repro.core.paging import luq_decode_rows, luq_encode_rows, make_codec
from repro.kernels import ops, ref
from repro.kernels.favas_agg import stream_tile_vmem_bytes
from repro.launch.roofline import dense_materializations


def _fused_inputs(n, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    server = jax.random.normal(ks[0], (D,), dtype)
    clients = jax.random.normal(ks[1], (n, D), dtype)
    inits = jax.random.normal(ks[2], (n, D), dtype)
    alpha = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=8.0)
    mask = (jax.random.uniform(ks[4], (n,)) > 0.5).astype(jnp.float32)
    return server, clients, inits, alpha, mask, float(mask.sum())


def _encode_delta(clients, inits, bits, seed=0, shards=1):
    """The engine's transport encoding: f32 delta -> codes + scales."""
    delta = clients.astype(jnp.float32) - inits.astype(jnp.float32)
    return luq_encode_rows(delta, bits, jax.random.PRNGKey(100 + seed),
                           shards=shards)


def _oracle_round(server, clients, inits, alpha, mask, s, enc, bits,
                  shards=1):
    """The §10 reference composition: decode to dense f32, run the ref."""
    prog = luq_decode_rows(enc, bits, jnp.float32, shards=shards)
    return ref.favas_stream_ref(server, clients, inits, alpha, mask, s,
                                progress=prog)


def _assert_exact(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _assert_ulp_bounded(got, want, server, inits, alpha, mask, s, enc, bits,
                        shards=1):
    """Kernel-path bound on the new server row: 2 fp32 ULPs of the
    per-lane accumulator magnitude |server| + sum_i |mask_i * msg_i|,
    scaled by the 1/(s+1) division —
    the test_tiled_kernel.py idiom with one extra ULP of budget. The
    kernel body is one fused XLA computation, so vs the op-by-op oracle it
    pays (a) FMA contraction of the in-VMEM dequant + msg expressions
    (<= 1 ULP of the contribution) and (b) — on the tiled path — the
    client-reduction reorder (<= 1 ULP of the accumulator)."""
    prog = np.asarray(luq_decode_rows(enc, bits, jnp.float32,
                                      shards=shards), np.float64)
    msg = (np.asarray(inits, np.float64)
           + prog / np.asarray(alpha, np.float64)[:, None])
    acc_scale = (np.abs(np.asarray(server, np.float64))
                 + np.sum(np.abs(np.asarray(mask, np.float64)[:, None] * msg),
                          axis=0))
    ulp = 2.0 * np.spacing(acc_scale.astype(np.float32)) / (s + 1.0)
    assert got.dtype == want.dtype and got.shape == want.shape
    d = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    assert np.all(d <= ulp), float((d / ulp).max())


# ---------------------------------------------------------------------------
# Dispatch regression: use_kernel=True executes the Pallas codec
# ---------------------------------------------------------------------------

def test_requant_use_kernel_true_executes_pallas(monkeypatch):
    """``cold_requant_rows(use_kernel=True)`` must dispatch
    ``kernels.luq.luq_encode_pallas`` (patched at the ``ops`` import site —
    the bug this pins was exactly a dispatch that never reached it), and
    the kernel encoding must be bit-identical to the jnp oracle under the
    same key."""
    calls = []
    real = ops.luq_encode_pallas

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(ops, "luq_encode_pallas", spy)
    x = jax.random.normal(jax.random.PRNGKey(0), (7, 1024), jnp.float32)
    key = jax.random.PRNGKey(42)
    enc_k = ops.cold_requant_rows(x, 4, key, use_kernel=True)
    assert calls, "use_kernel=True never reached luq_encode_pallas"
    enc_o = ops.cold_requant_rows(x, 4, key, use_kernel=False)
    assert enc_k["codes"].dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(enc_k["codes"]),
                                  np.asarray(enc_o["codes"]))
    np.testing.assert_array_equal(np.asarray(enc_k["scale"]),
                                  np.asarray(enc_o["scale"]))


def test_dequant_use_kernel_true_executes_pallas(monkeypatch):
    calls = []
    real = ops.luq_decode_pallas

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(ops, "luq_decode_pallas", spy)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 1024), jnp.float32)
    enc = ops.cold_requant_rows(x, 4, jax.random.PRNGKey(2),
                                use_kernel=False)
    dec_k = ops.cold_dequant_rows(enc, 4, jnp.float32, use_kernel=True)
    assert calls, "use_kernel=True never reached luq_decode_pallas"
    dec_o = ops.cold_dequant_rows(enc, 4, jnp.float32, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dec_k), np.asarray(dec_o))


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shards", [1, 2])
def test_requant_kernel_oracle_bit_identical(bits, shards):
    """Both eviction-path encoders draw the SAME (rows, D) uniform fields
    from the key, so the packed codes and scales agree bit for bit at
    every width and shard count (rows not a multiple of ENC_ROWS: the
    kernel's row padding must not leak)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (11, 2048), jnp.float32)
    key = jax.random.PRNGKey(9 + bits)
    enc_k = ops.cold_requant_rows(x, bits, key, shards=shards,
                                  use_kernel=True)
    enc_o = ops.cold_requant_rows(x, bits, key, shards=shards,
                                  use_kernel=False)
    np.testing.assert_array_equal(np.asarray(enc_k["codes"]),
                                  np.asarray(enc_o["codes"]))
    np.testing.assert_array_equal(np.asarray(enc_k["scale"]),
                                  np.asarray(enc_o["scale"]))
    # and the decoders invert identically
    dec_k = ops.cold_dequant_rows(enc_k, bits, jnp.float32, shards=shards,
                                  use_kernel=True)
    dec_o = ops.cold_dequant_rows(enc_o, bits, jnp.float32, shards=shards,
                                  use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dec_k), np.asarray(dec_o))


# ---------------------------------------------------------------------------
# Codes-in round: oracle composition (element-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("n", [7, 257])
def test_codes_in_oracle_composition_exact(n, dtype, bits):
    """``favas_stream_flat(progress_codes=..., use_kernel=False)`` ==
    decode -> ``favas_stream_ref``, element for element: the codes-in round
    is the SAME mathematical round, only the transport changed."""
    D = 1000
    server, clients, inits, alpha, mask, s = _fused_inputs(
        n, D, dtype, seed=n + bits)
    enc = _encode_delta(clients, inits, bits, seed=bits)
    got = ops.favas_stream_flat(server, clients, inits, alpha, mask, s,
                                progress_codes=enc, progress_bits=bits,
                                use_kernel=False)
    want = _oracle_round(server, clients, inits, alpha, mask, s, enc, bits)
    _assert_exact(got, want)


def test_codes_in_rejects_dense_progress_too():
    server, clients, inits, alpha, mask, s = _fused_inputs(
        4, 256, jnp.float32)
    enc = _encode_delta(clients, inits, 4)
    with pytest.raises(ValueError, match="mutually exclusive"):
        ops.favas_stream_flat(server, clients, inits, alpha, mask, s,
                              progress=clients - inits, progress_codes=enc,
                              progress_bits=4, use_kernel=False)


# ---------------------------------------------------------------------------
# Codes-in round: kernel path (per-VMEM-tile dequant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_codes_in_kernel_resident_parity(dtype, bits):
    """Resident path (n <= CLIENT_TILE): the in-kernel dequant
    (``dequant_block``, mirroring ``luq_decode_rows``
    expression-for-expression) composed with the resident-order client
    reduction stays within the 1-ULP accumulator bound of the oracle
    composition."""
    n, D = 7, 2048
    server, clients, inits, alpha, mask, s = _fused_inputs(
        n, D, dtype, seed=bits)
    enc = _encode_delta(clients, inits, bits, seed=bits)
    got = ops.favas_stream_flat(server, clients, inits, alpha, mask, s,
                                progress_codes=enc, progress_bits=bits,
                                use_kernel=True)
    want = _oracle_round(server, clients, inits, alpha, mask, s, enc, bits)
    _assert_ulp_bounded(got, want, server, inits, alpha, mask, s, enc, bits)


def test_codes_in_kernel_sharded_scales_parity():
    """progress_shards > 1: each lane segment dequantizes against its own
    scale column — the layout the §6 mesh path slices per device."""
    n, D, bits, shards = 7, 4096, 4, 2
    server, clients, inits, alpha, mask, s = _fused_inputs(
        n, D, jnp.float32, seed=5)
    enc = _encode_delta(clients, inits, bits, shards=shards)
    got = ops.favas_stream_flat(server, clients, inits, alpha, mask, s,
                                progress_codes=enc, progress_bits=bits,
                                progress_shards=shards, use_kernel=True)
    want = _oracle_round(server, clients, inits, alpha, mask, s, enc, bits,
                         shards=shards)
    _assert_ulp_bounded(got, want, server, inits, alpha, mask, s, enc, bits,
                        shards=shards)


def test_codes_in_kernel_lane_padding_parity():
    """D not a multiple of TILE: the padded code bytes are zero, zero codes
    decode to exact zeros, so the lane tail stays a no-op through the
    codec (the same invariant the dense operands rely on)."""
    n, D, bits = 7, 300, 4
    server, clients, inits, alpha, mask, s = _fused_inputs(
        n, D, jnp.float32, seed=7)
    enc = _encode_delta(clients, inits, bits)
    got = ops.favas_stream_flat(server, clients, inits, alpha, mask, s,
                                progress_codes=enc, progress_bits=bits,
                                use_kernel=True)
    want = _oracle_round(server, clients, inits, alpha, mask, s, enc, bits)
    _assert_ulp_bounded(got, want, server, inits, alpha, mask, s, enc, bits)


def test_codes_in_kernel_tiled_ulp_at_accumulator_scale():
    """Tiled path (n > CLIENT_TILE, row padding at n=257): adds the
    client-reduction reorder on top of the dequant contraction — still
    within the shared accumulator-scale ULP budget."""
    n, D, bits = 257, 2048, 4
    server, clients, inits, alpha, mask, s = _fused_inputs(
        n, D, jnp.float32, seed=13)
    enc = _encode_delta(clients, inits, bits)
    got = ops.favas_stream_flat(server, clients, inits, alpha, mask, s,
                                progress_codes=enc, progress_bits=bits,
                                use_kernel=True)
    want = _oracle_round(server, clients, inits, alpha, mask, s, enc, bits)
    _assert_ulp_bounded(got, want, server, inits, alpha, mask, s, enc, bits)


# ---------------------------------------------------------------------------
# VMEM budget: the codec term
# ---------------------------------------------------------------------------

def test_codes_in_vmem_budget_production_shape():
    """Acceptance: n=1024, D=2^20, fp32, every width — the per-grid-step
    footprint with the packed-codes + scale blocks stays under 2 MiB, and
    below the dense-progress operand it replaces."""
    for bits in (2, 4, 8):
        total = stream_tile_vmem_bytes(1024, jnp.float32, codec_bits=bits)
        assert total <= 2 * 1024 ** 2, (bits, total)
        assert total < stream_tile_vmem_bytes(1024, jnp.float32,
                                              progress=True)
    with pytest.raises(ValueError, match="mutually exclusive"):
        stream_tile_vmem_bytes(1024, jnp.float32, progress=True,
                               codec_bits=4)


# ---------------------------------------------------------------------------
# Engine integration: quant_fused transport
# ---------------------------------------------------------------------------

def _params():
    w = jnp.asarray(np.linspace(-1.0, 1.0, 256).reshape(16, 16), jnp.float32)
    b = jnp.asarray(np.linspace(0.5, 1.5, 5), jnp.float32)
    return {"w": w, "b": b}


def _loss(p, batch):
    return sum(jnp.mean((l.astype(jnp.float32) - batch["t"]) ** 2)
               for l in jax.tree_util.tree_leaves(p))


def _batches(fcfg, T, seed=0):
    vals = np.linspace(0.0, 1.0, T * fcfg.n_clients * fcfg.R) + 0.01 * seed
    return {"t": jnp.asarray(vals.reshape(T, fcfg.n_clients, fcfg.R),
                             jnp.float32)}


def _quant_engine(n, *, use_kernel, quant_fused, **paging):
    params = _params()
    fcfg = FavasConfig(n_clients=n, s_selected=max(n // 10, 2),
                       local_steps=2, eta=0.1, quant_bits=4)
    eng = round_engine.RoundEngine(
        params, fcfg, _loss, lambdas=jnp.asarray(client_lambdas(fcfg)),
        use_kernel=use_kernel, quant_fused=quant_fused, **paging)
    return eng, fcfg, params


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["oracle", "kernel"])
def test_engine_quant_fused_runs_finite_paged(use_kernel):
    """End to end on the paged engine: codes-in transport + LUQ cold pools
    + the Pallas codec path all composed, several rounds, finite loss and
    finite hot state."""
    eng, fcfg, params = _quant_engine(10, use_kernel=use_kernel,
                                      quant_fused=True, residency="paged",
                                      s_max=4, cold_bits=4)
    state = eng.init_state(params, jax.random.PRNGKey(6))
    state, ms = eng.run(state, _batches(fcfg, 3))
    assert np.all(np.isfinite(np.asarray(ms["loss"])))
    for c in state.clients:
        assert np.all(np.isfinite(np.asarray(c, np.float32)))


def test_engine_quant_fused_matches_unfused_quantization_level():
    """quant_fused changes the TRANSPORT, not the statistics: a dense
    engine with codes-in transport stays finite and close to the tree-space
    quantized engine (different PRNG streams -> not bit-equal, but the
    same 4-bit unbiased noise scale)."""
    T = 5
    fused, fcfg, params = _quant_engine(7, use_kernel=False,
                                        quant_fused=True)
    tree, _, _ = _quant_engine(7, use_kernel=False, quant_fused=False)
    key = jax.random.PRNGKey(8)
    sf, mf = fused.run(fused.init_state(params, key), _batches(fcfg, T))
    st, mt = tree.run(tree.init_state(params, key), _batches(fcfg, T))
    lf = np.asarray(mf["loss"])
    lt = np.asarray(mt["loss"])
    assert np.all(np.isfinite(lf)) and np.all(np.isfinite(lt))
    np.testing.assert_allclose(lf, lt, rtol=0.15)


# ---------------------------------------------------------------------------
# HLO gates: no dense (population, D) float materialization
# ---------------------------------------------------------------------------

def test_hlo_gate_paged_quant_round_never_densifies_population():
    """Compile the FULL paged quantized round (codes-in transport) at
    n=40 / s_max=16 and census the HLO: no op may define an f32/bf16
    [40, >=128] array. The hot stacks legitimately live at s_max rows;
    the full population exists only as uint8 code pools + narrow scale
    columns. (Feature dims are kept < 128 so batch inputs can't trip the
    gate — only a dense decode of the population could.)"""
    n, s_max = 40, 16
    eng, fcfg, params = _quant_engine(n, use_kernel=False, quant_fused=True,
                                      residency="paged", s_max=s_max,
                                      cold_bits=4)
    state = eng.init_state(params, jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(lambda x: x[0], _batches(fcfg, 1))
    hlo = eng._round.lower(state, batch).compile().as_text()
    dense = dense_materializations(hlo, rows=n)
    assert dense == [], (
        "compiled paged round materializes the full population densely: "
        f"{dense[:5]}")


def test_hlo_gate_cold_cycle_touches_churn_rows_only():
    """An isolated jitted evict/promote cycle (gather s_churn rows ->
    decode_pair -> encode_pair -> scatter back) over an n=40-row LUQ pool:
    the compiled program defines dense float arrays at the CHURN row count
    only — never at the pool population (40) nor the full working set
    (16). A decode of the whole pool would be the §10 bug reborn at the
    residency layer."""
    n, s_max, s_churn, D = 40, 16, 4, 256
    codec = make_codec(4)
    cli = jax.random.normal(jax.random.PRNGKey(1), (n, D), jnp.float32)
    ini = jax.random.normal(jax.random.PRNGKey(2), (n, D), jnp.float32)
    pool = codec.encode_pair(cli, ini, jax.random.PRNGKey(3),
                             use_kernel=False)

    def cycle(pool, idx, key):
        rows = jax.tree_util.tree_map(lambda p: p[idx], pool)
        c, i = codec.decode_pair(rows, jnp.float32, use_kernel=False)
        enc = codec.encode_pair(c, i, key, use_kernel=False)
        return jax.tree_util.tree_map(
            lambda p, e: p.at[idx].set(e.astype(p.dtype)), pool, enc)

    idx = jnp.arange(s_churn)
    hlo = (jax.jit(cycle)
           .lower(pool, idx, jax.random.PRNGKey(4)).compile().as_text())
    for rows in (n, s_max):
        dense = dense_materializations(hlo, rows=rows)
        assert dense == [], (rows, dense[:5])
    # the cycle is not a no-op: the churn rows' floats DO materialize
    assert dense_materializations(hlo, rows=s_churn), (
        "gate sanity: the churn-row decode should be visible in the HLO")
