"""The resident streamed kernel's wide lane blocks (docs/architecture.md §13).

With no transmitted progress, ``favas_stream_pallas`` reads the resident
(n, D) stacks in lane blocks ``stream_width`` lanes wide instead of TILE:
per-lane arithmetic is unchanged, so the new server row equals the
TILE-wide kernel's bit for bit at any width, with a partial last block
where the width does not divide D. What is proven, in interpret mode on
the CPU:

* parity — the wide kernel against the TILE-wide kernel, bit for bit, and
  against the masked oracle ``favas_stream_ref`` to one ULP of |server| +
  sum_i |m_i msg_i| per lane before the 1/(s+1) division, plus one ULP of
  the result. Cases: 1 to 32 rows, s in {1, 2, 3}, f32 and bf16 stacks,
  unaligned D and widths whose last block is partial (D / TILE prime);
* an engine whose streamed rounds run the wide kernel gives the states the
  TILE-wide kernel gives, dense and paged, bit for bit;
* the VMEM budget — ``stream_width`` keeps the double-buffered blocks
  (``stream_block_vmem_bytes``) inside the budget for every resident n,
  narrowing the blocks as n grows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import round_engine
from repro.core.favas import FavasConfig, client_lambdas
from repro.kernels import favas_agg
from repro.kernels.favas_agg import (CLIENT_TILE, STREAM_MAX_WIDTH,
                                     STREAM_VMEM_BUDGET, TILE,
                                     favas_stream_pallas,
                                     stream_block_vmem_bytes, stream_width)
from repro.kernels.ref import favas_stream_ref

RAGGED = TILE * 37      # 37 is prime: no block wider than TILE divides it
CELL_WIDTH = 52_066_304    # padded f32 bucket of the mamba2-1.3b fan-in cell


def _inputs(rows, D, dtype, selected, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    server = jax.random.normal(ks[0], (D,), jnp.float32).astype(dtype)
    clients = jax.random.normal(ks[1], (rows, D), jnp.float32).astype(dtype)
    inits = jax.random.normal(ks[2], (rows, D), jnp.float32).astype(dtype)
    alpha = jax.random.uniform(ks[3], (rows,), minval=0.25, maxval=4.0)
    mask = jnp.zeros(rows).at[jnp.asarray(selected, jnp.int32)].set(1.0)
    return server, clients, inits, alpha, mask


# (rows, dtype, D, selected rows, lane cap): the cap narrows the blocks so
# that a small D still spans several, the last one partial
CASES = {
    "r1-s1": (1, jnp.float32, 2500, [0], STREAM_MAX_WIDTH),
    "r8-s1-ragged": (8, jnp.float32, RAGGED, [5], STREAM_MAX_WIDTH),
    "r8-s2-unsorted-ragged": (8, jnp.float32, RAGGED, [6, 1],
                              STREAM_MAX_WIDTH),
    "r8-s3-capped": (8, jnp.float32, RAGGED, [7, 0, 3], 4 * TILE),
    "r5-s2-unaligned": (5, jnp.float32, 4 * TILE + 77, [4, 2], 2 * TILE),
    "r32-s3-ragged": (32, jnp.float32, RAGGED, [13, 30, 9],
                      STREAM_MAX_WIDTH),
    "r8-s2-bf16-ragged": (8, jnp.bfloat16, RAGGED, [4, 2], STREAM_MAX_WIDTH),
    "r32-s3-bf16-capped": (32, jnp.bfloat16, RAGGED, [31, 2, 16], 3 * TILE),
}


@pytest.mark.parametrize("case", list(CASES))
def test_wide_stream_matches_tile_kernel_and_oracle(case, monkeypatch):
    rows, dtype, D, selected, cap = CASES[case]
    s = len(selected)
    server, clients, inits, alpha, mask = _inputs(rows, D, dtype, selected,
                                                  seed=len(case))
    monkeypatch.setattr(favas_agg, "STREAM_MAX_WIDTH", cap)
    Dp = D + (-D) % TILE
    width = stream_width(rows, dtype, Dp)
    assert TILE < width <= cap and (Dp % width or Dp == width)
    got = favas_stream_pallas(server, clients, inits, alpha, mask, s,
                              interpret=True)
    assert got.dtype == server.dtype and got.shape == (D,)
    monkeypatch.setattr(favas_agg, "STREAM_MAX_WIDTH", TILE)
    assert stream_width(rows, dtype, Dp) == TILE
    narrow = favas_stream_pallas(server, clients, inits, alpha, mask, s,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(narrow, np.float32))
    want = favas_stream_ref(server, clients, inits, alpha, mask, s)
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    c, i = (np.asarray(x, np.float64) for x in (clients, inits))
    m = np.asarray(mask, np.float64)[:, None]
    msg = i + (c - i) / np.asarray(alpha, np.float64)[:, None]
    acc = (np.abs(np.asarray(server, np.float64))
           + np.sum(np.abs(m * msg), axis=0))
    ulp = (np.spacing(acc.astype(np.float32)) / (s + 1.0)
           + np.spacing(np.abs(want).astype(dtype)).astype(np.float64))
    assert np.all(np.abs(got - want) <= ulp), \
        float((np.abs(got - want) / ulp).max())


def test_transmitted_progress_keeps_tile_blocks(monkeypatch):
    """The ``progress`` variant keeps its TILE-wide blocks: it never
    consults the width function."""
    server, clients, inits, alpha, mask = _inputs(8, RAGGED, jnp.float32,
                                                  [3, 6], seed=3)
    progress = clients - inits
    want = favas_stream_pallas(server, clients, inits, alpha, mask, 2,
                               progress=progress, interpret=True)

    def refuse(*a, **k):
        raise AssertionError("stream_width consulted")

    monkeypatch.setattr(favas_agg, "stream_width", refuse)
    got = favas_stream_pallas(server, clients, inits, alpha, mask, 2,
                              progress=progress, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# the engine's streamed rounds
# --------------------------------------------------------------------------

def _params():
    # buckets of 3 TILEs (one bf16, one f32), so 2-TILE blocks leave a
    # partial last block
    w = jnp.asarray(np.linspace(-1.0, 1.0, 5000).reshape(50, 100),
                    jnp.bfloat16)
    b = jnp.asarray(np.linspace(0.5, 1.5, 5000), jnp.float32)
    return {"w": w, "b": b}


def _loss(p, batch):
    return sum(jnp.mean((l.astype(jnp.float32) - batch["t"]) ** 2)
               for l in jax.tree_util.tree_leaves(p))


@pytest.mark.parametrize("n,kw", [(8, {}),
                                  (9, {"residency": "paged", "s_max": 4})],
                         ids=["dense", "paged"])
def test_engine_wide_stream_matches_tile_kernel(n, kw, monkeypatch):
    """Three streamed rounds through the kernel (interpret mode): 2-TILE
    blocks give the states TILE blocks give, bit for bit."""
    fcfg = FavasConfig(n_clients=n, s_selected=2, local_steps=2, eta=0.1)
    vals = np.linspace(0.0, 1.0, 3 * fcfg.n_clients * fcfg.R)
    batches = {"t": jnp.asarray(vals.reshape(3, fcfg.n_clients, fcfg.R),
                                jnp.float32)}

    def run(cap):
        monkeypatch.setattr(favas_agg, "STREAM_MAX_WIDTH", cap)
        eng = round_engine.RoundEngine(
            _params(), fcfg, _loss,
            lambdas=jnp.asarray(client_lambdas(fcfg)), use_kernel=True,
            **kw)
        assert eng.spec.bucket_padded == (3 * TILE, 3 * TILE)
        state, _ = eng.run(eng.init_state(_params(), jax.random.PRNGKey(5)),
                           batches)
        return state

    wide, narrow = run(2 * TILE), run(TILE)
    for x, y in zip(wide.server + wide.clients + wide.inits,
                    narrow.server + narrow.clients + narrow.inits):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# --------------------------------------------------------------------------
# VMEM budget
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_width_vmem_budget(dtype):
    """Every resident n at the fan-in cell's width: the lane blocks are
    multiples of TILE, narrow as n grows, and keep the kernel's VMEM
    inside the budget, itself inside the 16 MiB of scoped VMEM a v5e
    kernel gets by default (the kernel sets no limit of its own)."""
    assert STREAM_VMEM_BUDGET <= 16 << 20
    widths = []
    for n in range(1, CLIENT_TILE + 1):
        w = stream_width(n, dtype, CELL_WIDTH)
        assert w % TILE == 0 and TILE <= w <= STREAM_MAX_WIDTH
        assert stream_block_vmem_bytes(n, dtype, w) <= STREAM_VMEM_BUDGET
        widths.append(w)
    assert widths == sorted(widths, reverse=True) and widths[-1] < widths[0]
    # never wider than D
    assert stream_width(8, dtype, 3 * TILE) == 3 * TILE
    # the fan-in cell: two copies of the (8, W) client and init blocks, of
    # the (1, W) server and new-server rows and of the (8, 1) alpha and mask
    # columns, two (8, W) f32 values and the (8, W) total
    w = stream_width(8, jnp.float32, CELL_WIDTH)
    assert w == STREAM_MAX_WIDTH
    assert stream_block_vmem_bytes(8, jnp.float32, w) == (
        2 * 2 * 8 * w * 4 + 2 * 2 * w * 4 + 2 * 2 * 8 * 128 * 4
        + 3 * 8 * w * 4)
