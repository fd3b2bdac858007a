"""Pallas TPU kernel: fused FAVAS server aggregation (Algorithm 1 line 10 +
eq. 3 reweighting) over flattened parameter buffers.

Why a kernel: the aggregation touches every byte of every resident client's
parameters each server round and is purely memory-bound. Unfused HLO does
4+ passes per leaf (sub, div, add, mul-mask, reduce); this kernel streams
each (CLIENT_TILE, TILE) block through VMEM once per sweep: one HBM read
per operand per sweep, one write.

Tiling. The lane dim is tiled at ``TILE`` (multiple of 128 for clean
(8, 128) vreg tiling). The client dim rides the sublane axis and is tiled
at ``CLIENT_TILE`` rows: a second grid dimension streams client-row blocks
through a VMEM scratch accumulator, so the number of resident clients ``n``
can scale to thousands while VMEM stays bounded at O(CLIENT_TILE * TILE).
For ``n <= CLIENT_TILE`` the whole client axis fits one block and the
single-sweep resident kernels below are used unchanged.

VMEM budget for the tiled fused kernel @ TILE=2048, CLIENT_TILE=32, fp32,
independent of n and D: in blocks (2*CT+1)*TILE*4B + (CT,1) scalars, out
blocks (2*CT+1)*TILE*4B, two (1, TILE) f32 scratch rows — about 1.03 MiB
total (1.29 MiB with the explicit-progress operand), comfortably inside
~16 MiB VMEM even with double buffering. ``fused_block_vmem_bytes`` computes
this number from the declared block shapes; tests pin it under 2 MiB for
the production shape (n=1024, D=2^20). The resident small-n kernels keep the
PR-1 budget: (2n+1)*TILE*4B in + out ≈ 2.1 MiB at n=64 — except the plain
streamed kernel, whose lane blocks widen to fill ``STREAM_VMEM_BUDGET``
(``stream_width``, ``stream_block_vmem_bytes``).

Grid schedule of the tiled fused kernel, for each lane tile i (outer grid
dim, "arbitrary" sequential semantics):

* phase 0 (inner grid steps j = 0..nb-1): client block j streams through
  VMEM; its masked message partial sum accumulates into a (1, TILE) f32
  scratch row; the clients/inits out tiles pass the inputs through (already
  final for unselected rows). A ``@pl.when`` epilogue on the last client
  block folds in the server row and stores the new server tile to a second
  scratch row and to the server output.
* phase 1 (j = nb..2*nb-1): client block j-nb streams through again and the
  per-block client/init reset tiles are emitted from the scratch server row
  (line 11-12 selects between the new server and the untouched state).

So the round moves 2 HBM reads + 2 writes per resident client byte at any
n — versus the seed's ~6 passes, and versus 1+1 for the resident small-n
kernel (which remains the dispatch below CLIENT_TILE).

``favas_agg_pallas`` (the original single-output aggregation, kept for the
leafwise ``ops.favas_aggregate_tree`` path) needs no reset phase, so its
tiled variant is a single sweep: accumulate, then one ``@pl.when`` epilogue
emits the server tile once the last client block has streamed through.

The client axis is padded to a CLIENT_TILE multiple with zero rows, zero
mask and unit alpha, so padded rows contribute exactly 0.0 to the masked
sum (adding 0.0 is exact in fp32 — no parity impact). The flat-buffer
engine (``core/round_engine.py``) pre-pads both axes so the kernel path
never re-pads.

Validated with interpret=True on CPU against ``ref.favas_agg_ref`` /
``ref.favas_fused_ref``: the kernel body uses the same jnp expressions
(including true division) as the oracle. The resident kernels reduce over
the same (n, TILE) block as the oracle, so fp32 parity holds to 1 ULP; the
tiled kernels accumulate per-block partial sums sequentially, which
reorders the client reduction — parity then holds to ~1 ULP *of the
accumulator magnitude* (tests bound |kernel - oracle| by ULPs of
|server| + sum_i |mask_i * msg_i| per lane, before the 1/(s+1) division).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.luq import LANES, dequant_block, lane_scales

TILE = 2048        # lane-dim tile; multiple of 128
CLIENT_TILE = 32   # sublane-dim tile over client rows; multiple of 8


def _pad_clients(n: int, client_tile: int, arrays, alpha, mask):
    """Zero-pad the client axis to a CLIENT_TILE multiple: zero rows, zero
    mask, unit alpha — exact no-ops under the masked sum."""
    rpad = (-n) % client_tile
    if rpad:
        arrays = [a if a is None else jnp.pad(a, ((0, rpad), (0, 0)))
                  for a in arrays]
        alpha = jnp.pad(alpha, (0, rpad), constant_values=1.0)
        mask = jnp.pad(mask, (0, rpad))
    return n + rpad, arrays, alpha, mask


def _pad_codes(codes, bits: int, pad: int):
    """Lane-pad packed progress codes by ``pad`` zero codes (zero codes
    decode to exact zeros, matching the zero-padded dense operands). The
    padded row is re-packed through the storage layout: an unaligned row
    is one packing group (``kernels.luq.pack_group``), the padded row is
    not. The engine's buffers are tile-aligned and never take this path."""
    from repro.core.paging import pack_codes, unpack_codes  # lazy: no cycle
    return pack_codes(jnp.pad(unpack_codes(codes, bits), ((0, 0), (0, pad))),
                      bits)


def fused_block_vmem_bytes(n: int, dtype, *, progress: bool = False,
                           codec_bits: int = 0, tile: int = TILE,
                           client_tile: int = CLIENT_TILE,
                           schedule: str = "two_sweep",
                           double_buffered: bool = False) -> int:
    """Per-grid-step VMEM footprint of ``favas_fused_pallas`` computed from
    the declared BlockSpec shapes (inputs + outputs + scratch). For the
    tiled path (n > client_tile) this is independent of both n and D —
    the property that lets the engine scale to thousands of clients.

    ``codec_bits`` > 0 accounts the CODES-IN progress operand instead of a
    dense row block: a bit-packed (rows, tile*bits/8) uint8 codes block
    plus a (rows, 128) f32 lane-broadcast scale block — the codec term of docs/
    architecture.md §10. At n=1024/fp32/bits=8 the total stays ~1.1 MiB
    (vs 1.29 MiB for the dense-progress operand), pinned < 2 MiB by
    tests/test_quant_fused.py.

    ``schedule="streamed"`` accounts the single-sweep aggregation-only
    kernel (``favas_stream_pallas``, docs/architecture.md §13): no
    client/init out blocks (the churn-bounded reset happens outside the
    kernel) and a single f32 accumulator scratch row. ``double_buffered``
    makes the pipeline's double buffering EXPLICIT in the budget: the grid
    pipeline keeps two copies of every in/out block resident (fetching
    block j+1 while block j computes), so the honest peak footprint is
    2x the block bytes (scratch rows are not pipelined and stay single).
    The default (two_sweep, single-buffer) keeps the historical number
    that tests pin."""
    if progress and codec_bits:
        raise ValueError("progress and codec_bits are mutually exclusive")
    if schedule not in ("two_sweep", "streamed"):
        raise ValueError(f"unknown schedule {schedule!r}")
    itemsize = jnp.dtype(dtype).itemsize
    rows = min(n, client_tile)
    row_block = rows * tile * itemsize          # clients / inits / progress
    srv_block = tile * itemsize                 # (1, TILE) server row
    scalar_block = rows * 4                     # (rows, 1) f32 alpha / mask
    n_row_in = 3 if progress else 2
    inputs = srv_block + n_row_in * row_block + 2 * scalar_block
    if codec_bits:
        inputs += rows * tile * codec_bits // 8  # packed progress codes
        inputs += rows * LANES * 4               # (rows, 128) f32 scale block
    if schedule == "streamed":
        outputs = srv_block                      # server row only
        scratch = tile * 4 if n > client_tile else 0      # f32 acc
    else:
        outputs = srv_block + 2 * row_block      # server + client/init tiles
        scratch = 2 * tile * 4 if n > client_tile else 0  # acc + new-server
    if double_buffered:
        inputs, outputs = 2 * inputs, 2 * outputs
    return inputs + outputs + scratch


STREAM_VMEM_BUDGET = 12 << 20  # of a v5e kernel's 16 MiB of scoped VMEM
STREAM_MAX_WIDTH = 1 << 15      # lanes per block: wider gains nothing


def stream_block_vmem_bytes(n: int, dtype, width: int) -> int:
    """VMEM footprint of the resident streamed kernel (``n`` rows, no
    transmitted progress) at lane width ``width``: the pipeline's two
    copies of the (n, width) client and init blocks (rows padded to the
    dtype's sublane tiling), of the (1, width) server and new-server rows
    (one sublane, as the (1, D) row is tiled in HBM; at most 4 bytes a
    lane) and of the (n, 1) alpha and mask columns (lanes padded to 128);
    and the f32 values Mosaic keeps in VMEM for the sum, at most two
    (rows padded to 8, width) blocks and one 8-sublane block for the
    total. A compile for a described v5e reports no more scoped VMEM than
    this (tests/test_chip_compile.py)."""
    itemsize = jnp.dtype(dtype).itemsize
    tile_rows = 8 * 4 // itemsize
    padded = -(-n // tile_rows) * tile_rows
    stacks = 2 * 2 * padded * width * itemsize
    rows = 2 * 2 * width * 4
    cols = 2 * 2 * -(-n // 8) * 8 * LANES * 4
    sums = (2 * -(-n // 8) * 8 + 8) * width * 4
    return stacks + rows + cols + sums


def stream_width(n: int, dtype, D: int) -> int:
    """Lane width of the resident streamed kernel's blocks: the widest
    multiple of TILE up to ``STREAM_MAX_WIDTH`` (and no wider than D
    rounded up to TILE) whose blocks fit ``STREAM_VMEM_BUDGET``, and never
    below TILE. The last block may be partial: Pallas reads unspecified
    lanes past the array's end and drops their writes."""
    fixed = stream_block_vmem_bytes(n, dtype, 0)
    per_lane = stream_block_vmem_bytes(n, dtype, 1) - fixed
    width = min(STREAM_MAX_WIDTH, -(-D // TILE) * TILE,
                (STREAM_VMEM_BUDGET - fixed) // per_lane)
    return max(TILE, width // TILE * TILE)


# ---------------------------------------------------------------------------
# Single-output aggregation (ops.favas_aggregate_tree path)
# ---------------------------------------------------------------------------

def _agg_kernel(server_ref, clients_ref, inits_ref, coef_ref, mask_ref, out_ref,
                *, inv_s1: float):
    """One resident (n, TILE) block.
    coef = mask/alpha (n,1); mask (n,1); server/out (1, TILE)."""
    c = clients_ref[...].astype(jnp.float32)          # (n, T)
    i = inits_ref[...].astype(jnp.float32)            # (n, T)
    coef = coef_ref[...].astype(jnp.float32)          # (n, 1)
    m = mask_ref[...].astype(jnp.float32)             # (n, 1)
    # sum_i [ mask*init + (mask/alpha)*(client-init) ]
    total = jnp.sum(m * i + coef * (c - i), axis=0, keepdims=True)
    s = server_ref[...].astype(jnp.float32)           # (1, T)
    out_ref[...] = ((s + total) * inv_s1).astype(out_ref.dtype)


def _agg_kernel_tiled(server_ref, clients_ref, inits_ref, coef_ref, mask_ref,
                      out_ref, acc_ref, *, inv_s1: float, n_blocks: int):
    """One (CLIENT_TILE, TILE) client block; partial sums accumulate in the
    f32 scratch row, the epilogue emits the server tile after the last
    client block has streamed through."""
    j = pl.program_id(1)
    c = clients_ref[...].astype(jnp.float32)          # (CT, T)
    i = inits_ref[...].astype(jnp.float32)            # (CT, T)
    coef = coef_ref[...].astype(jnp.float32)          # (CT, 1)
    m = mask_ref[...].astype(jnp.float32)             # (CT, 1)
    part = jnp.sum(m * i + coef * (c - i), axis=0, keepdims=True)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _():
        acc_ref[...] = acc_ref[...] + part

    @pl.when(j == n_blocks - 1)
    def _():
        s = server_ref[...].astype(jnp.float32)       # (1, T)
        out_ref[...] = ((s + acc_ref[...]) * inv_s1).astype(out_ref.dtype)


def favas_agg_pallas(server, clients, inits, alpha, mask, s: float,
                     *, client_tile: int | None = None,
                     interpret: bool = False):
    """Single-output FAVAS aggregation kernel (Algorithm 1 line 10 + eq. 3).

    Args:
      server: (D,) f32/bf16 current server vector.
      clients / inits: (n, D) stacked client / last-reset buffers.
      alpha: (n,) eq. 3 reweight coefficients (clamped at 1e-9).
      mask: (n,) 0/1 selection mask for this round's polled set.
      s: |S_t| — the aggregation divides by ``s + 1``.
      client_tile: sublane rows per client block (default ``CLIENT_TILE``);
        ``n <= client_tile`` keeps the whole client axis resident in one
        block, larger n streams blocks through the VMEM accumulator.
      interpret: run the kernel in Pallas interpret mode (CPU validation);
        the default compiles it for the TPU.

    Returns the (D,) new server vector in the server's dtype. Lane padding
    to ``TILE`` happens here if D is unaligned (the flat-buffer engine
    pre-pads so this is a no-op on the engine path)."""
    n, D = clients.shape
    ct = client_tile or CLIENT_TILE
    pad = (-D) % TILE
    if pad:
        server = jnp.pad(server, (0, pad))
        clients = jnp.pad(clients, ((0, 0), (0, pad)))
        inits = jnp.pad(inits, ((0, 0), (0, pad)))
    Dp = D + pad
    if n <= ct:                                   # whole client axis resident
        coef = (mask / jnp.maximum(alpha, 1e-9)).astype(jnp.float32).reshape(n, 1)
        maskc = mask.astype(jnp.float32).reshape(n, 1)
        out = pl.pallas_call(
            functools.partial(_agg_kernel, inv_s1=1.0 / (s + 1.0)),
            grid=(Dp // TILE,),
            in_specs=[
                pl.BlockSpec((1, TILE), lambda i: (0, i)),    # server (as (1,D))
                pl.BlockSpec((n, TILE), lambda i: (0, i)),    # clients
                pl.BlockSpec((n, TILE), lambda i: (0, i)),    # inits
                pl.BlockSpec((n, 1), lambda i: (0, 0)),       # coef
                pl.BlockSpec((n, 1), lambda i: (0, 0)),       # mask
            ],
            out_specs=pl.BlockSpec((1, TILE), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, Dp), server.dtype),
            interpret=interpret,
            name="favas_agg",
        )(server.reshape(1, Dp), clients, inits, coef, maskc)
        return out.reshape(Dp)[:D]

    npad, (clients, inits), alpha, mask = _pad_clients(
        n, ct, (clients, inits), alpha, mask)
    nb = npad // ct
    coef = (mask / jnp.maximum(alpha, 1e-9)).astype(jnp.float32).reshape(npad, 1)
    maskc = mask.astype(jnp.float32).reshape(npad, 1)
    out = pl.pallas_call(
        functools.partial(_agg_kernel_tiled, inv_s1=1.0 / (s + 1.0),
                          n_blocks=nb),
        grid=(Dp // TILE, nb),
        in_specs=[
            pl.BlockSpec((1, TILE), lambda i, j: (0, i)),     # server
            pl.BlockSpec((ct, TILE), lambda i, j: (j, i)),    # clients
            pl.BlockSpec((ct, TILE), lambda i, j: (j, i)),    # inits
            pl.BlockSpec((ct, 1), lambda i, j: (j, 0)),       # coef
            pl.BlockSpec((ct, 1), lambda i, j: (j, 0)),       # mask
        ],
        out_specs=pl.BlockSpec((1, TILE), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), server.dtype),
        scratch_shapes=[pltpu.VMEM((1, TILE), jnp.float32)],
        interpret=interpret,
        name="favas_agg",
    )(server.reshape(1, Dp), clients, inits, coef, maskc)
    return out.reshape(Dp)[:D]


# ---------------------------------------------------------------------------
# Fused full-round kernels (aggregation + selected-client reset)
# ---------------------------------------------------------------------------

def _fused_kernel(server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                  srv_out_ref, cli_out_ref, ini_out_ref, *, s1: float):
    """One resident (n, TILE) block of the full round update:
      msg_i   = init_i + (client_i - init_i) / alpha_i          (eq. 3)
      server' = (server + sum_i mask_i * msg_i) / (s+1)         (line 10)
      client' = mask_i ? server' : client_i                     (line 11)
      init'   = mask_i ? server' : init_i                       (line 12)
    alpha/mask (n, 1); server (1, TILE); clients/inits (n, TILE).
    All arithmetic in fp32; expressions mirror ref.favas_fused_ref exactly
    (true division, same reduction axis) so fp32 parity holds to 1 ULP."""
    c = clients_ref[...].astype(jnp.float32)          # (n, T)
    i = inits_ref[...].astype(jnp.float32)            # (n, T)
    a = alpha_ref[...].astype(jnp.float32)            # (n, 1)
    m = mask_ref[...].astype(jnp.float32)             # (n, 1)
    msg = i + (c - i) / a
    total = jnp.sum(m * msg, axis=0, keepdims=True)   # (1, T)
    s_new = (server_ref[...].astype(jnp.float32) + total) / s1
    srv_out_ref[...] = s_new.astype(srv_out_ref.dtype)
    cli_out_ref[...] = (m * s_new + (1.0 - m) * c).astype(cli_out_ref.dtype)
    ini_out_ref[...] = (m * s_new + (1.0 - m) * i).astype(ini_out_ref.dtype)


def _fused_kernel_prog(server_ref, clients_ref, inits_ref, prog_ref, alpha_ref,
                       mask_ref, srv_out_ref, cli_out_ref, ini_out_ref,
                       *, s1: float):
    """FAVAS[QNN] variant: the transmitted progress is supplied explicitly
    (already quantized), msg_i = init_i + prog_i / alpha_i, while the client
    reset keeps the client's own full-precision state — quantization is
    communication-only (paper Remark 1)."""
    c = clients_ref[...].astype(jnp.float32)          # (n, T)
    i = inits_ref[...].astype(jnp.float32)            # (n, T)
    p = prog_ref[...].astype(jnp.float32)             # (n, T)
    a = alpha_ref[...].astype(jnp.float32)            # (n, 1)
    m = mask_ref[...].astype(jnp.float32)             # (n, 1)
    msg = i + p / a
    total = jnp.sum(m * msg, axis=0, keepdims=True)   # (1, T)
    s_new = (server_ref[...].astype(jnp.float32) + total) / s1
    srv_out_ref[...] = s_new.astype(srv_out_ref.dtype)
    cli_out_ref[...] = (m * s_new + (1.0 - m) * c).astype(cli_out_ref.dtype)
    ini_out_ref[...] = (m * s_new + (1.0 - m) * i).astype(ini_out_ref.dtype)


def _fused_kernel_codes(server_ref, clients_ref, inits_ref, codes_ref,
                        pscale_ref, alpha_ref, mask_ref, srv_out_ref,
                        cli_out_ref, ini_out_ref, *, s1: float, bits: int):
    """CODES-IN FAVAS[QNN] variant: the transmitted progress arrives as a
    bit-packed (n, T*bits/8) uint8 block + (n, 1) f32 scales and is
    dequantized HERE, inside the VMEM pass — ``msg_i = init_i +
    dequant(code_i) / alpha_i`` — so the dense (n, D) f32 progress buffer
    never exists. Resets keep the client's own full-precision state
    (quantization is communication-only, paper Remark 1)."""
    c = clients_ref[...].astype(jnp.float32)          # (n, T)
    i = inits_ref[...].astype(jnp.float32)            # (n, T)
    a = alpha_ref[...].astype(jnp.float32)            # (n, 1)
    m = mask_ref[...].astype(jnp.float32)             # (n, 1)
    p = dequant_block(codes_ref[...], pscale_ref[:, :1], bits)
    msg = i + p / a
    total = jnp.sum(m * msg, axis=0, keepdims=True)   # (1, T)
    s_new = (server_ref[...].astype(jnp.float32) + total) / s1
    srv_out_ref[...] = s_new.astype(srv_out_ref.dtype)
    cli_out_ref[...] = (m * s_new + (1.0 - m) * c).astype(cli_out_ref.dtype)
    ini_out_ref[...] = (m * s_new + (1.0 - m) * i).astype(ini_out_ref.dtype)


def _fused_kernel_tiled(server_ref, clients_ref, inits_ref, alpha_ref,
                        mask_ref, srv_out_ref, cli_out_ref, ini_out_ref,
                        acc_ref, snew_ref, *, s1: float, n_blocks: int,
                        has_progress: bool, prog_ref=None,
                        codes_ref=None, pscale_ref=None, bits: int = 0):
    """Two-phase sweep over (CLIENT_TILE, TILE) client blocks — see the
    module docstring for the schedule. ``prog_ref`` is bound (via the
    dispatcher's wrapper kernel) only for the dense FAVAS[QNN] variant;
    ``codes_ref``/``pscale_ref`` only for the codes-in variant, which
    dequantizes the packed progress block in-VMEM during phase 0."""
    j = pl.program_id(1)
    c = clients_ref[...].astype(jnp.float32)          # (CT, T)
    i = inits_ref[...].astype(jnp.float32)            # (CT, T)
    m = mask_ref[...].astype(jnp.float32)             # (CT, 1)

    @pl.when(j < n_blocks)
    def _accumulate():
        a = alpha_ref[...].astype(jnp.float32)        # (CT, 1)
        if has_progress:
            p = prog_ref[...].astype(jnp.float32)
        elif codes_ref is not None:
            p = dequant_block(codes_ref[...], pscale_ref[:, :1], bits)
        else:
            p = c - i
        msg = i + p / a
        part = jnp.sum(m * msg, axis=0, keepdims=True)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _():
            acc_ref[...] = acc_ref[...] + part

        # pass the state through so every flushed out tile holds valid data
        # (already final for rows this phase doesn't reset)
        cli_out_ref[...] = c.astype(cli_out_ref.dtype)
        ini_out_ref[...] = i.astype(ini_out_ref.dtype)

        @pl.when(j == n_blocks - 1)
        def _epilogue():
            s_new = (server_ref[...].astype(jnp.float32) + acc_ref[...]) / s1
            snew_ref[...] = s_new
            srv_out_ref[...] = s_new.astype(srv_out_ref.dtype)

    @pl.when(j >= n_blocks)
    def _reset():
        s_new = snew_ref[...]                         # (1, T) f32
        cli_out_ref[...] = (m * s_new + (1.0 - m) * c).astype(cli_out_ref.dtype)
        ini_out_ref[...] = (m * s_new + (1.0 - m) * i).astype(ini_out_ref.dtype)


def favas_fused_pallas(server, clients, inits, alpha, mask, s: float,
                       *, progress=None, progress_codes=None,
                       progress_bits: int = 0, progress_shards: int = 1,
                       client_tile: int | None = None,
                       interpret: bool = False):
    """Fused aggregation + selected-client reset over flat buffers.

    server: (D,) f32/bf16; clients/inits: (n, D); alpha/mask: (n,).
    ``progress``: optional (n, D) explicit transmitted progress (e.g. LUQ-
    quantized client deltas); None means progress = clients - inits,
    computed in-kernel. ``progress_codes`` (mutually exclusive): the
    transmitted progress as ``{"codes": (n, D*bits/8) uint8, "scale":
    (n, shards) f32}`` — dequantized INSIDE the per-tile VMEM pass, so the
    dense (n, D) f32 progress never materializes; ``progress_bits`` is the
    LUQ width, ``progress_shards`` the per-row scale count (shard segments
    must be TILE-aligned when > 1 — guaranteed on the engine path by the
    per-shard lane padding). Client resets always use ``clients`` (full
    precision) — both progress forms affect only the transmitted message.
    ``client_tile``: sublane rows per client block (default CLIENT_TILE);
    n <= client_tile keeps the whole client axis resident in one block.
    Returns (server_new (D,), clients_new (n, D), inits_new (n, D))."""
    n, D = clients.shape
    ct = client_tile or CLIENT_TILE
    pad = (-D) % TILE
    codes = pscale = None
    bits = progress_bits
    if progress_codes is not None:
        if progress is not None:
            raise ValueError("progress and progress_codes are mutually "
                             "exclusive")
        if bits not in (2, 4, 8):
            raise ValueError(f"progress_bits must be 2, 4 or 8 (got {bits})")
        if D % progress_shards:
            raise ValueError(f"D={D} does not divide into "
                             f"{progress_shards} shards")
        if progress_shards > 1 and (D // progress_shards) % TILE:
            raise ValueError(
                f"codes-in progress needs TILE-aligned shard segments "
                f"(D={D}, shards={progress_shards}, tile={TILE})")
        codes, pscale = progress_codes["codes"], progress_codes["scale"]
    if pad:
        server = jnp.pad(server, (0, pad))
        clients = jnp.pad(clients, ((0, 0), (0, pad)))
        inits = jnp.pad(inits, ((0, 0), (0, pad)))
        if progress is not None:
            progress = jnp.pad(progress, ((0, 0), (0, pad)))
        if codes is not None:
            codes = _pad_codes(codes, bits, pad)
    Dp = D + pad
    # lane tiles per shard segment: the (rows, 128) scale block for lane
    # tile i sits at column block i // seg_tiles (0 when shards == 1)
    seg_tiles = (Dp // progress_shards) // TILE if codes is not None else 1
    if codes is not None:
        pscale = lane_scales(pscale)

    if n <= ct:                                   # whole client axis resident
        alphac = jnp.maximum(alpha.astype(jnp.float32), 1e-9).reshape(n, 1)
        maskc = mask.astype(jnp.float32).reshape(n, 1)
        row_spec = pl.BlockSpec((n, TILE), lambda i: (0, i))
        scalar_spec = pl.BlockSpec((n, 1), lambda i: (0, 0))
        srv_spec = pl.BlockSpec((1, TILE), lambda i: (0, i))
        if codes is not None:
            kernel = functools.partial(_fused_kernel_codes,
                                       s1=float(s) + 1.0, bits=bits)
            in_specs = [srv_spec, row_spec, row_spec,
                        pl.BlockSpec((n, TILE * bits // 8),
                                     lambda i: (0, i)),
                        pl.BlockSpec((n, LANES),
                                     lambda i: (0, i // seg_tiles)),
                        scalar_spec, scalar_spec]
            operands = (server.reshape(1, Dp), clients, inits, codes,
                        pscale, alphac, maskc)
        elif progress is None:
            kernel = functools.partial(_fused_kernel, s1=float(s) + 1.0)
            in_specs = [srv_spec, row_spec, row_spec, scalar_spec, scalar_spec]
            operands = (server.reshape(1, Dp), clients, inits, alphac, maskc)
        else:
            kernel = functools.partial(_fused_kernel_prog, s1=float(s) + 1.0)
            in_specs = [srv_spec, row_spec, row_spec, row_spec, scalar_spec,
                        scalar_spec]
            operands = (server.reshape(1, Dp), clients, inits, progress,
                        alphac, maskc)
        srv, cli, ini = pl.pallas_call(
            kernel,
            grid=(Dp // TILE,),
            in_specs=in_specs,
            out_specs=(srv_spec, row_spec, row_spec),
            out_shape=(
                jax.ShapeDtypeStruct((1, Dp), server.dtype),
                jax.ShapeDtypeStruct((n, Dp), clients.dtype),
                jax.ShapeDtypeStruct((n, Dp), inits.dtype),
            ),
            interpret=interpret,
            name="favas_fused",
        )(*operands)
        return srv.reshape(Dp)[:D], cli[:, :D], ini[:, :D]

    npad, (clients, inits, progress, codes, pscale), alpha, mask = \
        _pad_clients(n, ct, (clients, inits, progress, codes, pscale),
                     alpha, mask)
    nb = npad // ct
    alphac = jnp.maximum(alpha.astype(jnp.float32), 1e-9).reshape(npad, 1)
    maskc = mask.astype(jnp.float32).reshape(npad, 1)
    # two-phase inner grid dim: j in [0, nb) accumulates, [nb, 2nb) resets
    row_spec = pl.BlockSpec((ct, TILE), lambda i, j: (j % nb, i))
    scalar_spec = pl.BlockSpec((ct, 1), lambda i, j: (j % nb, 0))
    srv_spec = pl.BlockSpec((1, TILE), lambda i, j: (0, i))
    if codes is not None:
        # bind codes/scale as trailing positional refs via a wrapper (same
        # pattern as the dense-progress variant below)
        def kernel(server_ref, clients_ref, inits_ref, codes_ref, pscale_ref,
                   alpha_ref, mask_ref, srv_out_ref, cli_out_ref, ini_out_ref,
                   acc_ref, snew_ref):
            return _fused_kernel_tiled(
                server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                srv_out_ref, cli_out_ref, ini_out_ref, acc_ref, snew_ref,
                s1=float(s) + 1.0, n_blocks=nb, has_progress=False,
                codes_ref=codes_ref, pscale_ref=pscale_ref, bits=bits)
        # codes are only read in phase 0 — clamp the block index at the last
        # phase-0 block so phase 1 never re-fetches them (see prog_spec)
        codes_spec = pl.BlockSpec(
            (ct, TILE * bits // 8),
            lambda i, j: (jnp.minimum(j, nb - 1), i))
        pscale_spec = pl.BlockSpec(
            (ct, LANES),
            lambda i, j: (jnp.minimum(j, nb - 1), i // seg_tiles))
        in_specs = [srv_spec, row_spec, row_spec, codes_spec, pscale_spec,
                    scalar_spec, scalar_spec]
        operands = (server.reshape(1, Dp), clients, inits, codes, pscale,
                    alphac, maskc)
    elif progress is None:
        kernel = functools.partial(_fused_kernel_tiled, s1=float(s) + 1.0,
                                   n_blocks=nb, has_progress=False)
        in_specs = [srv_spec, row_spec, row_spec, scalar_spec, scalar_spec]
        operands = (server.reshape(1, Dp), clients, inits, alphac, maskc)
    else:
        # bind prog_ref as the trailing positional ref via a wrapper so the
        # no-progress variant keeps a progress-free operand list
        def kernel(server_ref, clients_ref, inits_ref, prog_ref, alpha_ref,
                   mask_ref, srv_out_ref, cli_out_ref, ini_out_ref,
                   acc_ref, snew_ref):
            return _fused_kernel_tiled(
                server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                srv_out_ref, cli_out_ref, ini_out_ref, acc_ref, snew_ref,
                s1=float(s) + 1.0, n_blocks=nb, has_progress=True,
                prog_ref=prog_ref)
        # progress is only read in phase 0: clamp its block index at the
        # last phase-0 block so the window never changes during phase 1 and
        # the pipeline skips the (otherwise redundant) re-fetch of every
        # progress block
        prog_spec = pl.BlockSpec((ct, TILE),
                                 lambda i, j: (jnp.minimum(j, nb - 1), i))
        in_specs = [srv_spec, row_spec, row_spec, prog_spec, scalar_spec,
                    scalar_spec]
        operands = (server.reshape(1, Dp), clients, inits, progress, alphac,
                    maskc)
    srv, cli, ini = pl.pallas_call(
        kernel,
        grid=(Dp // TILE, 2 * nb),
        in_specs=in_specs,
        out_specs=(
            srv_spec,
            pl.BlockSpec((ct, TILE), lambda i, j: (j % nb, i)),
            pl.BlockSpec((ct, TILE), lambda i, j: (j % nb, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, Dp), server.dtype),
            jax.ShapeDtypeStruct((npad, Dp), clients.dtype),
            jax.ShapeDtypeStruct((npad, Dp), inits.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((1, TILE), jnp.float32),
                        pltpu.VMEM((1, TILE), jnp.float32)],
        interpret=interpret,
        name="favas_fused",
    )(*operands)
    return srv.reshape(Dp)[:D], cli[:n, :D], ini[:n, :D]


# ---------------------------------------------------------------------------
# Streamed single-sweep aggregation (docs/architecture.md §13)
# ---------------------------------------------------------------------------
# The two-sweep fused kernel above reads every client block TWICE (phase 0
# accumulate, phase 1 reset) and rewrites every pass-through tile unchanged:
# ~2R+2W per resident client byte. The streamed schedule splits the round:
# this kernel does ONE pipelined sweep (the grid pipeline double-buffers the
# HBM->VMEM block stream, prefetching client block j+1 while block j's
# partial sum computes) and emits ONLY the new server row; the selected-
# client reset happens OUTSIDE as a churn-bounded scatter of that row into
# the s selected positions of the donated (aliased) client/init buffers —
# unselected rows are never read for the reset nor rewritten. Steady-state
# traffic drops to 1R per resident byte + O(s*D) scatter writes.
#
# Lane width: a TILE-wide grid step moves too little for HBM to set its
# pace (on a TPU v5e, ~0.4 us a step for (8, TILE) f32 blocks, against
# ~0.18 us of transfer at 819 GB/s). The resident kernel without
# transmitted progress therefore reads ``stream_width`` lanes a step, a
# partial last block covering a D that no wide block divides; per-lane
# arithmetic, and so every bit of the result, is the same at any width.
#
# Bit-exactness contract (why the split loses nothing): the selection mask
# is exactly the 0/1 indicator of the Gumbel top-s index set, so the fused
# reset `m*s_new + (1-m)*x` is `x` to the bit for unselected rows and
# `s_new.astype(dtype)` — exactly the row this kernel returns — for
# selected ones. The accumulation order matches `_fused_kernel_tiled`
# phase 0 block-for-block, so streamed-vs-two-sweep server parity is exact
# per dispatch path and kernel-vs-oracle parity bounds are unchanged.

def _stream_kernel(server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                   srv_out_ref, *, s1: float, prog_ref=None, codes_ref=None,
                   pscale_ref=None, bits: int = 0):
    """One resident (n, TILE) block, aggregation only — the `msg`/`total`/
    `s_new` expressions of ``_fused_kernel`` (same reduction axis, true
    division), without the reset outputs."""
    c = clients_ref[...].astype(jnp.float32)          # (n, T)
    i = inits_ref[...].astype(jnp.float32)            # (n, T)
    a = alpha_ref[...].astype(jnp.float32)            # (n, 1)
    m = mask_ref[...].astype(jnp.float32)             # (n, 1)
    if prog_ref is not None:
        p = prog_ref[...].astype(jnp.float32)
    elif codes_ref is not None:
        p = dequant_block(codes_ref[...], pscale_ref[:, :1], bits)
    else:
        p = c - i
    msg = i + p / a
    total = jnp.sum(m * msg, axis=0, keepdims=True)   # (1, T)
    s_new = (server_ref[...].astype(jnp.float32) + total) / s1
    srv_out_ref[...] = s_new.astype(srv_out_ref.dtype)


def _stream_kernel_tiled(server_ref, clients_ref, inits_ref, alpha_ref,
                         mask_ref, srv_out_ref, acc_ref, *, s1: float,
                         n_blocks: int, prog_ref=None, codes_ref=None,
                         pscale_ref=None, bits: int = 0):
    """Single pipelined sweep over (CLIENT_TILE, TILE) client blocks: each
    block's masked message partial sum accumulates into the f32 scratch
    row (identical accumulation order to ``_fused_kernel_tiled`` phase 0),
    and the epilogue on the last block folds in the server row. No client/
    init outputs exist, so no pass-through tile is ever written back."""
    j = pl.program_id(1)
    c = clients_ref[...].astype(jnp.float32)          # (CT, T)
    i = inits_ref[...].astype(jnp.float32)            # (CT, T)
    a = alpha_ref[...].astype(jnp.float32)            # (CT, 1)
    m = mask_ref[...].astype(jnp.float32)             # (CT, 1)
    if prog_ref is not None:
        p = prog_ref[...].astype(jnp.float32)
    elif codes_ref is not None:
        p = dequant_block(codes_ref[...], pscale_ref[:, :1], bits)
    else:
        p = c - i
    msg = i + p / a
    part = jnp.sum(m * msg, axis=0, keepdims=True)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _():
        acc_ref[...] = acc_ref[...] + part

    @pl.when(j == n_blocks - 1)
    def _epilogue():
        s_new = (server_ref[...].astype(jnp.float32) + acc_ref[...]) / s1
        srv_out_ref[...] = s_new.astype(srv_out_ref.dtype)


def favas_stream_pallas(server, clients, inits, alpha, mask, s: float,
                        *, progress=None, progress_codes=None,
                        progress_bits: int = 0, progress_shards: int = 1,
                        client_tile: int | None = None,
                        interpret: bool = False):
    """Aggregation-only half of the STREAMED round schedule.

    Same operand contract as ``favas_fused_pallas`` (server (D,), clients/
    inits (n, D), alpha/mask (n,), optional dense ``progress`` or packed
    ``progress_codes`` + ``progress_bits``/``progress_shards``), but
    returns ONLY the (D,) new server vector: the caller applies the
    selected-client reset as a churn-bounded scatter of this row into the
    donated state buffers (``core.round_engine.stream_bucket_update``).
    One HBM read per resident client byte, ~zero client-buffer writes;
    lane blocks ``stream_width`` wide on the resident path without
    transmitted progress, TILE wide otherwise."""
    n, D = clients.shape
    ct = client_tile or CLIENT_TILE
    pad = (-D) % TILE
    codes = pscale = None
    bits = progress_bits
    if progress_codes is not None:
        if progress is not None:
            raise ValueError("progress and progress_codes are mutually "
                             "exclusive")
        if bits not in (2, 4, 8):
            raise ValueError(f"progress_bits must be 2, 4 or 8 (got {bits})")
        if D % progress_shards:
            raise ValueError(f"D={D} does not divide into "
                             f"{progress_shards} shards")
        if progress_shards > 1 and (D // progress_shards) % TILE:
            raise ValueError(
                f"codes-in progress needs TILE-aligned shard segments "
                f"(D={D}, shards={progress_shards}, tile={TILE})")
        codes, pscale = progress_codes["codes"], progress_codes["scale"]
    if pad:
        server = jnp.pad(server, (0, pad))
        clients = jnp.pad(clients, ((0, 0), (0, pad)))
        inits = jnp.pad(inits, ((0, 0), (0, pad)))
        if progress is not None:
            progress = jnp.pad(progress, ((0, 0), (0, pad)))
        if codes is not None:
            codes = _pad_codes(codes, bits, pad)
    Dp = D + pad
    seg_tiles = (Dp // progress_shards) // TILE if codes is not None else 1
    if codes is not None:
        pscale = lane_scales(pscale)

    if n <= ct:                                   # whole client axis resident
        alphac = jnp.maximum(alpha.astype(jnp.float32), 1e-9).reshape(n, 1)
        maskc = mask.astype(jnp.float32).reshape(n, 1)
        width = (stream_width(n, clients.dtype, Dp)
                 if progress is None and codes is None else TILE)
        row_spec = pl.BlockSpec((n, width), lambda i: (0, i))
        scalar_spec = pl.BlockSpec((n, 1), lambda i: (0, 0))
        srv_spec = pl.BlockSpec((1, width), lambda i: (0, i))
        if codes is not None:
            def kernel(server_ref, clients_ref, inits_ref, codes_ref,
                       pscale_ref, alpha_ref, mask_ref, srv_out_ref):
                return _stream_kernel(
                    server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                    srv_out_ref, s1=float(s) + 1.0,
                    codes_ref=codes_ref, pscale_ref=pscale_ref, bits=bits)
            in_specs = [srv_spec, row_spec, row_spec,
                        pl.BlockSpec((n, TILE * bits // 8),
                                     lambda i: (0, i)),
                        pl.BlockSpec((n, LANES),
                                     lambda i: (0, i // seg_tiles)),
                        scalar_spec, scalar_spec]
            operands = (server.reshape(1, Dp), clients, inits, codes,
                        pscale, alphac, maskc)
        elif progress is None:
            kernel = functools.partial(_stream_kernel, s1=float(s) + 1.0)
            in_specs = [srv_spec, row_spec, row_spec, scalar_spec,
                        scalar_spec]
            operands = (server.reshape(1, Dp), clients, inits, alphac, maskc)
        else:
            def kernel(server_ref, clients_ref, inits_ref, prog_ref,
                       alpha_ref, mask_ref, srv_out_ref):
                return _stream_kernel(
                    server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                    srv_out_ref, s1=float(s) + 1.0, prog_ref=prog_ref)
            in_specs = [srv_spec, row_spec, row_spec, row_spec, scalar_spec,
                        scalar_spec]
            operands = (server.reshape(1, Dp), clients, inits, progress,
                        alphac, maskc)
        srv = pl.pallas_call(
            kernel,
            grid=(pl.cdiv(Dp, width),),
            in_specs=in_specs,
            out_specs=srv_spec,
            out_shape=jax.ShapeDtypeStruct((1, Dp), server.dtype),
            interpret=interpret,
            name="favas_stream",
        )(*operands)
        return srv.reshape(Dp)[:D]

    npad, (clients, inits, progress, codes, pscale), alpha, mask = \
        _pad_clients(n, ct, (clients, inits, progress, codes, pscale),
                     alpha, mask)
    nb = npad // ct
    alphac = jnp.maximum(alpha.astype(jnp.float32), 1e-9).reshape(npad, 1)
    maskc = mask.astype(jnp.float32).reshape(npad, 1)
    # single-phase inner grid dim: j in [0, nb) — every block exactly once,
    # double-buffered by the grid pipeline (block j+1 prefetches during j)
    row_spec = pl.BlockSpec((ct, TILE), lambda i, j: (j, i))
    scalar_spec = pl.BlockSpec((ct, 1), lambda i, j: (j, 0))
    srv_spec = pl.BlockSpec((1, TILE), lambda i, j: (0, i))
    if codes is not None:
        def kernel(server_ref, clients_ref, inits_ref, codes_ref, pscale_ref,
                   alpha_ref, mask_ref, srv_out_ref, acc_ref):
            return _stream_kernel_tiled(
                server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                srv_out_ref, acc_ref, s1=float(s) + 1.0, n_blocks=nb,
                codes_ref=codes_ref, pscale_ref=pscale_ref, bits=bits)
        in_specs = [srv_spec, row_spec, row_spec,
                    pl.BlockSpec((ct, TILE * bits // 8),
                                 lambda i, j: (j, i)),
                    pl.BlockSpec((ct, LANES),
                                 lambda i, j: (j, i // seg_tiles)),
                    scalar_spec, scalar_spec]
        operands = (server.reshape(1, Dp), clients, inits, codes, pscale,
                    alphac, maskc)
    elif progress is None:
        kernel = functools.partial(_stream_kernel_tiled, s1=float(s) + 1.0,
                                   n_blocks=nb)
        in_specs = [srv_spec, row_spec, row_spec, scalar_spec, scalar_spec]
        operands = (server.reshape(1, Dp), clients, inits, alphac, maskc)
    else:
        def kernel(server_ref, clients_ref, inits_ref, prog_ref, alpha_ref,
                   mask_ref, srv_out_ref, acc_ref):
            return _stream_kernel_tiled(
                server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                srv_out_ref, acc_ref, s1=float(s) + 1.0, n_blocks=nb,
                prog_ref=prog_ref)
        in_specs = [srv_spec, row_spec, row_spec, row_spec, scalar_spec,
                    scalar_spec]
        operands = (server.reshape(1, Dp), clients, inits, progress, alphac,
                    maskc)
    srv = pl.pallas_call(
        kernel,
        grid=(Dp // TILE, nb),
        in_specs=in_specs,
        out_specs=srv_spec,
        out_shape=jax.ShapeDtypeStruct((1, Dp), server.dtype),
        scratch_shapes=[pltpu.VMEM((1, TILE), jnp.float32)],
        interpret=interpret,
        name="favas_stream",
    )(*operands)
    return srv.reshape(Dp)[:D]
