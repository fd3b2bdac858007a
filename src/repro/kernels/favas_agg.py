"""Pallas TPU kernel: the FAVAS server aggregation (Algorithm 1 line 10 +
eq. 3 reweighting) over flattened parameter buffers, ``favas_stream``.

Why a kernel: the aggregation touches every byte of every resident client's
parameters each server round and is purely memory-bound. Unfused HLO does
4+ passes per leaf (sub, div, add, mul-mask, reduce); this kernel streams
each client block through VMEM once: one HBM read per operand, and one
write, the new (1, D) server row. The selected-client reset (lines 11-12)
is not in the kernel: the caller writes the returned row into the s
selected rows of the donated client/init buffers
(``core.round_engine.aggregate_and_reset``), so unselected rows are never
rewritten.

Tiling. The lane dim is tiled at ``TILE`` (multiple of 128 for clean
(8, 128) vreg tiling). The client dim rides the sublane axis. For
``n <= CLIENT_TILE`` the whole client axis is one resident block; the
resident kernel without transmitted progress reads ``stream_width`` lanes
a step (``stream_block_vmem_bytes``), every other variant TILE. Above
``CLIENT_TILE`` a second grid dimension streams (CLIENT_TILE, TILE)
client-row blocks through a VMEM scratch accumulator, so ``n`` can scale to
thousands while VMEM stays bounded at O(CLIENT_TILE * TILE)
(``stream_tile_vmem_bytes``: about 1.1 MiB at n=1024, fp32, independent of
n and D; tests pin it under 2 MiB for the production shape n=1024,
D=2^20).

The client axis is padded to a CLIENT_TILE multiple with zero rows, zero
mask and unit alpha, so padded rows contribute exactly 0.0 to the masked
sum (adding 0.0 is exact in fp32 — no parity impact). The flat-buffer
engine (``core/round_engine.py``) pre-pads both axes so the kernel path
never re-pads.

Validated with interpret=True on CPU against ``ref.favas_stream_ref``: the
kernel body uses the same jnp expressions (including true division) as
the oracle. The resident kernel reduces over the same (n, TILE) block as
the oracle, so fp32 parity holds to 1 ULP; the tiled kernel accumulates
per-block partial sums sequentially, which reorders the client reduction
— parity then holds to ~1 ULP *of the accumulator magnitude* (tests bound
|kernel - oracle| by ULPs of |server| + sum_i |mask_i * msg_i| per lane,
before the 1/(s+1) division).
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


def stream_tile_vmem_bytes(n: int, dtype, *, progress: bool = False,
                           codec_bits: int = 0, tile: int = TILE,
                           client_tile: int = CLIENT_TILE) -> int:
    """Per-grid-step VMEM footprint of the TILE-wide ``favas_stream_pallas``
    (the tiled path, and the resident path with a transmitted progress)
    computed from the declared BlockSpec shapes: the pipeline's two copies
    of every in and out block (it fetches block j+1 while block j
    computes), plus the f32 accumulator row of the tiled path. For the
    tiled path (n > client_tile) this is independent of both n and D —
    the property that lets the engine scale to thousands of clients.

    ``progress`` accounts a dense (rows, tile) transmitted-progress block;
    ``codec_bits`` > 0 the CODES-IN operand instead: a bit-packed (rows,
    tile*bits/8) uint8 codes block plus a (rows, 128) f32 lane-broadcast
    scale block — the codec term of docs/architecture.md §10."""
    if progress and codec_bits:
        raise ValueError("progress and codec_bits are mutually exclusive")
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
    outputs = srv_block                          # the new server row only
    scratch = tile * 4 if n > client_tile else 0  # f32 accumulator row
    return 2 * (inputs + outputs) + scratch


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
# Streamed single-sweep aggregation (docs/architecture.md §13)
# ---------------------------------------------------------------------------
# ONE pipelined sweep (the grid pipeline double-buffers the HBM->VMEM block
# stream, prefetching client block j+1 while block j's partial sum
# computes) that emits ONLY the new server row; the selected-client reset
# happens outside, in the caller, on the donated (aliased) client/init
# buffers. Traffic: 1R per resident byte + the reset's writes.
#
# Lane width: a TILE-wide grid step moves too little for HBM to set its
# pace (on a TPU v5e, ~0.4 us a step for (8, TILE) f32 blocks, against
# ~0.18 us of transfer at 819 GB/s). The resident kernel without
# transmitted progress therefore reads ``stream_width`` lanes a step, a
# partial last block covering a D that no wide block divides; per-lane
# arithmetic, and so every bit of the result, is the same at any width.
#
# Why the split loses nothing against the whole-round oracle
# (``ref.favas_fused_ref``): the selection mask is exactly the 0/1
# indicator of the Gumbel top-s index set, so the blended reset
# `m*s_new + (1-m)*x` is `x` to the bit for unselected rows and
# `s_new.astype(dtype)` — exactly the row this kernel returns — for
# selected ones.

def _stream_kernel(server_ref, clients_ref, inits_ref, alpha_ref, mask_ref,
                   srv_out_ref, *, s1: float, prog_ref=None, codes_ref=None,
                   pscale_ref=None, bits: int = 0):
    """One resident (n, width) block: the `msg`/`total`/`s_new`
    expressions of ``ref.favas_stream_ref`` (same reduction axis, true
    division)."""
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
    row, and the epilogue on the last block folds in the server row. No
    client/init outputs exist, so no pass-through tile is ever written
    back."""
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
    """FAVAS aggregation (Alg. 1 line 10 + eq. 3) over flat buffers.

    server: (D,) f32/bf16; clients/inits: (n, D); alpha/mask: (n,).
    ``progress``: optional (n, D) explicit transmitted progress (e.g. LUQ-
    quantized client deltas); None means progress = clients - inits,
    computed in-kernel. ``progress_codes`` (mutually exclusive): the
    transmitted progress as ``{"codes": (n, D*bits/8) uint8, "scale":
    (n, shards) f32}`` — dequantized INSIDE the per-tile VMEM pass, so the
    dense (n, D) f32 progress never materializes; ``progress_bits`` is the
    LUQ width, ``progress_shards`` the per-row scale count (shard segments
    must be TILE-aligned when > 1 — guaranteed on the engine path by the
    per-shard lane padding). ``client_tile``: sublane rows per client
    block (default CLIENT_TILE); n <= client_tile keeps the whole client
    axis resident in one block.

    Returns ONLY the (D,) new server vector: the caller writes this row
    into the selected rows of the donated state buffers
    (``core.round_engine.aggregate_and_reset``). One HBM read per resident
    client byte, no client-buffer writes; lane blocks ``stream_width``
    wide on the resident path without transmitted progress, TILE wide
    otherwise."""
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
