"""Pallas TPU kernels: LUQ logarithmic unbiased quantization (FAVAS[QNN],
paper Remark 1 / Chmiel et al. 2021).

Three kernels share the LUQ math (threshold + stochastic prune + log2 +
stochastic exponent rounding) in one VMEM pass over (8, 128)-aligned tiles:

* ``luq_pallas`` — the original dequantized-value variant (x -> Q(x)),
  used by ``ops.luq_quantize`` for the transmitted-progress path.
* ``luq_encode_pallas`` — code-EMITTING variant: x + uniforms -> bit-packed
  uint8 codes + per-(row, shard) f32 scales, bit-identical to
  ``core.paging.luq_encode_rows`` under the same uniforms. The pack runs
  in-kernel (contiguous 128-lane slices + shifts, see :func:`pack_group`)
  so the stored representation never leaves VMEM wider than ``bits/8``
  bytes per element.
* ``luq_decode_pallas`` — code-CONSUMING inverse, bit-identical to
  ``core.paging.luq_decode_rows``.

Scales are cheap separate reductions; the uniform random fields are passed
in as inputs so CPU interpret-mode tests are bit-identical to the jnp
oracle (a production TPU build would draw them on-chip with
``pltpu.prng_random_bits`` — noted in DESIGN.md §7). The scale guard is
shared with ``core.quant.luq_scale``: all-zero segments map to 1.0, a NaN
max PROPAGATES (decode of such a row is loudly non-finite, never silently
finite — pinned by tests/test_quant_codec.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS, COLS = 256, 1024  # (sublane, lane) tile — multiples of (8, 128)
ENC_ROWS = 8            # codec kernels: sublane rows per block
ENC_TILE = 512          # codec kernels: lane tile; 512*bits/8 >= 128 packed
LANES = 128             # vreg lane width: one packed plane


def guard_scale(scale):
    """Shared LUQ scale guard: zero -> 1.0 (exact-zero segments decode to
    exact zeros), positive/Inf pass through, NaN PROPAGATES (a poisoned
    segment must decode loudly non-finite, not quantize against 1.0)."""
    return jnp.where(jnp.isnan(scale), scale,
                     jnp.where(scale > 0, scale, 1.0))


def pack_group(width: int, bits: int) -> int:
    """Codes per packing group of a ``width``-code row at ``bits``.

    The packed layout is group-planar: a group of ``G = 128 * (8 // bits)``
    codes packs into 128 bytes, and byte j of the group holds codes
    ``j, j + 128, ..., j + 128 * (k - 1)`` (k = 8 // bits), plane i in bits
    ``[i*bits, (i+1)*bits)``. Packing and unpacking are then shifts of
    contiguous 128-lane slices — the only lane access the chip's compiler
    accepts (strided lane slices are refused). A row whose width is not a
    multiple of G is one group of ``width // k`` bytes per plane; that
    only happens at small validation widths, never on the engine's
    lane-tile-padded buffers."""
    g = LANES * (8 // bits)
    return g if width % g == 0 else width


def pack_block(codes, bits: int):
    """In-kernel bit pack: (R, C) int32 codes < 2**bits -> (R, C*bits/8)
    uint8 in the group-planar layout of :func:`pack_group` — the layout of
    ``core.paging.pack_codes``. C must divide by 8//bits."""
    k = 8 // bits
    if k == 1:
        return codes.astype(jnp.uint8)
    width = codes.shape[-1]
    g = pack_group(width, bits)
    p = g // k
    groups = []
    for s in range(0, width, g):
        packed = codes[:, s:s + p]
        for i in range(1, k):
            packed = packed | (codes[:, s + i * p:s + (i + 1) * p]
                               << (i * bits))
        groups.append(packed)
    out = groups[0] if len(groups) == 1 else jnp.concatenate(groups, axis=1)
    return out.astype(jnp.uint8)


def unpack_block(packed, bits: int):
    """In-kernel inverse of :func:`pack_block`: (R, P) uint8 -> (R, P*8/
    bits) int32 codes — k shifted copies of each contiguous packed group,
    laid side by side."""
    k = 8 // bits
    c = packed.astype(jnp.int32)
    if k == 1:
        return c
    p = pack_group(c.shape[-1] * k, bits) // k
    mask = (1 << bits) - 1
    planes = [(c[:, s:s + p] >> (i * bits)) & mask
              for s in range(0, c.shape[-1], p) for i in range(k)]
    return planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=1)


def dequant_block(packed, scale, bits: int):
    """In-kernel LUQ dequant of a packed uint8 block against (R, 1) f32
    scales -> (R, P*8/bits) f32 values. The same expressions (and float-op
    order) as ``core.paging.luq_decode_rows``, so interpret-mode output is
    bit-identical to the jnp oracle."""
    levels = 2 ** (bits - 1) - 1
    codes = unpack_block(packed, bits)
    midx = codes & ((1 << (bits - 1)) - 1)
    sign = (codes >> (bits - 1)).astype(jnp.float32)
    q = jnp.where(midx == 0, 0.0,
                  jnp.exp2(midx.astype(jnp.float32) - levels))
    return ((1.0 - 2.0 * sign) * q) * scale


def _luq_kernel(x_ref, up_ref, ur_ref, scale_ref, out_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    up = up_ref[...].astype(jnp.float32)
    ur = ur_ref[...].astype(jnp.float32)
    scale = guard_scale(scale_ref[0, 0].astype(jnp.float32))
    sign = jnp.sign(x)
    m = jnp.abs(x) / scale
    min_level = 2.0 ** (-(levels - 1))
    below = m < min_level
    keep = up < (m / min_level)
    m_pruned = jnp.where(below, jnp.where(keep, min_level, 0.0), m)
    e = jnp.floor(jnp.log2(jnp.maximum(m_pruned, min_level)))
    f = m_pruned / jnp.exp2(e)
    e_hat = e + (ur < (f - 1.0)).astype(jnp.float32)
    q = jnp.where(m_pruned == 0.0, 0.0,
                  jnp.exp2(jnp.clip(e_hat, -(levels - 1), 0.0)))
    out_ref[...] = (sign * scale * q).astype(out_ref.dtype)


def luq_pallas(x, u_prune, u_round, bits: int, *, interpret: bool = False):
    """Elementwise over any shape; flattened to (R, COLS) tiles.
    ``interpret`` runs the Pallas interpreter (CPU validation)."""
    # lazy: core.__init__ transitively imports this module, so a top-level
    # import of core.quant would be circular from some entry points
    from repro.core.quant import luq_scale
    levels = 2 ** (bits - 1) - 1
    orig_shape, dtype = x.shape, x.dtype
    scale = luq_scale(x).reshape(1, 1)
    flat = x.reshape(-1)
    D = flat.shape[0]
    width = ROWS * COLS
    pad = (-D) % width
    if pad:
        flat = jnp.pad(flat, (0, pad))
        u_prune = jnp.pad(u_prune.reshape(-1), (0, pad))
        u_round = jnp.pad(u_round.reshape(-1), (0, pad))
    else:
        u_prune = u_prune.reshape(-1)
        u_round = u_round.reshape(-1)
    rows = flat.shape[0] // COLS
    x2 = flat.reshape(rows, COLS)
    up2 = u_prune.reshape(rows, COLS)
    ur2 = u_round.reshape(rows, COLS)
    grid = (rows // ROWS,)
    out = pl.pallas_call(
        functools.partial(_luq_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, COLS), dtype),
        interpret=interpret,
        name="luq",
    )(x2, up2, ur2, scale)
    return out.reshape(-1)[:D].reshape(orig_shape)


# ---------------------------------------------------------------------------
# Code-emitting / code-consuming codec kernels (paged cold path + the
# codes-in fused round). Math mirrors core.paging.luq_encode_rows /
# luq_decode_rows expression-for-expression: under shared uniforms the
# interpret-mode output is BIT-IDENTICAL to the jnp oracle (pinned by
# tests/test_quant_codec.py / tests/test_quant_fused.py).
# ---------------------------------------------------------------------------

def lane_scales(scale):
    """(rows, S) per-(row, shard) scales -> (rows, S * 128) f32, each scale
    repeated across one 128-lane column block. A ``(rows, 128)`` block of
    this array satisfies the chip's (8, 128) block rule for any shard
    count S, where a ``(rows, 1)`` block of the (rows, S) array does not;
    kernels read lane 0 of their block."""
    return jnp.repeat(scale.astype(jnp.float32), LANES, axis=1)


def _codec_tile(D: int, shards: int, bits: int):
    """Lane tile for the codec grid: ``ENC_TILE`` when the per-shard
    segment is tile-aligned (always true on the engine path, where shard
    segments are multiples of the 2048-lane kernel tile), else the whole
    segment — an interpret-mode validation shape, not a TPU layout."""
    k = 8 // bits
    seg = D // shards
    if seg % k:
        raise ValueError(f"segment width {seg} does not divide into "
                         f"{bits}-bit groups of {k}")
    if seg % ENC_TILE == 0:
        return ENC_TILE
    if shards > 1 and k > 1 and seg % pack_group(D, bits):
        raise ValueError(f"shard segment {seg} splits a {bits}-bit packing "
                         f"group of the {D}-wide row")
    return seg


def _luq_encode_kernel(x_ref, up_ref, ur_ref, scale_ref, out_ref,
                       *, levels: int, bits: int):
    x = x_ref[...].astype(jnp.float32)
    up = up_ref[...].astype(jnp.float32)
    ur = ur_ref[...].astype(jnp.float32)
    scale = scale_ref[:, :1]                          # (R, 1), pre-guarded
    m = jnp.abs(x) / scale
    min_level = 2.0 ** (-(levels - 1))
    below = m < min_level
    keep = up < (m / min_level)
    m_pruned = jnp.where(below, jnp.where(keep, min_level, 0.0), m)
    e = jnp.floor(jnp.log2(jnp.maximum(m_pruned, min_level)))
    f = m_pruned / jnp.exp2(e)
    e_hat = jnp.clip(e + (ur < (f - 1.0)).astype(jnp.float32),
                     -(levels - 1), 0.0)
    midx = jnp.where(m_pruned == 0.0, 0, (e_hat + levels).astype(jnp.int32))
    sign = (x < 0).astype(jnp.int32)
    out_ref[...] = pack_block((sign << (bits - 1)) | midx, bits)


def _luq_decode_kernel(codes_ref, scale_ref, out_ref, *, bits: int):
    v = dequant_block(codes_ref[...], scale_ref[:, :1], bits)
    out_ref[...] = v.astype(out_ref.dtype)


def luq_encode_pallas(x, u_prune, u_round, bits: int, *, shards: int = 1,
                      interpret: bool = False):
    """LUQ-encode (rows, D) to bit-packed codes + per-(row, shard) scales.

    The kernel-path twin of ``core.paging.luq_encode_rows``: given the SAME
    (rows, D) uniform fields it emits bit-identical packed codes and
    scales. The per-(row, shard) max-|x| scale is a cheap jnp reduction
    (identical to the oracle's); all elementwise math and the bit pack run
    in one VMEM pass per (8, tile) block, with the scale riding an (8, 128)
    block of :func:`lane_scales` indexed by ``lane_tile // tiles_per_shard``.
    ``interpret`` runs the Pallas interpreter (CPU validation)."""
    levels = 2 ** (bits - 1) - 1
    rows, D = x.shape
    if D % shards:
        raise ValueError(f"D={D} does not divide into {shards} shards")
    seg = D // shards
    tile = _codec_tile(D, shards, bits)
    seg_tiles = seg // tile
    xf = x.astype(jnp.float32)
    scale = guard_scale(jnp.max(jnp.abs(xf.reshape(rows, shards, seg)),
                                axis=2))
    rpad = (-rows) % ENC_ROWS
    up = u_prune.astype(jnp.float32)
    ur = u_round.astype(jnp.float32)
    scale_p = scale
    if rpad:
        xf = jnp.pad(xf, ((0, rpad), (0, 0)))
        up = jnp.pad(up, ((0, rpad), (0, 0)))
        ur = jnp.pad(ur, ((0, rpad), (0, 0)))
        scale_p = jnp.pad(scale, ((0, rpad), (0, 0)), constant_values=1.0)
    rp = rows + rpad
    packed = pl.pallas_call(
        functools.partial(_luq_encode_kernel, levels=levels, bits=bits),
        grid=(rp // ENC_ROWS, D // tile),
        in_specs=[
            pl.BlockSpec((ENC_ROWS, tile), lambda i, c: (i, c)),
            pl.BlockSpec((ENC_ROWS, tile), lambda i, c: (i, c)),
            pl.BlockSpec((ENC_ROWS, tile), lambda i, c: (i, c)),
            pl.BlockSpec((ENC_ROWS, LANES),
                         lambda i, c: (i, c // seg_tiles)),
        ],
        out_specs=pl.BlockSpec((ENC_ROWS, tile * bits // 8),
                               lambda i, c: (i, c)),
        out_shape=jax.ShapeDtypeStruct((rp, D * bits // 8), jnp.uint8),
        interpret=interpret,
        name="luq_encode",
    )(xf, up, ur, lane_scales(scale_p))
    return {"codes": packed[:rows], "scale": scale}


def luq_decode_pallas(enc, bits: int, dtype, *, shards: int = 1,
                      interpret: bool = False):
    """Inverse of :func:`luq_encode_pallas` -> (rows, D) in ``dtype``;
    bit-identical to ``core.paging.luq_decode_rows`` on the same encoding.
    The unpack + dequant run in one VMEM pass per packed block."""
    codes, scale = enc["codes"], enc["scale"]
    rows, W = codes.shape
    k = 8 // bits
    D = W * k
    if D % shards:
        raise ValueError(f"D={D} does not divide into {shards} shards")
    seg = D // shards
    tile = _codec_tile(D, shards, bits)
    seg_tiles = seg // tile
    rpad = (-rows) % ENC_ROWS
    scale_p = scale
    if rpad:
        codes = jnp.pad(codes, ((0, rpad), (0, 0)))
        scale_p = jnp.pad(scale, ((0, rpad), (0, 0)), constant_values=1.0)
    rp = rows + rpad
    out = pl.pallas_call(
        functools.partial(_luq_decode_kernel, bits=bits),
        grid=(rp // ENC_ROWS, D // tile),
        in_specs=[
            pl.BlockSpec((ENC_ROWS, tile * bits // 8), lambda i, c: (i, c)),
            pl.BlockSpec((ENC_ROWS, LANES),
                         lambda i, c: (i, c // seg_tiles)),
        ],
        out_specs=pl.BlockSpec((ENC_ROWS, tile), lambda i, c: (i, c)),
        out_shape=jax.ShapeDtypeStruct((rp, D), jnp.dtype(dtype)),
        interpret=interpret,
        name="luq_decode",
    )(codes, lane_scales(scale_p))
    return out[:rows]
