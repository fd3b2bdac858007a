"""Cold-pool codecs for the paged client-state residency layer.

The paged engine (docs/architecture.md §9) keeps only a hot working set of
``s_max`` client rows in full precision; the remaining ``n - s_max``
clients live in a *cold pool* — one encoded row per client per bucket,
written on eviction and read on promotion. This module owns the encodings:

* :class:`PassthroughCodec` — stores the rows verbatim. Zero compression,
  but evict -> promote is bitwise identity, which is what makes the paged
  engine provably equal to the dense engine (the parity lattice in
  tests/test_paged_engine.py runs on this codec).
* :class:`LuqCodec` — LUQ logarithmic unbiased quantization (the same
  math as ``core.quant`` / ``kernels.luq``, FAVAS[QNN] paper Remark 1)
  at 2/4/8 bits, bit-packed into uint8, with a per-(row, shard) scale.
  A client row costs ``2 * D * bits / 8`` bytes (progress + init pools)
  instead of ``2 * D * 4`` — the resident-population lever of ROADMAP
  open item 1. The pair encoding stores the INIT row and the PROGRESS
  relative to the *decoded* init (``cli - dequant(init)``), so the
  reconstruction ``init_dec + prog_dec`` pays the progress quantization
  error once instead of compounding the init error.

Codecs are frozen (hashable) dataclasses so they can ride inside the
static ``FlatSpec``; the encoded representation is a plain dict-of-arrays
pytree so cold pools flow through jit/scan/donation like any buffer.
Per-shard scales keep encode/decode shard-local on a §6 mesh: the flat
lane axis is shard-major, so reshaping ``(rows, Dp)`` to ``(rows, S,
Dp/S)`` and reducing the last axis never crosses a device boundary.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Bit packing: b-bit codes <-> uint8 lanes
# ---------------------------------------------------------------------------

def pack_codes(codes, bits: int):
    """(..., C) uint8 codes (< 2**bits) -> (..., C*bits/8) packed uint8.

    Group-planar layout (``kernels.luq.pack_group``): each group of G codes
    packs into G/k bytes (k = 8//bits), byte j holding codes j, j + G/k,
    ..., j + (k-1)*G/k of the group, LSB-first. G is 128*k where C allows,
    else C. C must divide by k; the flat-buffer lane padding (multiples of
    the 2048-lane kernel tile) guarantees both for bits in {2, 4, 8}."""
    from repro.kernels.luq import pack_group    # lazy: no import cycle
    k = 8 // bits
    if k == 1:
        return codes.astype(jnp.uint8)
    C = codes.shape[-1]
    if C % k:
        raise ValueError(f"cannot pack {C} codes into "
                         f"{bits}-bit groups of {k}")
    g = pack_group(C, bits)
    planes = codes.reshape(codes.shape[:-1] + (C // g, k, g // k))
    planes = planes.astype(jnp.uint8)
    out = planes[..., 0, :]
    for i in range(1, k):
        out = out | (planes[..., i, :] << jnp.uint8(i * bits))
    return out.reshape(codes.shape[:-1] + (C // k,))


def unpack_codes(packed, bits: int):
    """Inverse of :func:`pack_codes`: (..., P) uint8 -> (..., P*8/bits)."""
    from repro.kernels.luq import pack_group    # lazy: no import cycle
    k = 8 // bits
    if k == 1:
        return packed
    P = packed.shape[-1]
    g = pack_group(P * k, bits)
    groups = packed.reshape(packed.shape[:-1] + (P * k // g, g // k))
    mask = jnp.uint8((1 << bits) - 1)
    planes = [(groups >> jnp.uint8(i * bits)) & mask for i in range(k)]
    return jnp.stack(planes, axis=-2).reshape(packed.shape[:-1] + (P * k,))


# ---------------------------------------------------------------------------
# Row-wise LUQ encode/decode (code-emitting variant of core.quant.luq_quantize)
# ---------------------------------------------------------------------------

def luq_encode_rows(x, bits: int, key, *, shards: int = 1) -> Dict:
    """LUQ-encode (rows, D) to packed codes + per-(row, shard) scales.

    Same stochastic prune + log2 stochastic rounding as ``kernels.ref.
    luq_ref`` (decode(encode(x)) equals ``luq_ref`` for the same uniforms
    — pinned by tests/test_quant_codec.py), but emitting the b-bit code
    ``sign << (bits-1) | m`` with magnitude index m in {0..L} (0 = exact
    zero, m -> exponent m - L) instead of the dequantized float. The scale
    is the guarded per-(row, shard) max |x| (``core.quant.luq_scale``
    semantics: all-zero segments map to scale 1.0, so decode is exact
    zeros, the PR 2 all-zero regression; a NaN max PROPAGATES so a
    poisoned segment decodes loudly non-finite instead of quantizing
    against 1.0 — pinned by tests/test_quant_codec.py)."""
    levels = 2 ** (bits - 1) - 1
    rows, D = x.shape
    if D % shards:
        raise ValueError(f"D={D} does not divide into {shards} shards")
    from repro.kernels.luq import guard_scale    # lazy: no import cycle
    xf = x.astype(jnp.float32)
    xs = xf.reshape(rows, shards, D // shards)
    scale = guard_scale(jnp.max(jnp.abs(xs), axis=2))
    m = jnp.abs(xs) / scale[..., None]
    min_level = 2.0 ** (-(levels - 1))
    k1, k2 = jax.random.split(key)
    # draw at (rows, D) so the uniforms line up element-for-element with a
    # caller passing explicit (rows, D) fields to kernels.ref.luq_ref
    up = jax.random.uniform(k1, (rows, D)).reshape(xs.shape)
    ur = jax.random.uniform(k2, (rows, D)).reshape(xs.shape)
    below = m < min_level
    keep = up < (m / min_level)
    m_pruned = jnp.where(below, jnp.where(keep, min_level, 0.0), m)
    e = jnp.floor(jnp.log2(jnp.maximum(m_pruned, min_level)))
    f = m_pruned / jnp.exp2(e)
    e_hat = jnp.clip(e + (ur < (f - 1.0)).astype(jnp.float32),
                     -(levels - 1), 0.0)
    midx = jnp.where(m_pruned == 0.0, 0,
                     (e_hat + levels).astype(jnp.int32))
    sign = (xs < 0).astype(jnp.int32)
    codes = ((sign << (bits - 1)) | midx).reshape(rows, D).astype(jnp.uint8)
    return {"codes": pack_codes(codes, bits), "scale": scale}


def luq_decode_rows(enc: Dict, bits: int, dtype, *, shards: int = 1):
    """Inverse of :func:`luq_encode_rows` -> (rows, D) in ``dtype``."""
    levels = 2 ** (bits - 1) - 1
    codes = unpack_codes(enc["codes"], bits)
    rows, D = codes.shape
    midx = (codes & jnp.uint8((1 << (bits - 1)) - 1)).astype(jnp.int32)
    sign = (codes >> jnp.uint8(bits - 1)).astype(jnp.float32)
    q = jnp.where(midx == 0, 0.0,
                  jnp.exp2(midx.astype(jnp.float32) - levels))
    v = ((1.0 - 2.0 * sign) * q).reshape(rows, shards, D // shards)
    v = v * enc["scale"][..., None].astype(jnp.float32)
    return v.reshape(rows, D).astype(dtype)


# ---------------------------------------------------------------------------
# Codecs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PassthroughCodec:
    """Identity cold codec: rows are stored verbatim (client AND init).

    No compression — this codec exists so the paged control flow (select ->
    gather -> round -> scatter-back) can be proven BIT-EXACT against
    the dense engine, independently of any quantization effect."""

    def encode_pair(self, cli, init, key, *, shards: int = 1,
                    use_kernel=None) -> Dict:
        del key, shards, use_kernel
        return {"cli": cli, "init": init}

    def decode_pair(self, enc: Dict, dtype, *, shards: int = 1,
                    use_kernel=None):
        del shards, use_kernel
        return enc["cli"].astype(dtype), enc["init"].astype(dtype)

    def bytes_per_row(self, d_padded: int, dtype, *, shards: int = 1) -> int:
        del shards                      # verbatim rows carry no scale
        return 2 * d_padded * jnp.dtype(dtype).itemsize

    def partition_specs(self, sharded: bool, axis: str = "model") -> Dict:
        from jax.sharding import PartitionSpec as P
        lane = P(None, axis if sharded else None)
        return {"cli": lane, "init": lane}


@dataclasses.dataclass(frozen=True)
class LuqCodec:
    """LUQ cold codec: init + progress pools, bit-packed at ``bits``.

    ``encode_pair`` stores (a) the init row LUQ-quantized and (b) the
    progress ``cli - dequant(init)`` LUQ-quantized — both with per-(row,
    shard) scales — so a cold client costs ``2 * D * bits / 8`` bytes plus
    two f32 scales per shard. Stochastic (unbiased) by construction: the
    requant noise of an evict/promote cycle has zero mean, the same
    principle that makes FAVAS[QNN]'s transmitted-progress quantization
    sound (paper Remark 1)."""
    bits: int = 4

    def __post_init__(self):
        if self.bits not in (2, 4, 8):
            raise ValueError(f"LuqCodec bits must be 2, 4 or 8 "
                             f"(got {self.bits})")

    def encode_pair(self, cli, init, key, *, shards: int = 1,
                    use_kernel=None) -> Dict:
        # route through kernels.ops so the requant dispatch point is shared
        # with the rest of the kernel surface: ``use_kernel`` picks the
        # code-emitting Pallas kernel exactly like the round's knob
        # (None = TPU auto, True = kernel / interpret off-TPU, False = jnp
        # oracle — the two are bit-identical under shared uniforms)
        from repro.kernels.ops import cold_dequant_rows, cold_requant_rows
        k_i, k_p = jax.random.split(key)
        ie = cold_requant_rows(init, self.bits, k_i, shards=shards,
                               use_kernel=use_kernel)
        init_dec = cold_dequant_rows(ie, self.bits, jnp.float32,
                                     shards=shards, use_kernel=use_kernel)
        prog = cli.astype(jnp.float32) - init_dec
        pe = cold_requant_rows(prog, self.bits, k_p, shards=shards,
                               use_kernel=use_kernel)
        return {"init": ie, "prog": pe}

    def decode_pair(self, enc: Dict, dtype, *, shards: int = 1,
                    use_kernel=None):
        from repro.kernels.ops import cold_dequant_rows
        init = cold_dequant_rows(enc["init"], self.bits, jnp.float32,
                                 shards=shards, use_kernel=use_kernel)
        cli = init + cold_dequant_rows(enc["prog"], self.bits, jnp.float32,
                                       shards=shards, use_kernel=use_kernel)
        return cli.astype(dtype), init.astype(dtype)

    def bytes_per_row(self, d_padded: int, dtype, *, shards: int = 1) -> int:
        del dtype
        # two pools (init + progress), each d_padded*bits/8 code bytes plus
        # ONE f32 scale per (row, shard) — on a §6 mesh the scale is
        # per-shard so encode/decode stay shard-local, and the cost scales
        # with the shard count (previously hard-coded to a single + 4)
        return 2 * (d_padded * self.bits // 8 + 4 * shards)

    def partition_specs(self, sharded: bool, axis: str = "model") -> Dict:
        from jax.sharding import PartitionSpec as P
        lane = P(None, axis if sharded else None)
        one = {"codes": lane, "scale": lane}
        return {"init": dict(one), "prog": dict(one)}


def make_codec(cold_bits: int):
    """CLI-facing factory: 0 -> passthrough, {2,4,8} -> LUQ at that width."""
    return PassthroughCodec() if cold_bits <= 0 else LuqCodec(bits=cold_bits)


def encoded_nbytes(enc) -> int:
    """Actual device bytes of an encoded pool (or any pytree of arrays)."""
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(enc)
               if leaf is not None)
