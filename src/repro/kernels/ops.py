"""Jit'd public wrappers over the Pallas kernels, with automatic fallback:
the kernels run natively on TPU and in interpret mode on CPU; ``use_kernel=
False`` selects the pure-jnp oracle path (used by the default pjit trainer,
where XLA fusion already handles the arithmetic — the kernel path is the
single-host / kernel-benchmark configuration).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.favas_agg import favas_stream_pallas
from repro.kernels.luq import (luq_decode_pallas, luq_encode_pallas,
                               luq_pallas)


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def favas_stream_flat(server, clients, inits, alpha, mask, s: float,
                      *, progress=None, progress_codes=None,
                      progress_bits: int = 0, progress_shards: int = 1,
                      client_tile=None, n_logical=None, use_kernel=None):
    """FAVAS aggregation over flat buffers (docs §13); see
    kernels/favas_agg.py. Returns ONLY the (D,) new server vector: the
    caller writes it into the selected rows of the donated state buffers
    (``core.round_engine.aggregate_and_reset``), so unselected rows are
    never rewritten.

    Args:
      server: (D,) flat server vector; clients / inits: (n, D) stacks.
      alpha / mask: (n,) eq. 3 coefficients and 0/1 selection mask, already
        padded alongside any client-row padding (unit alpha / zero mask on
        padded rows keeps them exact no-ops).
      s: |S_t|; the aggregation divides by ``s + 1``.
      progress: optional explicit (n, D) transmitted progress (e.g. the
        LUQ-quantized client deltas); None means ``clients - inits``,
        computed inside.
      progress_codes: the CODES-IN variant of ``progress`` (mutually
        exclusive with it): a ``{"codes": (n, D*bits/8) uint8, "scale":
        (n, shards) f32}`` encoding from ``cold_requant_rows``. The kernel
        path dequantizes per VMEM tile (``msg_i = init_i + dequant(code_i)
        / alpha_i``) so the dense (n, D) f32 progress never materializes;
        the oracle path decodes with ``core.paging.luq_decode_rows`` and
        runs the dense reference — element-identical by construction.
      progress_bits / progress_shards: LUQ width and per-row scale count
        of ``progress_codes``.
      client_tile: client-axis tile for the kernel path (the jnp oracle is
        shape-agnostic and ignores it).
      n_logical: real client rows when the buffers carry client-tile
        padding; the oracle path reduces over the logical rows only, so a
        padded row count never reorders the fp32 client sum (keeps the
        engine bit-identical to ``favas_round_reference`` at any n).
      use_kernel: None (auto) picks the Pallas kernel on TPU and the jnp
        oracle on CPU (interpret mode is a validation tool, not a fast
        path); True forces the kernel (interpret off-TPU), False forces the
        oracle.

    On a device mesh, call this through
    ``core.round_engine.stream_bucket_update`` — it wraps the kernel path
    in ``shard_map`` over per-shard flat slices and pins the oracle path's
    output sharding, so sharded buckets never gather."""
    if progress is not None and progress_codes is not None:
        raise ValueError("progress and progress_codes are mutually exclusive")
    if use_kernel is None:
        use_kernel = _is_tpu()
    if use_kernel:
        return favas_stream_pallas(server, clients, inits, alpha, mask, s,
                                   progress=progress,
                                   progress_codes=progress_codes,
                                   progress_bits=progress_bits,
                                   progress_shards=progress_shards,
                                   client_tile=client_tile,
                                   interpret=not _is_tpu())
    if progress_codes is not None:
        # oracle: decode to dense f32 and run the reference — decode is
        # row-elementwise, so slice-then-decode == decode-then-slice
        from repro.core.paging import luq_decode_rows   # lazy: no cycle
        progress = luq_decode_rows(progress_codes, progress_bits,
                                   jnp.float32, shards=progress_shards)
    rows = clients.shape[0]
    nl = rows if n_logical is None else n_logical
    if nl < rows:
        # padded rows are zero with zero mask: exact no-ops under the sum
        return ref.favas_stream_ref(
            server, clients[:nl], inits[:nl], alpha[:nl], mask[:nl], s,
            progress=None if progress is None else progress[:nl])
    return ref.favas_stream_ref(server, clients, inits, alpha, mask, s,
                                progress=progress)


def cold_requant_rows(x, bits: int, key, *, shards: int = 1,
                      use_kernel=None):
    """Paged-engine EVICTION path: LUQ-encode (rows, D) hot rows into
    bit-packed cold-pool codes + per-(row, shard) scales (see
    ``core.paging.luq_encode_rows`` for the math — the same stochastic
    prune/round as ``luq_pallas``/``luq_ref``, emitting codes instead of
    dequantized floats).

    ``use_kernel`` follows the ``favas_stream_flat`` dispatch contract:
    None picks the code-emitting Pallas kernel (``kernels.luq.
    luq_encode_pallas``) on TPU and the jnp oracle elsewhere; True forces
    the kernel (interpret mode off-TPU — a validation tool, not a fast
    path); False forces the oracle. Both paths draw the SAME (rows, D)
    uniform fields from ``key`` and are bit-identical (pinned by
    tests/test_quant_fused.py — this dispatch used to be a silent no-op)."""
    if use_kernel is None:
        use_kernel = _is_tpu()
    if use_kernel:
        k1, k2 = jax.random.split(key)
        rows, D = x.shape
        up = jax.random.uniform(k1, (rows, D))
        ur = jax.random.uniform(k2, (rows, D))
        return luq_encode_pallas(x, up, ur, bits, shards=shards,
                                 interpret=not _is_tpu())
    from repro.core.paging import luq_encode_rows   # lazy: no import cycle
    return luq_encode_rows(x, bits, key, shards=shards)


def cold_dequant_rows(enc, bits: int, dtype, *, shards: int = 1,
                      use_kernel=None):
    """Paged-engine PROMOTION path: decode cold-pool rows gathered for the
    new hot working set back to (rows, D) in ``dtype``. Inverse of
    :func:`cold_requant_rows`, same ``use_kernel`` contract (the Pallas
    path is ``kernels.luq.luq_decode_pallas``)."""
    if use_kernel is None:
        use_kernel = _is_tpu()
    if use_kernel:
        return luq_decode_pallas(enc, bits, dtype, shards=shards,
                                 interpret=not _is_tpu())
    from repro.core.paging import luq_decode_rows   # lazy: no import cycle
    return luq_decode_rows(enc, bits, dtype, shards=shards)


def luq_quantize(x, bits: int, key, *, use_kernel: bool = True):
    """LUQ quantization with explicit PRNG key (kernel or oracle path)."""
    # lazy: core.__init__ transitively imports this module
    from repro.core.quant import luq_scale
    k1, k2 = jax.random.split(key)
    up = jax.random.uniform(k1, x.shape)
    ur = jax.random.uniform(k2, x.shape)
    if use_kernel:
        return luq_pallas(x, up, ur, bits, interpret=not _is_tpu())
    # the guarded scale (all-zero inputs -> 1.0) is shared with
    # core.quant.luq_quantize and the kernel path — one helper, no drift
    return ref.luq_ref(x, up, ur, luq_scale(x), bits)
