"""Pure-jnp oracles for the Pallas kernels. Tests assert_allclose the
kernels (interpret=True on CPU) against these across shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def favas_fused_ref(server, clients, inits, alpha, mask, s: float,
                    *, progress=None):
    """Whole-round oracle: aggregation (line 10) + selected-client reset
    (lines 11–12) over flat buffers, the reset as the blend
    ``m*s_new + (1-m)*x``. Tests hold the engine's streamed step
    (``core.round_engine.aggregate_and_reset``) to it; nothing in the
    program calls it.

    server: (D,), clients/inits: (n, D), alpha/mask: (n,). ``progress``:
    optional explicit (quantized) transmitted progress; None means
    clients - inits. Resets always use full-precision ``clients``.
    Returns (server_new, clients_new, inits_new)."""
    c = clients.astype(jnp.float32)
    i = inits.astype(jnp.float32)
    a = jnp.maximum(alpha.astype(jnp.float32), 1e-9)[:, None]
    m = mask.astype(jnp.float32)[:, None]
    p = (c - i) if progress is None else progress.astype(jnp.float32)
    msg = i + p / a
    total = jnp.sum(m * msg, axis=0, keepdims=True)
    s_new = (server.astype(jnp.float32)[None] + total) / (float(s) + 1.0)
    server_new = s_new[0].astype(server.dtype)
    clients_new = (m * s_new + (1.0 - m) * c).astype(clients.dtype)
    inits_new = (m * s_new + (1.0 - m) * i).astype(inits.dtype)
    return server_new, clients_new, inits_new


def favas_stream_ref(server, clients, inits, alpha, mask, s: float,
                     *, progress=None):
    """Oracle of ``favas_agg.favas_stream_pallas`` (docs §13): the exact
    ``favas_fused_ref`` server expressions, emitting ONLY the new server
    row. The selected-client reset happens outside the kernel, as a write
    of this row into the s selected positions.

    Bit-exactness with the blended reset: ``mask`` is exactly the 0/1
    indicator of the selected index set (Gumbel top-s), so for every
    unselected row ``m*s_new + (1-m)*x == x`` to the bit (the f32
    round-trip of a finite value is identity for f32/bf16 states) and for
    every selected row it equals ``s_new.astype(dtype)`` — the row this
    oracle returns."""
    c = clients.astype(jnp.float32)
    i = inits.astype(jnp.float32)
    a = jnp.maximum(alpha.astype(jnp.float32), 1e-9)[:, None]
    m = mask.astype(jnp.float32)[:, None]
    p = (c - i) if progress is None else progress.astype(jnp.float32)
    msg = i + p / a
    total = jnp.sum(m * msg, axis=0, keepdims=True)
    s_new = (server.astype(jnp.float32)[None] + total) / (float(s) + 1.0)
    return s_new[0].astype(server.dtype)


def luq_ref(x, u_prune, u_round, scale, bits: int):
    """LUQ log-domain unbiased quantization (see core/quant.py), with the
    randomness and the global scale passed in (kernel parity)."""
    levels = 2 ** (bits - 1) - 1
    xf = x.astype(jnp.float32)
    sign = jnp.sign(xf)
    mag = jnp.abs(xf)
    # shared guard (core.quant.luq_scale semantics): zero -> 1.0, NaN
    # propagates — see kernels/luq.py::guard_scale
    from repro.kernels.luq import guard_scale
    scale = guard_scale(scale).astype(jnp.float32)
    m = mag / scale
    min_level = 2.0 ** (-(levels - 1))
    below = m < min_level
    keep = u_prune < (m / min_level)
    m_pruned = jnp.where(below, jnp.where(keep, min_level, 0.0), m)
    e = jnp.floor(jnp.log2(jnp.maximum(m_pruned, min_level)))
    f = m_pruned / jnp.exp2(e)
    e_hat = e + (u_round < (f - 1.0)).astype(jnp.float32)
    q = jnp.where(m_pruned == 0.0, 0.0,
                  jnp.exp2(jnp.clip(e_hat, -(levels - 1), 0.0)))
    return (sign * scale * q).astype(x.dtype)
