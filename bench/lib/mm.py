"""Matrix products of the plain references, in the precision asked for.

``highest``: float32 operands, ``Precision.HIGHEST`` — the reference.
``fp8``: the control. Every product quantizes its operands to float8
e4m3 with one scale per tensor (amax / 448) in the forward pass, and in
the backward pass quantizes the incoming cotangent to float8 e5m2 (amax /
57344) before the two products that carry it back — the usual scaled
fp8 training recipe, one step below the bfloat16 the configurations
state. Everything that is not a matrix product stays in float32."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _quant(x, dtype, top):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def q_e4m3(x):
    return _quant(x, jnp.float8_e4m3fn, 448.0)


def q_e5m2(x):
    return _quant(x, jnp.float8_e5m2, 57344.0)


def _einsum(sub, a, b):
    return jnp.einsum(sub, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(sub, a, b):
    return _einsum(sub, q_e4m3(a), q_e4m3(b))


def _fp8_fwd(sub, a, b):
    qa, qb = q_e4m3(a), q_e4m3(b)
    return _einsum(sub, qa, qb), (qa, qb)


def _fp8_bwd(sub, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(sub, x, y), qa, qb)
    return vjp(q_e5m2(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(mode: str):
    """``mm(subscripts, a, b)`` for a precision mode."""
    if mode == "highest":
        return _einsum
    if mode == "fp8":
        return _fp8_einsum
    raise ValueError(f"unknown precision mode {mode!r}")
