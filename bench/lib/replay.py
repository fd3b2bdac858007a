"""The engine's per-round random streams, replayed from the state key.

A copy of the sampler arithmetic of the program's superstep on the device
data plane (``core/round_engine.py::engine_multi_round`` and
``engine_round``, ``core/sampler.py``, ``data/device_corpus.py``), so that
the reference and the live-step count read the same draws as the program
without importing it. Per round, from the carried key:

    key, k_batch = split(key)                 # superstep body
    key, k_inc, k_sel, k_q = split(key, 4)    # the round
    d_i   = max(1 + floor(log u_i / log1p(-lambda_i)), 1),
            u ~ U[1e-7, 1) from k_inc
    q'_i  = min(q_i + d_i, K);   step k of client i is live iff q_i + k < q'_i
    S_t   = top-s of Gumbel(k_sel, (n,))
    start = lo_i + min(int(u * span_i), span_i - 1), u ~ U[0, 1) from
            k_batch, shape (n, R, B)
    after the round: q_i = 0 for i in S_t, else q'_i.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def state_key(seed: int):
    """The engine's initial key from a seed of any size: the low 32 bits
    seed the key and the high bits are folded in, so seeds past 2**32 do
    not collide with small ones."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def speed_law(traffic: dict) -> np.ndarray:
    """Per-client geometric rates: the first ``round(slow_fraction * n)``
    clients are slow. The assignment is the same for every seed, so every
    seed runs the same program (the rates are compiled in)."""
    n = traffic["n_clients"]
    n_slow = int(round(traffic["slow_fraction"] * n))
    lam = np.where(np.arange(n) < n_slow, traffic["lam_slow"],
                   traffic["lam_fast"])
    return lam.astype(np.float32)


def split_round(key):
    """(next key, k_batch, k_inc, k_sel) of one round."""
    key, k_batch = jax.random.split(key)
    key, k_inc, k_sel, _ = jax.random.split(key, 4)
    return key, k_batch, k_inc, k_sel


def increments(k_inc, lambdas):
    u = jax.random.uniform(k_inc, lambdas.shape, minval=1e-7, maxval=1.0)
    d = 1 + jnp.floor(jnp.log(u) / jnp.log1p(-lambdas)).astype(jnp.int32)
    return jnp.maximum(d, 1)


def selection(k_sel, n: int, s: int):
    z = jax.random.gumbel(k_sel, (n,))
    _, idx = jax.lax.top_k(z, s)
    return idx


def window_starts(k_batch, lo, span, R: int, batch: int):
    """(n, R, B) int32 window starts into the token stream."""
    u = jax.random.uniform(k_batch, (lo.shape[0], R, batch))
    L = span[:, None, None]
    j = jnp.minimum((u * L.astype(jnp.float32)).astype(jnp.int32), L - 1)
    return lo[:, None, None] + j


def plan_round(key, counters, lambdas, *, K: int, s: int):
    """One round's bookkeeping: (next key, k_batch, new counters q', the
    counters after the reset, selected ids)."""
    key, k_batch, k_inc, k_sel = split_round(key)
    new = jnp.minimum(counters + increments(k_inc, lambdas), K)
    sel = selection(k_sel, counters.shape[0], s)
    after = new.at[sel].set(0)
    return key, k_batch, new, after, sel


def live_steps(key, lambdas, *, K: int, s: int, n_rounds: int):
    """Per-round live local steps (sum over clients of q' - q) for rounds
    0 .. n_rounds-1 from the initial key, and the counters after the last
    round — the engine's ``state.counters`` at that point."""
    lambdas = jnp.asarray(lambdas)

    def body(carry, _):
        key, q = carry
        key, _, new, after, _ = plan_round(key, q, lambdas, K=K, s=s)
        return (key, after), jnp.sum(new - q)

    q0 = jnp.zeros(lambdas.shape, jnp.int32)
    (_, q), live = jax.jit(lambda k: jax.lax.scan(
        body, (k, q0), None, length=n_rounds))(key)
    return np.asarray(live), np.asarray(q)
