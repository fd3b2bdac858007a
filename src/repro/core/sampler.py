"""Client heterogeneity and selection sampling (paper App. C.2).

* Per-round local-step increments d_t^i ~ shifted-Geometric(lambda_i)
  (support {1, 2, ...}, mean 1/lambda_i). Fast clients: lambda = 1/16
  (≈16 steps/round); slow: lambda = 1/2 (≈2 steps/round). The paper's text
  labels these by "running time"; we parameterize by steps-per-round so fast
  clients make more progress, which is the behaviour its experiments need.
* Server selection S_t: s of n uniformly without replacement, drawn in-jit
  via Gumbel top-s (exact uniform w/o replacement).

Everything is drawn inside the jitted round from explicit PRNG keys.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.profiling import scoped


def make_lambdas(n: int, slow_fraction: float = 1.0 / 3.0,
                 lam_fast: float = 1.0 / 16.0, lam_slow: float = 0.5,
                 seed: int = 0) -> np.ndarray:
    """Per-client geometric parameters; first ``slow_fraction`` are slow
    (assignment randomized by seed)."""
    rng = np.random.default_rng(seed)
    lam = np.where(np.arange(n) < int(round(slow_fraction * n)), lam_slow, lam_fast)
    return rng.permutation(lam).astype(np.float32)


@scoped("favas_sample")
def sample_increments(key, lambdas) -> jnp.ndarray:
    """d_i ~ 1 + Geom0(lambda_i): support {1,2,...}, E[d] = 1/lambda."""
    u = jax.random.uniform(key, lambdas.shape, minval=1e-7, maxval=1.0)
    d = 1 + jnp.floor(jnp.log(u) / jnp.log1p(-lambdas)).astype(jnp.int32)
    return jnp.maximum(d, 1)


@scoped("favas_sample")
def sample_selection_indices(key, n: int, s: int):
    """Uniform s-of-n without replacement, drawn in-jit via Gumbel top-s
    (exact uniform w/o replacement). Returns ``(idx (s,) int32, mask (n,)
    float32)`` — the on-device replacement for the simulator's old host-side
    ``np.random.choice(n, s, replace=False)``, so client selection can live
    inside a scanned superstep."""
    z = jax.random.gumbel(key, (n,))
    _, idx = jax.lax.top_k(z, s)
    mask = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
    return idx, mask


def sample_selection(key, n: int, s: int) -> jnp.ndarray:
    """Uniform s-of-n without replacement -> float mask (n,) with sum s."""
    return sample_selection_indices(key, n, s)[1]


def time_ticks(step_time, round_dur: float, max_denominator: int = 10 ** 4):
    """Scale (possibly fractional) step times + round duration to a common
    INTEGER tick grid: each time is read as the rational
    ``Fraction(t).limit_denominator(max_denominator)`` (so the float 0.3
    means the rational 3/10, exactly) and everything is multiplied by the
    lcm of the denominators. Returns ``(step_ticks (n,) int32 numpy,
    round_ticks int)`` for :func:`credit_steps`.

    The tick clock is drift-free integer arithmetic — unlike the previous
    f32 on-device clock, non-representable step times like 0.3 match the
    f64 host reference exactly at every round
    (tests/test_superstep.py::test_credit_steps_ticks_adversarial)."""
    from fractions import Fraction
    from math import gcd
    fr = [Fraction(float(t)).limit_denominator(max_denominator)
          for t in np.asarray(step_time).ravel()]
    fr.append(Fraction(float(round_dur)).limit_denominator(max_denominator))
    den = 1
    for f in fr:
        den = den * f.denominator // gcd(den, f.denominator)
    ticks = [int(f * den) for f in fr]
    if min(ticks) <= 0:
        raise ValueError(
            f"step times {np.asarray(step_time)!r} / round_dur {round_dur} "
            f"contain a value below the 1/{max_denominator} tick resolution "
            f"(it would quantize to zero ticks and divide by zero); use "
            f"larger times or a bigger max_denominator")
    if max(ticks) + ticks[-1] >= 2 ** 31:
        raise ValueError(
            f"step times {np.asarray(step_time)!r} / round_dur {round_dur} "
            f"need > int32 ticks (common denominator {den}); pass simpler "
            f"rational times or a smaller max_denominator")
    return (np.asarray(ticks[:-1], np.int32).reshape(np.shape(step_time)),
            ticks[-1])


def credit_steps(credit, step_ticks, q, K: int, round_ticks: int):
    """Deterministic-rate local-step bookkeeping, on-device (the simulator's
    App. C.2 clock), on INTEGER ticks: every client accrues ``round_ticks``
    ticks, converts whole ``step_ticks`` quanta into available steps
    (keeping the remainder as credit), and runs ``min(available, K - q)``
    of them this round. ``credit``/``step_ticks`` are (n,) int32 (build the
    ticks once with :func:`time_ticks`); ``q`` stays (n,) float32. Returns
    ``(steps_run (n,) float32, new_credit (n,) int32)``.

    Integer division replaces the old f32 ``floor(credit / step_time)``,
    so the clock is exact for ANY rational step time — the f64 host loop
    and this scan body can no longer disagree by a step (the ROADMAP
    f32-clock caveat)."""
    credit = credit + round_ticks
    avail = credit // step_ticks
    credit = credit - avail * step_ticks
    return jnp.minimum(avail.astype(jnp.float32), K - q), credit


# ---------------------------------------------------------------------------
# Analytic moments of E ∧ K (E = steps between consecutive polls)
# ---------------------------------------------------------------------------

def poll_steps_distribution(lam: float, K: int, poll_prob: float,
                            max_rounds: int = 2000) -> np.ndarray:
    """Exact (to truncation) pmf of q_poll = min(K, sum_{j<=M} d_j) where
    d_j ~ shifted-Geom(lam) per round and M ~ Geom(poll_prob) rounds between
    polls. Used for the deterministic reweight alpha = E[E ∧ K] and the
    Theorem-3 constants. Dynamic program over capped step counts."""
    # pmf of one round's increment, capped at K
    j = np.arange(1, K + 1)
    inc = lam * (1.0 - lam) ** (j - 1)
    inc[-1] = (1.0 - lam) ** (K - 1)          # P(d >= K) mass into cap
    # state pmf over {0..K} steps accumulated (capped)
    state = np.zeros(K + 1)
    state[0] = 1.0
    out = np.zeros(K + 1)
    survive = 1.0
    for _ in range(max_rounds):
        # advance one round of local compute
        new = np.zeros(K + 1)
        for q in range(K + 1):
            if state[q] <= 0:
                continue
            if q == K:
                new[K] += state[q]
                continue
            add = np.minimum(q + j, K)
            np.add.at(new, add, state[q] * inc)
        state = new
        # poll happens after this round w.p. poll_prob: P(M=m) = (1-p)^{m-1} p
        out += poll_prob * survive * state
        survive *= (1.0 - poll_prob)
        if survive < 1e-9:
            break
    out /= max(out.sum(), 1e-12)
    return out


def moments_at_poll(lam: float, K: int, poll_prob: float):
    """(P(E>0), E[E∧K], E[(E∧K)^2], E[1(E>0)/(E∧K)]) for the poll-interval
    step count. With shifted-geometric increments E >= 1 a.s."""
    pmf = poll_steps_distribution(lam, K, poll_prob)
    q = np.arange(K + 1)
    p_pos = pmf[1:].sum()
    e1 = float((pmf * q).sum())
    e2 = float((pmf * q * q).sum())
    einv = float((pmf[1:] / q[1:]).sum())
    return float(p_pos), e1, e2, einv
