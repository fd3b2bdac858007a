"""End-to-end behaviour tests for the FAVAS system."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import (FavasConfig, favas_init, favas_round, favas_variance,
                        favas_mu, client_lambdas, deterministic_alphas)
from repro.data import make_lm_corpus
from repro.data.pipeline import lm_round_batch
from repro.models.model import init_params, loss_fn
from repro.utils.tree import tree_map, tree_sq_dist


def _setup(arch="qwen3-4b", n=4, s=2, K=4, eta=0.05, seed=0, **fkw):
    cfg = get_reduced_config(arch)
    fcfg = FavasConfig(n_clients=n, s_selected=s, local_steps=K, eta=eta,
                       seed=seed, **fkw)
    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg)
    state = favas_init(params, fcfg, key)
    lambdas = jnp.asarray(client_lambdas(fcfg))

    def lfn(p, b):
        return loss_fn(p, cfg, b)
    step = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                     lambdas=lambdas))
    return cfg, fcfg, state, step


@functools.lru_cache(maxsize=None)
def _corpus(vocab, n_domains):
    return make_lm_corpus(vocab, 60_000, n_domains=n_domains, seed=0)


def _batch(cfg, fcfg, rng, B=2, S=32):
    # The trainer's structured corpus, NOT uniform random tokens: uniform
    # tokens have entropy log(V) = 6.24 nats, so no amount of training can
    # reduce the loss below that — the seed test only ever "passed" because
    # idle clients' zero contributions dragged the old loss metric down.
    tokens, domains = _corpus(cfg.vocab_size_raw, fcfg.n_clients)
    toks = lm_round_batch(tokens, domains, fcfg.n_clients, fcfg.R, B, S, rng)
    return {"tokens": jnp.asarray(toks)}


def test_favas_training_reduces_loss():
    cfg, fcfg, state, step = _setup()
    rng = np.random.default_rng(0)
    losses, stales = [], []
    for _ in range(12):
        state, m = step(state, _batch(cfg, fcfg, rng))
        losses.append(float(m["loss"]))
        stales.append(float(m["stale_rounds"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.3
    # the live-step-weighted loss must not re-spike to init level (log V).
    # Judged on 4-round window means, the window of the decrease check
    # above: one round's loss covers 2 clients x 2 x 32 tokens and swings by
    # ~0.3 nats between rounds, so a single round can touch log(V) - 0.1 on
    # one PRNG stream and not on another (JAX's threefry stream changed
    # after 0.4: the old stream's worst round after warm-up is 6.07, the
    # new one's 6.14, against a guard of 6.138) while every window mean
    # stays below 5.9 on both
    init_level = float(np.log(cfg.vocab_size_raw))
    windows = [np.mean(losses[i:i + 4]) for i in range(4, len(losses) - 3)]
    assert max(windows) < init_level - 0.1, losses
    assert max(stales) <= 2 * fcfg.n_clients, stales


def test_favas_round_counters_and_selection():
    cfg, fcfg, state, step = _setup(n=6, s=3)
    rng = np.random.default_rng(1)
    for _ in range(5):
        state, m = step(state, _batch(cfg, fcfg, rng))
        assert float(m["selected"]) == 3
        q = np.asarray(state.counters)
        assert q.min() >= 0 and q.max() <= fcfg.local_steps


def test_selected_clients_reset_to_server():
    """After a round, every client is either at the new server model (just
    selected, counter 0) or has nonzero counter."""
    cfg, fcfg, state, step = _setup(n=4, s=2)
    rng = np.random.default_rng(2)
    state, _ = step(state, _batch(cfg, fcfg, rng))
    q = np.asarray(state.counters)
    for i in range(fcfg.n_clients):
        ci = tree_map(lambda x: x[i], state.clients)
        d = float(tree_sq_dist(ci, state.server))
        if q[i] == 0:
            assert d < 1e-6, f"selected client {i} not reset (d={d})"
        else:
            assert d > 0.0


def test_variance_and_mu_finite():
    cfg, fcfg, state, step = _setup()
    rng = np.random.default_rng(3)
    for _ in range(3):
        state, _ = step(state, _batch(cfg, fcfg, rng))
    assert np.isfinite(float(favas_variance(state)))
    mu = favas_mu(state)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(mu))


def test_deterministic_reweight_round():
    cfg, fcfg, state, _ = _setup(reweight="deterministic")
    det = jnp.asarray(deterministic_alphas(fcfg))
    lambdas = jnp.asarray(client_lambdas(fcfg))

    def lfn(p, b):
        return loss_fn(p, cfg, b)
    step = jax.jit(functools.partial(favas_round, cfg=fcfg, loss_fn=lfn,
                                     lambdas=lambdas, det_alpha=det))
    rng = np.random.default_rng(4)
    state, m = step(state, _batch(cfg, fcfg, rng))
    assert np.isfinite(float(m["loss"]))


def test_quantized_round_runs():
    cfg, fcfg, state, step = _setup(quant_bits=4)
    rng = np.random.default_rng(5)
    state, m = step(state, _batch(cfg, fcfg, rng))
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(favas_variance(state)))


def test_rounds_are_reproducible():
    cfg, fcfg, s1, step = _setup(seed=7)
    _, _, s2, _ = _setup(seed=7)
    rng1, rng2 = np.random.default_rng(9), np.random.default_rng(9)
    s1, m1 = step(s1, _batch(cfg, fcfg, rng1))
    s2, m2 = step(s2, _batch(cfg, fcfg, rng2))
    assert float(m1["loss"]) == float(m2["loss"])
    assert float(tree_sq_dist(s1.server, s2.server)) == 0.0
