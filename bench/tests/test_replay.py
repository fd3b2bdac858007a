"""The live-step replay reads the engine's own draws: at a chunk end its
counters equal the engine's, on the CPU at a toy size."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import replay

TRAFFIC = {"n_clients": 8, "s": 2, "K": 3, "slow_fraction": 1 / 3,
           "lam_fast": 1 / 16, "lam_slow": 0.5}


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_replay_matches_engine_counters(seed):
    from repro.configs import get_reduced_config
    from repro.core import FavasConfig, RoundEngine
    from repro.data.device_corpus import make_lm_device_corpus
    from repro.models.model import init_params, loss_fn
    cfg = dataclasses.replace(get_reduced_config("qwen3-4b"), d_model=32,
                              n_heads=2, n_kv_heads=1, head_dim=16, d_ff=32,
                              vocab_size_raw=64, n_layers=1)
    tr = TRAFFIC
    fcfg = FavasConfig(n_clients=tr["n_clients"], s_selected=tr["s"],
                       local_steps=tr["K"], eta=0.05)
    lambdas = replay.speed_law(tr)
    params = init_params(jax.random.PRNGKey(1), cfg)
    engine = RoundEngine(params, fcfg, lambda p, b: loss_fn(p, cfg, b),
                         lambdas=jnp.asarray(lambdas))
    key = replay.state_key(seed)
    state = engine.init_state(params, key)
    tokens = np.arange(4000, dtype=np.int32) % 64
    domains = np.repeat(np.arange(8), 500).astype(np.int32)
    corpus = make_lm_device_corpus(tokens, domains, 8, 1, 8)
    T, chunks = 5, 3
    for _ in range(chunks):
        state, _ = engine.run_device(state, corpus, T)
    live, q = replay.live_steps(key, lambdas, K=tr["K"], s=tr["s"],
                                n_rounds=T * chunks)
    np.testing.assert_array_equal(q, np.asarray(state.counters))
    assert live.shape == (T * chunks,) and live.min() >= 0
    assert live[0] >= 8                       # every client starts live


def test_large_seeds_do_not_collide():
    a = replay.state_key(7)
    b = replay.state_key(2 ** 32 + 7)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_speed_law_is_the_same_for_every_seed():
    lam = replay.speed_law(TRAFFIC)
    assert list(lam[:3]) == [0.5] * 3 and list(lam[3:]) == [1 / 16] * 5
