"""Tests for the §Perf optimization features: int8 KV cache, activation
sequence-sharding, the distributed FedAvg baseline, and the dry-run
integration (subprocess — the only place 512 fake devices exist).
"""
import dataclasses
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core.favas import FavasConfig
from repro.core.fedavg import fedavg_round
from repro.models.model import init_params, forward, init_cache, decode_step, loss_fn

B, S = 2, 16


def test_int8_kv_cache_close_to_bf16():
    cfg = get_reduced_config("llama3-8b")
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size_raw)
    full, _ = forward(params, cfg, {"tokens": toks})
    cache = init_cache(cfg8, B, S, dtype=jnp.float32)
    assert cache["layers"]["k"].dtype == jnp.int8
    logits = None
    for t in range(S):
        logits, cache = decode_step(params, cfg8, cache, toks[:, t:t + 1],
                                    jnp.int32(t))
    err = float(jnp.max(jnp.abs(full[:, -1] - logits[:, 0])))
    assert err < 0.15, f"int8 KV error too large: {err}"


def test_act_seq_axis_numerically_identical():
    """Sharding constraints must not change values (1-device mesh)."""
    cfg = get_reduced_config("qwen3-4b")
    cfg_s = dataclasses.replace(cfg, act_seq_axis="model")
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size_raw)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    base, _ = forward(params, cfg, {"tokens": toks})
    with jax.sharding.use_mesh(mesh) if hasattr(jax.sharding, "use_mesh") \
            else mesh:
        opt, _ = jax.jit(lambda p, b: forward(p, cfg_s, b))(
            params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(base), np.asarray(opt),
                               rtol=1e-5, atol=1e-4)


def test_fedavg_round_trains():
    cfg = get_reduced_config("qwen3-4b")
    fcfg = FavasConfig(n_clients=4, s_selected=2, local_steps=3, eta=0.05)
    key = jax.random.PRNGKey(2)
    server = init_params(key, cfg)
    lfn = lambda p, b: loss_fn(p, cfg, b)
    step = jax.jit(functools.partial(fedavg_round, cfg=fcfg, loss_fn=lfn))
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(6):
        toks = rng.integers(0, cfg.vocab_size_raw,
                            (4, fcfg.local_steps, B, S)).astype(np.int32)
        server, key, m = step(server, key, {"tokens": jnp.asarray(toks)})
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_dryrun_one_combo_subprocess(tmp_path):
    """The 512-device dry-run must succeed end-to-end (cheapest combo). It
    writes its report under ``tmp_path``, not over the tracked one."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "recurrentgemma-2b", "--shape", "long_500k", "--mesh", "multi",
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "1 ok" in out.stdout
