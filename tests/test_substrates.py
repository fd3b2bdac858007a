"""Substrate tests: data partitioners, pipelines, optimizers, checkpointing,
sharding rules, theory calculator, FL simulator."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import (make_classification, make_lm_corpus, partition_iid,
                        partition_label_skew, FederatedBatcher, lm_round_batch)
from repro.optim import sgd, momentum, adamw, cosine_schedule
from repro.checkpointing import save_checkpoint, load_checkpoint, latest_checkpoint
from repro.sharding.rules import check_divisible, spec_for
from repro.core.theory import TheoryParams, units_of_time, favas_speed_constants
from repro.core.fl_sim import SimConfig, run_simulation


# ------------------------------ data ---------------------------------------

def test_partition_label_skew_covers_all_samples():
    _, y, _, _ = make_classification("mnist-like", n_train=2000, n_test=10)
    parts = partition_label_skew(y, 10, 2, seed=0)
    allidx = np.concatenate(parts)
    assert len(allidx) == 2000 and len(np.unique(allidx)) == 2000
    for p in parts:
        assert len(np.unique(y[p])) <= 2        # non-IID: <=2 classes/client


def test_partition_iid():
    parts = partition_iid(1000, 7)
    assert sum(len(p) for p in parts) == 1000


def test_federated_batcher_shapes():
    x, y, _, _ = make_classification("mnist-like", n_train=1000, n_test=10)
    parts = partition_iid(1000, 5)
    b = FederatedBatcher(x, y, parts, 16)
    xs, ys = b.round_batch(3)
    assert xs.shape == (5, 3, 16, 784) and ys.shape == (5, 3, 16)


def test_lm_corpus_and_round_batch():
    toks, doms = make_lm_corpus(500, 50_000, n_domains=4)
    assert toks.max() < 500
    rng = np.random.default_rng(0)
    batch = lm_round_batch(toks, doms, 4, 2, 3, 64, rng)
    assert batch.shape == (4, 2, 3, 64)
    assert batch.dtype == np.int32


# ------------------------------ optim --------------------------------------

@pytest.mark.parametrize("opt", [sgd(0.1), momentum(0.1), adamw(0.1)])
def test_optimizers_minimize_quadratic(opt):
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for t in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state = opt.update(g, state, params, jnp.int32(t))
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_cosine_schedule_shape():
    fn = cosine_schedule(1.0, warmup=10, total=100)
    assert float(fn(jnp.int32(0))) == 0.0
    assert abs(float(fn(jnp.int32(10))) - 1.0) < 1e-6
    assert float(fn(jnp.int32(100))) < 0.2


# ------------------------------ checkpoint ---------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    p = save_checkpoint(str(tmp_path), 3, tree)
    assert latest_checkpoint(str(tmp_path)) == p
    back = load_checkpoint(p, tree)
    for x, y in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# ------------------------------ sharding -----------------------------------

def test_check_divisible_drops_bad_axes():
    sizes = {"model": 16, "data": 16}
    assert check_divisible((24, 64), ("model", None), sizes) == (None, None)
    assert check_divisible((32, 64), ("model", None), sizes) == ("model", None)
    assert check_divisible((256,), (("data", "model"),), {"model": 16, "data": 16}
                           ) == ((("data", "model")),)
    # 128 is NOT divisible by the 256-way combined axis -> replicate
    assert check_divisible((128,), (("data", "model"),), {"model": 16, "data": 16}
                           ) == (None,)


def test_spec_rules():
    sizes = {"model": 16, "data": 16, "pod": 2}
    s = spec_for("layers/attn/wq/w", (2, 4096, 4096), sizes, prefix=(None,))
    assert tuple(s) == (None, None, "model")
    s = spec_for("embed/table", (51968, 1024), sizes)
    assert tuple(s) == ("model", None)
    s = spec_for("layers/mlp/down", (2, 40, 512, 1536), sizes, prefix=(None,))
    assert tuple(s) == (None, None, "model", None)
    s = spec_for("layers/0/rnn/out/w", (2560, 2560), sizes)
    assert tuple(s) == ("model", None)


def test_param_specs_smoke():
    """All specs materialize on a 1-device mesh (divisibility -> replicate)."""
    from repro.configs import get_reduced_config
    from repro.models.model import init_params
    from repro.sharding.rules import param_specs
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch in ["llama3-8b", "granite-moe-3b-a800m", "mamba2-1.3b",
                 "recurrentgemma-2b"]:
        cfg = get_reduced_config(arch)
        params = jax.eval_shape(
            lambda k, c=cfg: init_params(k, c),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = param_specs(params, mesh, cfg)
        assert len(jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: x is None or hasattr(x, "index"))) > 0


def test_model_mesh_axes_are_auto():
    """The engine's meshes use Auto axes: JAX 0.9's ``jax.make_mesh``
    defaults to Explicit, under which the engine's flatten/unflatten
    reshapes of sharded buckets raise ``ShardingTypeError``."""
    from repro.launch.mesh import make_model_mesh
    mesh = make_model_mesh(1)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


# ------------------------------ theory -------------------------------------

def test_units_of_time_all_positive():
    T = units_of_time(TheoryParams())
    assert set(T) == {"FedAvg", "FedBuff", "AsyncSGD", "QuAFL", "FAVAS"}
    assert all(v > 0 for v in T.values())


def test_favas_bound_insensitive_to_straggler_severity():
    """The paper's headline: FedBuff/AsyncSGD bounds grow with tau_max
    (slow/fast ratio); FAVAS's does not grow comparably."""
    mild = TheoryParams(slow_step_time=16.0)
    harsh = TheoryParams(slow_step_time=1000.0)
    Tm, Th = units_of_time(mild), units_of_time(harsh)
    growth_fedbuff = Th["FedBuff"] / Tm["FedBuff"]
    growth_favas = Th["FAVAS"] / Tm["FAVAS"]
    assert growth_fedbuff > 3.0 * growth_favas


def test_speed_constants_finite():
    a, b = favas_speed_constants(TheoryParams())
    assert np.isfinite(a) and np.isfinite(b) and a > 0 and b >= 1.0


# ------------------------------ FL simulator --------------------------------

@pytest.mark.parametrize("method", ["favas", "quafl", "fedavg", "fedbuff",
                                    "asyncsgd"])
def test_fl_sim_short_run(method):
    x, y, xt, yt = make_classification("mnist-like", n_train=600, n_test=200,
                                       seed=0)
    parts = partition_label_skew(y, 6, 2, seed=0)
    cfg = SimConfig(method=method, n_clients=6, s_selected=2, K=3,
                    total_time=120, eval_every=60, eta=0.2, batch_size=32)
    r = run_simulation(cfg, (x, y, xt, yt, parts), d_hidden=32)
    assert (np.diff(r["times"]) >= 0).all()
    assert np.isfinite(r["accuracy"]).all()
    assert 0.0 <= r["final_accuracy"] <= 1.0


# ------------------------------ metrics ------------------------------------

def test_metrics_logger_jsonl(tmp_path):
    from repro.utils.metrics import MetricsLogger
    import json as _json
    p = str(tmp_path / "m.jsonl")
    lg = MetricsLogger(p, window=3)
    for t in range(5):
        lg.log(t, loss=float(10 - t))
    assert abs(lg.mean("loss") - 7.0) < 1e-9      # mean of last 3: 8,7,6
    lg.close()
    lines = [_json.loads(l) for l in open(p)]
    assert len(lines) == 5 and lines[-1]["loss"] == 6.0


# ------------------------------ placement ----------------------------------

def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the cache
    goes to the fixed, git-ignored ``.jax_cache/`` of the checkout."""
    from repro.utils.compile_cache import CACHE_DIR, setup_compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CACHE_DIR == os.path.join(root, ".jax_cache")
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert setup_compile_cache() == CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cluster_clients_start_on_cpu(monkeypatch):
    """Client processes start with ``JAX_PLATFORMS=cpu`` (they model edge
    devices; the accelerator belongs to the server), and the parent's own
    setting comes back afterwards."""
    from repro.launch.cluster import _clients_on_cpu
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with _clients_on_cpu():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with _clients_on_cpu():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ
