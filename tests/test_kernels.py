"""Per-kernel validation (task spec c): sweep shapes/dtypes and
assert_allclose against the ref.py pure-jnp oracles, interpret=True on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, ops
from repro.core import round_engine
from repro.kernels.favas_agg import favas_stream_pallas
from repro.kernels.luq import luq_pallas


@pytest.mark.parametrize("n,D", [(2, 17), (4, 1000), (8, 2048), (16, 4097),
                                 (32, 65536)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_favas_stream_kernel_matches_ref(n, D, dtype):
    key = jax.random.PRNGKey(n * 1000 + D)
    ks = jax.random.split(key, 5)
    server = jax.random.normal(ks[0], (D,), dtype)
    clients = jax.random.normal(ks[1], (n, D), dtype)
    inits = jax.random.normal(ks[2], (n, D), dtype)
    alpha = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=8.0)
    mask = (jax.random.uniform(ks[4], (n,)) > 0.5).astype(jnp.float32)
    s = float(mask.sum())
    out_k = favas_stream_pallas(server, clients, inits, alpha, mask, s,
                                interpret=True)
    out_r = ref.favas_stream_ref(server, clients, inits, alpha, mask, s)
    # the kernel body and the ref are the same expressions, compiled apart
    # (FMA contraction): identical to an f32 ULP, but the bf16 OUTPUT cast
    # can then differ by 1 ULP (~2^-8 relative) on either side.
    tol = dict(rtol=2e-6, atol=2e-6) if dtype == jnp.float32 else \
        dict(rtol=8e-3, atol=8e-3)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), **tol)


@pytest.mark.parametrize("shape", [(64,), (1000,), (33, 129), (4, 5, 6)])
@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_luq_kernel_matches_ref(shape, bits, dtype):
    key = jax.random.PRNGKey(sum(shape) + bits)
    x = jax.random.normal(key, shape, dtype)
    up = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    ur = jax.random.uniform(jax.random.fold_in(key, 2), shape)
    out_k = luq_pallas(x, up, ur, bits, interpret=True)
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)))
    out_r = ref.luq_ref(x, up, ur, scale, bits)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=1e-6, atol=1e-6)
    assert out_k.dtype == x.dtype and out_k.shape == x.shape


def test_luq_output_is_on_grid():
    """Every quantized magnitude must be scale * 2^{-j} or 0."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (4096,))
    q = ops.luq_quantize(x, 3, key, use_kernel=True)
    scale = float(jnp.max(jnp.abs(x)))
    mags = np.abs(np.asarray(q)) / scale
    nz = mags[mags > 0]
    logs = np.log2(nz)
    np.testing.assert_allclose(logs, np.round(logs), atol=1e-5)
    assert logs.min() >= -(2 ** 2 - 1)


def test_ops_tree_aggregation_matches_loop():
    """The per-bucket streamed step (kernel path) on a flattened small
    pytree == naive python-loop oracle, leaf by leaf."""
    key = jax.random.PRNGKey(4)
    n = 4
    tree = {"a": jax.random.normal(key, (8, 6)),
            "b": {"c": jax.random.normal(key, (11,))}}
    C = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 1),
                                    (n,) + x.shape), tree)
    I = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 2),
                                    (n,) + x.shape), tree)
    alpha = jnp.array([1.0, 2.0, 4.0, 8.0])
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    s = 2.0
    spec = round_engine.make_flat_spec(tree, n_clients=n)
    server = round_engine.flatten_tree(spec, tree)
    srv, cli, ini = round_engine.aggregate_and_reset(
        spec, server, round_engine.flatten_stacked(spec, C),
        round_engine.flatten_stacked(spec, I), alpha, mask,
        jnp.array([0, 2], jnp.int32), s, n_logical=n, use_kernel=True)
    got = round_engine.unflatten_tree(spec, srv)

    def naive(w, Cl, Il):
        acc = np.asarray(w, np.float64).copy()
        for i in range(n):
            if float(mask[i]):
                msg = np.asarray(Il[i], np.float64) + (
                    np.asarray(Cl[i], np.float64)
                    - np.asarray(Il[i], np.float64)) / float(alpha[i])
                acc += msg
        return acc / (s + 1.0)
    want = jax.tree_util.tree_map(naive, tree, C, I)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5, atol=1e-5)
    # the selected clients (rows 0 and 2) now hold the new server, the
    # others their own state
    for stack, orig in ((cli, C), (ini, I)):
        back = round_engine.unflatten_stacked(spec, stack)
        for b, o, g in zip(jax.tree_util.tree_leaves(back),
                           jax.tree_util.tree_leaves(orig),
                           jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(b)[[0, 2]],
                                          np.stack([np.asarray(g)] * 2))
            np.testing.assert_array_equal(np.asarray(b)[[1, 3]],
                                          np.asarray(o)[[1, 3]])
