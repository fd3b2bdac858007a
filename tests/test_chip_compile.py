"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned or strided lane slices, blocks that break the
(8, 128) rule, kernels that overrun VMEM. Each test here compiles one entry
point for a v5e chip that is described, not attached, and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``). Nothing
runs, so nothing here is a result or a time. Each kernel also carries its
``pallas_call`` name, the name a profiler trace shows for it.

Shapes: the streamed and fused rounds at the bucket width of the Qwen3-4B
configuration ``chip_smoke.py`` trains (depth 1, a quarter of the
vocabulary), from ``jax.eval_shape`` on its ``make_flat_spec``. At n=64 the
client stacks of that width need about 95 GB, six times the chip's HBM,
which the compiler refuses; the n=64 cases compile a sixteenth of the width
instead — the kernels' block shapes do not depend on D. The LUQ codec and
the codes-in stream compile at D = 2^20 for bits {2, 4, 8} x shards {1, 4}.

The topology is described inside a module fixture (never at import), so
workers that do not run this file never load the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.favas_agg import (TILE, favas_agg_pallas,
                                     favas_fused_pallas, favas_stream_pallas)
from repro.kernels.luq import luq_decode_pallas, luq_encode_pallas, luq_pallas

CODEC_D = 1 << 20
BITS = [2, 4, 8]
SHARDS = [1, 4]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke_width():
    """Padded f32 bucket width of the smoke's cut Qwen3-4B configuration."""
    from repro.configs import get_config
    from repro.core.round_engine import make_flat_spec
    from repro.models.model import init_params
    cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=1,
                              vocab_size_raw=151936 // 4)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    spec = make_flat_spec(params, n_clients=2)
    assert spec.bucket_dtypes == ("float32",)
    return spec.bucket_padded[0]


def _compile(name, fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    kernels = re.findall(
        r'%([\w.\-]+) = .*custom_call_target="tpu_custom_call"', text)
    assert kernels and all(re.fullmatch(rf"{name}(\.\d+)?", k)
                           for k in kernels), kernels
    return text


def _round_args(one_chip, n, D, dtype=jnp.float32):
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (s((D,), dtype), s((n, D), dtype), s((n, D), dtype), s((n,)),
            s((n,)))


def _width(smoke_width, n):
    if n <= 2:
        return smoke_width
    return smoke_width // 16 // TILE * TILE


@pytest.mark.parametrize("n", [2, 64])
@pytest.mark.parametrize("kernel,name",
                         [(favas_stream_pallas, "favas_stream"),
                          (favas_fused_pallas, "favas_fused")],
                         ids=["stream", "fused"])
def test_round_kernel_compiles(one_chip, smoke_width, kernel, name, n):
    args = _round_args(one_chip, n, _width(smoke_width, n))
    _compile(name, lambda *a: kernel(*a, 1.0), *args)


@pytest.mark.parametrize("n", [2, 64])
def test_round_kernel_bf16_compiles(one_chip, smoke_width, n):
    args = _round_args(one_chip, n, _width(smoke_width, n), jnp.bfloat16)
    _compile("favas_stream", lambda *a: favas_stream_pallas(*a, 1.0), *args)


def test_agg_kernel_compiles(one_chip):
    _compile("favas_agg", lambda *a: favas_agg_pallas(*a, 1.0),
             *_round_args(one_chip, 64, CODEC_D))


def test_luq_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((CODEC_D,), jnp.float32, sharding=one_chip)
    _compile("luq", lambda x, u, v: luq_pallas(x, u, v, 4), x, x, x)


@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("bits", BITS)
def test_luq_encode_compiles(one_chip, bits, shards):
    x = jax.ShapeDtypeStruct((64, CODEC_D), jnp.float32, sharding=one_chip)
    _compile("luq_encode",
             lambda x, u, v: luq_encode_pallas(x, u, v, bits, shards=shards),
             x, x, x)


def _codes(one_chip, rows, bits, shards):
    return {"codes": jax.ShapeDtypeStruct((rows, CODEC_D * bits // 8),
                                          jnp.uint8, sharding=one_chip),
            "scale": jax.ShapeDtypeStruct((rows, shards), jnp.float32,
                                          sharding=one_chip)}


@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("bits", BITS)
def test_luq_decode_compiles(one_chip, bits, shards):
    _compile("luq_decode",
             lambda e: luq_decode_pallas(e, bits, jnp.float32, shards=shards),
             _codes(one_chip, 64, bits, shards))


@pytest.mark.parametrize("n", [2, 64])
@pytest.mark.parametrize("shards", SHARDS)
@pytest.mark.parametrize("bits", BITS)
def test_codes_in_stream_compiles(one_chip, bits, shards, n):
    def step(w, c, i, a, m, e):
        return favas_stream_pallas(w, c, i, a, m, 1.0, progress_codes=e,
                                   progress_bits=bits, progress_shards=shards)
    _compile("favas_stream", step, *_round_args(one_chip, n, CODEC_D),
             _codes(one_chip, n, bits, shards))
