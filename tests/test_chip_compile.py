"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned or strided lane slices, blocks that break the
(8, 128) rule, kernels that overrun VMEM. Each test here compiles one entry
point for a v5e chip that is described, not attached, and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``). Nothing
runs, so nothing here is a result or a time. Each kernel also carries its
``pallas_call`` name, the name a profiler trace shows for it.

Shapes: the streamed aggregation at the bucket width of the Qwen3-4B
configuration ``chip_smoke.py`` trains (depth 1, a quarter of the
vocabulary), from ``jax.eval_shape`` on its ``make_flat_spec``; the
streamed kernel's wide lane blocks, and its dense-``progress`` variant, at
the bucket of the benchmark's Mamba2-1.3B fan-in cell (8 rows, 52,066,304
f32 lanes). At n=64 the client stacks of the smoke's width need about
95 GB, six times the chip's HBM, which the compiler refuses; the n=64
cases compile a sixteenth of the width instead — the block shapes do not
depend on D once it spans a block. The LUQ codec and the codes-in stream
compile at D = 2^20 for bits {2, 4, 8} x shards {1, 4}.

The topology is described inside a module fixture (never at import), so
workers that do not run this file never load the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import xla_client
from jax.sharding import SingleDeviceSharding

from repro.kernels import favas_agg
from repro.kernels.favas_agg import (STREAM_VMEM_BUDGET, TILE,
                                     favas_stream_pallas,
                                     stream_block_vmem_bytes, stream_width)
from repro.kernels.luq import luq_decode_pallas, luq_encode_pallas, luq_pallas

CODEC_D = 1 << 20
CELL_WIDTH = 52_066_304    # padded f32 bucket of the mamba2-1.3b fan-in cell
# how bench/metrics/agg_kernel_ms.py finds the streamed kernel in a trace:
# a Pallas call mapping the (1, D) server row, its first operand, to a new
# (1, D) row
SIGNATURE = re.compile(r"= f32\[1,(\d+)\]\{[^}]*\} custom-call\(f32\[1,\1\]\{")
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


def _stream(progress):
    """The streamed kernel, plain or with a dense transmitted ``progress``
    (FAVAS[QNN] with tree-space LUQ, the default transport)."""
    if progress:
        return (lambda w, c, i, p, a, m: favas_stream_pallas(
            w, c, i, a, m, 1.0, progress=p))
    return lambda w, c, i, a, m: favas_stream_pallas(w, c, i, a, m, 1.0)


@pytest.mark.parametrize("n", [2, 64])
@pytest.mark.parametrize("progress", [False, True],
                         ids=["stream", "progress"])
def test_round_kernel_compiles(one_chip, smoke_width, progress, n):
    w, c, i, a, m = _round_args(one_chip, n, _width(smoke_width, n))
    args = (w, c, i, c, a, m) if progress else (w, c, i, a, m)
    _compile("favas_stream", _stream(progress), *args)


@pytest.mark.parametrize("n", [2, 64])
def test_round_kernel_bf16_compiles(one_chip, smoke_width, n):
    args = _round_args(one_chip, n, _width(smoke_width, n), jnp.bfloat16)
    _compile("favas_stream", _stream(False), *args)


def test_progress_stream_compiles_cell_width(one_chip):
    """The dense-``progress`` variant, resident at the fan-in cell's
    bucket: three (8, D) stacks, TILE-wide blocks."""
    w, c, i, a, m = _round_args(one_chip, 8, CELL_WIDTH)
    _compile("favas_stream", _stream(True), w, c, i, c, a, m)


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


@pytest.mark.parametrize("n", [8, 32])
def test_wide_stream_kernel_compiles(one_chip, n, monkeypatch):
    """The resident streamed kernel's wide lane blocks at the fan-in
    cell's bucket (n = 8) and at a sixteenth of it for n = 32, where the
    blocks narrow: one ``favas_stream`` kernel whose event keeps the
    server-row signature the benchmark reads, a partial last block
    (D / TILE is prime), no HBM temporary beyond the TILE-wide kernel's,
    and no more VMEM than ``stream_block_vmem_bytes`` models."""
    D = CELL_WIDTH if n == 8 else CELL_WIDTH // 16 // TILE * TILE
    width = stream_width(n, jnp.float32, D)
    assert width > TILE and D % width
    args = _round_args(one_chip, n, D)
    step = jax.jit(lambda w, c, i, a, m: favas_stream_pallas(
        w, c, i, a, m, 2.0))
    wide = step.lower(*args).compile()
    monkeypatch.setattr(favas_agg, "STREAM_MAX_WIDTH", TILE)
    narrow = jax.jit(lambda w, c, i, a, m: favas_stream_pallas(
        w, c, i, a, m, 2.0)).lower(*args).compile()
    assert (wide.memory_analysis().temp_size_in_bytes
            <= narrow.memory_analysis().temp_size_in_bytes)
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = wide.runtime_executable().hlo_modules()[0].to_string(opts)
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1, calls
    assert re.search(r"%favas_stream(\.\d+)? = ", calls[0]), calls[0]
    match = SIGNATURE.search(calls[0])
    assert match and int(match.group(1)) == D, calls[0][:200]
    # the VMEM the compiler gives the kernel is inside the modelled budget
    used = re.search(r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                     r'"offset":"0","size":"(\d+)"', calls[0])
    assert int(used.group(1)) <= stream_block_vmem_bytes(
        n, jnp.float32, width) <= STREAM_VMEM_BUDGET
