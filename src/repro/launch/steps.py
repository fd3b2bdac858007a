"""Step builders + ShapeDtypeStruct input specs for every
(architecture x input-shape) combination — the dry-run and the real
launchers share these.

Input shapes (task assignment):
  train_4k     seq 4096,   global_batch 256   -> FAVAS train_step (one round)
  prefill_32k  seq 32768,  global_batch 32    -> serve_prefill
  decode_32k   seq 32768,  global_batch 128   -> serve_step (1 token + cache)
  long_500k    seq 524288, global_batch 1     -> serve_step, sub-quadratic
                                                 archs only (DESIGN.md §4)
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.favas import FavasConfig, favas_init, favas_round, \
    favas_multi_round, client_lambdas
from repro.launch.mesh import data_axes, n_client_slots
from repro.models.model import ModelConfig, init_params, loss_fn, forward, \
    init_cache, decode_step
from repro.sharding.rules import param_specs, favas_state_specs, check_divisible

INPUT_SHAPES = {
    "train_4k": dict(seq=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq=524288, global_batch=1, kind="decode"),
}

N_PATCHES = 256       # stubbed vision tokens (qwen2-vl)


def supports(cfg: ModelConfig, shape_name: str) -> bool:
    """long_500k needs sub-quadratic attention: SSM, hybrid (RG-LRU + local
    window), or a sliding-window dense variant."""
    if shape_name != "long_500k":
        return True
    return cfg.arch_type in ("ssm", "hybrid") or cfg.window > 0


def serve_config(cfg: ModelConfig) -> ModelConfig:
    """Serving runs bf16 weights, no remat."""
    return dataclasses.replace(cfg, param_dtype="bfloat16", remat=False)


def apply_variant(cfg: ModelConfig, variant: str, seq: int,
                  model_shards: int) -> ModelConfig:
    """"base" = paper-faithful baseline lowering; "opt" = beyond-paper perf
    config (§Perf): residual-stream sequence sharding over "model" when the
    shape divides."""
    if (variant == "opt" and seq % model_shards == 0 and seq > 1
            and cfg.seq_shard_friendly):
        return dataclasses.replace(cfg, act_seq_axis="model")
    return cfg


# ---------------------------------------------------------------------------
# Input ShapeDtypeStructs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ModelConfig, fcfg: FavasConfig, seq: int,
                      global_batch: int) -> Dict:
    n, R = fcfg.n_clients, fcfg.R
    B_loc = max(global_batch // n, 1)
    batch = {"tokens": _sds((n, R, B_loc, seq), jnp.int32)}
    if cfg.arch_type == "audio":
        batch["enc_frames"] = _sds((n, R, B_loc, cfg.enc_seq, cfg.d_model),
                                   jnp.bfloat16)
    if cfg.arch_type == "vlm":
        batch["patch_embeds"] = _sds((n, R, B_loc, N_PATCHES, cfg.d_model),
                                     jnp.bfloat16)
    return batch


def prefill_batch_specs(cfg: ModelConfig, seq: int, global_batch: int) -> Dict:
    batch = {"tokens": _sds((global_batch, seq), jnp.int32)}
    if cfg.arch_type == "audio":
        batch["enc_frames"] = _sds((global_batch, cfg.enc_seq, cfg.d_model),
                                   jnp.bfloat16)
    if cfg.arch_type == "vlm":
        batch["patch_embeds"] = _sds((global_batch, N_PATCHES, cfg.d_model),
                                     jnp.bfloat16)
    return batch


def lm_corpus_specs(fcfg: FavasConfig, seq: int, global_batch: int,
                    n_tokens: int = 400_000):
    """ShapeDtypeStruct stand-in for a device-resident LM corpus
    (``data.device_corpus.DeviceCorpus``): the token stream + per-client
    window-start tables the device data plane samples in-scan
    (docs/architecture.md §8). Shardable (replicated), no allocation."""
    from repro.data.device_corpus import DeviceCorpus
    n = fcfg.n_clients
    B_loc = max(global_batch // n, 1)
    return DeviceCorpus(kind="lm", batch=B_loc, seq=seq,
                        tokens=_sds((n_tokens,), jnp.int32),
                        lo=_sds((n,), jnp.int32),
                        span=_sds((n,), jnp.int32))


def input_specs(arch: str, shape_name: str,
                fcfg: Optional[FavasConfig] = None, mesh=None) -> Dict:
    """Public entry: ShapeDtypeStruct stand-ins for every model input of the
    given (arch, shape) — weak-type-correct, shardable, no allocation."""
    cfg = get_config(arch)
    info = INPUT_SHAPES[shape_name]
    if info["kind"] == "train":
        fcfg = fcfg or default_favas_config(mesh)
        return train_batch_specs(cfg, fcfg, info["seq"], info["global_batch"])
    if info["kind"] == "prefill":
        return prefill_batch_specs(serve_config(cfg), info["seq"],
                                   info["global_batch"])
    B = info["global_batch"]
    return {"token": _sds((B, 1), jnp.int32), "pos": _sds((), jnp.int32)}


def default_favas_config(mesh=None, **overrides) -> FavasConfig:
    n = n_client_slots(mesh) if mesh is not None else 16
    kw = dict(n_clients=n, s_selected=max(n // 4, 1), local_steps=8, eta=1e-3)
    kw.update(overrides)
    return FavasConfig(**kw)


# ---------------------------------------------------------------------------
# Sharding for batches and caches
# ---------------------------------------------------------------------------

def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _dp(mesh):
    da = data_axes(mesh)
    return da if len(da) > 1 else da[0]


def batch_shardings(batch_sds, mesh, *, leading_client_axis: bool,
                    leading_rounds_axis: bool = False):
    """``leading_rounds_axis``: the batch carries a superstep (T,) rounds
    axis in front — the scan axis is never device-sharded, so the data axes
    move to dim 1."""
    dp = _dp(mesh)
    sizes = _axis_sizes(mesh)
    lead = 1 if leading_rounds_axis else 0

    def one(sds):
        dims = [None] * len(sds.shape)
        dims[lead] = dp
        spec = P(*check_divisible(sds.shape, tuple(dims), sizes))
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(one, batch_sds)


def cache_specs(cache_sds, mesh, cfg: ModelConfig):
    """PartitionSpec tree for a decode cache: batch over data axes, KV-cache
    sequence over "model" (distributed flash-decode), SSM/RNN inner channels
    over "model"."""
    dp = _dp(mesh)
    sizes = _axis_sizes(mesh)
    stacked = cfg.uniform_stack()

    def one(path, sds):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        ps = "/".join(names)
        rank = len(sds.shape)
        prefix = (None,) if (stacked and "layers" in names) else ()
        body_rank = rank - len(prefix)
        if re.search(r"/(k|v)$", ps) or names[-1] in ("k", "v"):
            dims = (dp, "model", None, None)
        elif names[-1] in ("k_scale", "v_scale"):
            dims = (dp, "model", None)
        elif names[-1] == "state":
            dims = (dp, "model", None, None)
        elif names[-1] == "conv_x":
            dims = (dp, None, "model")
        elif names[-1] in ("conv_B", "conv_C"):
            dims = (dp, None, None)
        elif names[-1] == "h":
            dims = (dp, "model")
        elif names[-1] == "conv":
            dims = (dp, None, "model")
        elif "cross_kv" in names:
            dims = (dp, None, "model", None)
        else:
            dims = (dp,) + (None,) * (body_rank - 1)
        dims = dims[:body_rank] + (None,) * max(body_rank - len(dims), 0)
        full = prefix + tuple(dims)
        return P(*check_divisible(sds.shape, full, sizes))

    return jax.tree_util.tree_map_with_path(one, cache_sds)


# ---------------------------------------------------------------------------
# Step builders (shared by dryrun.py, train.py, serve.py)
# ---------------------------------------------------------------------------

def build_train_step(arch: str, mesh, fcfg: Optional[FavasConfig] = None,
                     *, use_agg_kernel: bool = False, variant: str = "opt",
                     rounds_per_step: int = 1, data_plane: str = "host"):
    """Returns (jitted_step, state_sds, batch_sds). train_step = one FAVAS
    server round over the resident clients — or, with ``rounds_per_step`` >
    1, one SUPERSTEP: that many rounds scanned on-device in a single
    dispatch (``favas_multi_round``; batch gains a leading (T,) rounds axis
    and metrics come back (T,)-stacked).

    ``data_plane="device"`` (docs/architecture.md §8): the step's second
    operand becomes a replicated ``DeviceCorpus`` stand-in instead of a
    batch — the superstep samples every round's minibatches in-scan, so
    the host ships no batch bytes at all. Token-corpus archs only (audio /
    VLM side inputs have no corpus sampler yet)."""
    cfg = get_config(arch)
    ms = _axis_sizes(mesh)["model"]
    cfg = apply_variant(cfg, variant, INPUT_SHAPES["train_4k"]["seq"], ms)
    fcfg = fcfg or default_favas_config(mesh)
    lambdas = jnp.asarray(client_lambdas(fcfg))

    def lfn(p, b):
        return loss_fn(p, cfg, b)

    if data_plane not in ("host", "device"):
        raise ValueError(f"unknown data_plane {data_plane!r}")
    if data_plane == "device" and cfg.arch_type in ("audio", "vlm"):
        raise ValueError(
            f"--data-plane device needs a pure token corpus; {arch} "
            f"({cfg.arch_type}) feeds extra side inputs per batch")

    def step(state, batch):
        # use_agg_kernel=False keeps the jnp oracle under pjit (XLA fuses the
        # flat-buffer expression); True forces the Pallas kernel.
        if data_plane == "device":
            # batch IS the resident corpus; minibatches are sampled in-scan
            return favas_multi_round(state, corpus=batch,
                                     n_rounds=max(rounds_per_step, 1),
                                     cfg=fcfg, loss_fn=lfn, lambdas=lambdas,
                                     use_kernel=use_agg_kernel)
        if rounds_per_step > 1:
            return favas_multi_round(state, batch, cfg=fcfg, loss_fn=lfn,
                                     lambdas=lambdas,
                                     use_kernel=use_agg_kernel)
        return favas_round(state, batch, cfg=fcfg, loss_fn=lfn,
                           lambdas=lambdas, use_kernel=use_agg_kernel)

    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_sds = jax.eval_shape(functools.partial(init_params, cfg=cfg), key_sds)
    state_sds = jax.eval_shape(
        functools.partial(favas_init, cfg=fcfg), params_sds, key=key_sds)

    sspec = favas_state_specs(state_sds, mesh, cfg)
    state_sh = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), sspec,
        is_leaf=lambda x: isinstance(x, P))
    info = INPUT_SHAPES["train_4k"]
    if data_plane == "device":
        batch_sds = lm_corpus_specs(fcfg, info["seq"], info["global_batch"])
        # the corpus is a replicated side input: every shard gathers locally
        batch_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, P()), batch_sds)
    else:
        batch_sds = train_batch_specs(cfg, fcfg, info["seq"],
                                      info["global_batch"])
        if rounds_per_step > 1:
            batch_sds = jax.tree_util.tree_map(
                lambda s: _sds((rounds_per_step,) + s.shape, s.dtype),
                batch_sds)
        batch_sh = batch_shardings(batch_sds, mesh, leading_client_axis=True,
                                   leading_rounds_axis=rounds_per_step > 1)
    metrics_sh = {k: NamedSharding(mesh, P()) for k in
                  ("loss", "mean_steps", "selected", "stale_rounds",
                   "live_steps", "computed_steps")}
    jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh), donate_argnums=(0,))
    return jitted, (state_sds, batch_sds), cfg


def build_prefill_step(arch: str, mesh, shape_name: str = "prefill_32k",
                       *, variant: str = "opt"):
    cfg = serve_config(get_config(arch))
    info = INPUT_SHAPES[shape_name]
    cfg = apply_variant(cfg, variant, info["seq"], _axis_sizes(mesh)["model"])

    def step(params, batch):
        logits, _ = forward(params, cfg, batch)
        return logits

    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_sds = jax.eval_shape(functools.partial(init_params, cfg=cfg), key_sds)
    pspec = param_specs(params_sds, mesh, cfg)
    params_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspec,
                                       is_leaf=lambda x: isinstance(x, P))
    batch_sds = prefill_batch_specs(cfg, info["seq"], info["global_batch"])
    batch_sh = batch_shardings(batch_sds, mesh, leading_client_axis=False)
    logits_sh = NamedSharding(mesh, P(_dp(mesh), None, "model"))
    jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                     out_shardings=logits_sh)
    return jitted, (params_sds, batch_sds), cfg


def build_serve_step(arch: str, mesh, shape_name: str, *, variant: str = "opt"):
    """One-token decode with a seq_len KV cache."""
    cfg = serve_config(get_config(arch))
    if variant == "opt":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    info = INPUT_SHAPES[shape_name]
    B, S = info["global_batch"], info["seq"]

    def step(params, cache, token, pos):
        return decode_step(params, cfg, cache, token, pos)

    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_sds = jax.eval_shape(functools.partial(init_params, cfg=cfg), key_sds)
    pspec = param_specs(params_sds, mesh, cfg)
    params_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspec,
                                       is_leaf=lambda x: isinstance(x, P))
    cache_sds = jax.eval_shape(
        functools.partial(init_cache, cfg, B, S, dtype=jnp.bfloat16))
    if cfg.arch_type == "audio":
        # cross-KV filled by prefill; materialize specs for it too
        hd = cfg.head_dim
        xkv = [(_sds((B, cfg.enc_seq, cfg.n_kv_heads, hd), jnp.bfloat16),) * 2
               for _ in range(cfg.n_layers)]
        cache_sds = dict(cache_sds)
        cache_sds["cross_kv"] = [tuple(t) for t in xkv]
    cspec = cache_specs(cache_sds, mesh, cfg)
    cache_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), cspec,
                                      is_leaf=lambda x: isinstance(x, P))
    dp = _dp(mesh)
    sizes = _axis_sizes(mesh)
    tok_spec = P(*check_divisible((B, 1), (dp, None), sizes))
    token_sh = NamedSharding(mesh, tok_spec)
    pos_sh = NamedSharding(mesh, P())
    logits_sh = NamedSharding(mesh, P(*check_divisible(
        (B, 1, cfg.vocab_size), (dp, None, "model"), sizes)))
    jitted = jax.jit(step, in_shardings=(params_sh, cache_sh, token_sh, pos_sh),
                     out_shardings=(logits_sh, cache_sh), donate_argnums=(1,))
    token_sds = _sds((B, 1), jnp.int32)
    pos_sds = _sds((), jnp.int32)
    return jitted, (params_sds, cache_sds, token_sds, pos_sds), cfg


def build_step(arch: str, shape_name: str, mesh,
               fcfg: Optional[FavasConfig] = None, variant: str = "opt"):
    kind = INPUT_SHAPES[shape_name]["kind"]
    if kind == "train":
        return build_train_step(arch, mesh, fcfg, variant=variant) + ("train",)
    if kind == "prefill":
        return build_prefill_step(arch, mesh, shape_name,
                                  variant=variant) + ("prefill",)
    return build_serve_step(arch, mesh, shape_name, variant=variant) + ("decode",)
