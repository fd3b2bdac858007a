"""Bring-up smoke of the FAVAS trainer on TPU.

Drives ``repro.launch.train.run`` — the trainer's own entry point — at the
published widths of Qwen3-4B (``configs/qwen3_4b.py``) with random weights
made from a seed, and checks what comes out by the repo's own means.

  python chip_smoke.py             # one chip: both data planes
  python chip_smoke.py --chips 4   # the sharded engine on a 4-chip mesh

One chip: depth 1 and a quarter of the vocabulary (one chip's share of a
4-way vocabulary split); a few FAVAS rounds on the host and on the device
data plane, with the Pallas aggregation kernel chosen automatically. Checks:
every round's loss is finite, the compiled round holds the Pallas kernel
(``tpu_custom_call``), and the kernel agrees with the jnp oracle on the
live client buffers.

``--chips 4``: depth 1 and the full vocabulary, the engine's flat buffers
sharded 4-way over a ``("model",)`` mesh (shard_map plus a per-shard
kernel). Checks: finite losses, four distinct devices hold every bucket,
the per-bucket kernel path agrees with the pjit oracle on the live
sharded buffers, and the compiled round has no all-gather at full-buffer
size.

The script needs a TPU: without one it exits non-zero before printing any
result. Times and rates it prints are smoke output, not measurements. The
last line of standard output is the JSON result the caller reads.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.utils.compile_cache import setup_compile_cache  # noqa: E402

ARCH = "qwen3-4b"
FULL_VOCAB = 151936
N_CLIENTS, S_SELECTED, K_STEPS, BATCH, SEQ = 2, 1, 2, 2, 512
ROUNDS, ROUNDS_PER_STEP = 4, 2
# Kernel-vs-oracle bound, in f32 ULPs of the per-lane accumulator magnitude
# |server| + sum_i |mask_i * msg_i| over (s + 1) — the measure of
# kernels/favas_agg.py and tests/test_tiled_kernel.py. Both paths evaluate
# the same f32 expressions and, at n <= CLIENT_TILE, reduce the clients in
# the same order; the compiled kernel and XLA may still contract a
# multiply-add or round a division differently, each worth at most one
# ULP of that magnitude — the 2-ULP budget tests/test_quant_fused.py
# allows for the same reason.
ULP_BOUND = 2.0


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def cut_config(vocab: int):
    """Qwen3-4B at its published widths, cut to depth 1 (one whole period
    of a dense layer pattern) and to ``vocab`` token ids."""
    from repro.configs import get_config
    cfg = get_config(ARCH)
    cut = dataclasses.replace(cfg, n_layers=1, vocab_size_raw=vocab)
    print(f"config: {ARCH} d_model={cut.d_model} heads={cut.n_heads}/"
          f"{cut.n_kv_heads}x{cut.head_dim} d_ff={cut.d_ff} "
          f"qk_norm={cut.qk_norm} tied={cut.tie_embeddings} "
          f"params={cut.param_dtype} compute={cut.compute_dtype}")
    print(f"cuts: n_layers {cfg.n_layers} -> 1; vocab ids "
          f"{cfg.vocab_size_raw} -> {vocab} (table rows {cut.vocab_size} "
          f"after the config's pad to {cut.vocab_pad_to})")
    return cut


def train_args(data_plane: str, mesh: str = "none"):
    from repro.launch.train import build_cli
    return build_cli().parse_args([
        "--arch", ARCH, "--steps", str(ROUNDS),
        "--n-clients", str(N_CLIENTS), "--s", str(S_SELECTED),
        "--K", str(K_STEPS), "--batch", str(BATCH), "--seq", str(SEQ),
        "--rounds-per-step", str(ROUNDS_PER_STEP), "--use-kernel", "auto",
        "--data-plane", data_plane, "--mesh", mesh,
        "--log-every", str(ROUNDS_PER_STEP), "--seed", "0"])


def compile_seconds(fn):
    """Run ``fn()`` and return (result, seconds JAX spent tracing, lowering
    and compiling inside it)."""
    import jax
    spent = []

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            spent.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        out = fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return out, sum(spent)


def train(cfg, data_plane: str, mesh: str = "none"):
    from repro.launch.train import run
    t0 = time.time()
    (state, losses, engine), comp = compile_seconds(
        lambda: run(train_args(data_plane, mesh), cfg=cfg))
    print(f"[{data_plane} plane] losses per round: {losses}")
    print(f"[{data_plane} plane] compile {comp:.1f}s of {time.time() - t0:.1f}"
          f"s wall (smoke output, not a measurement)")
    check(len(losses) == ROUNDS, f"{len(losses)} losses for {ROUNDS} rounds")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss on the {data_plane} plane: {losses}")
    return state, engine


def peak_bytes(tag: str):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[{tag}] device 0 peak_bytes_in_use={peak}")


def round_text(engine, state):
    """Compiled text of the engine's host-plane superstep at the smoke's
    shapes."""
    import jax
    import jax.numpy as jnp
    batch = {"tokens": jax.ShapeDtypeStruct(
        (ROUNDS_PER_STEP, N_CLIENTS, engine.cfg.R, BATCH, SEQ), jnp.int32)}
    return engine._multi.lower(state, batch).compile().as_text()


def ulp_ratio(server, clients, inits, alpha, mask, s, got, want):
    """max over lanes of |got - want| / (ULP of the accumulator magnitude
    over (s + 1)), on device."""
    import jax.numpy as jnp
    f32 = jnp.float32
    cf, inf = clients.astype(f32), inits.astype(f32)
    msg = inf + (cf - inf) / alpha[:, None]
    acc = jnp.abs(server.astype(f32)) + jnp.sum(
        jnp.abs(mask[:, None] * msg), axis=0)
    ulp = (jnp.nextafter(acc, jnp.inf) - acc) / (s + 1.0)
    diff = jnp.abs(got.astype(f32) - want.astype(f32))
    # exact lanes count 0 even where the ULP flushes to zero (zero lanes)
    return jnp.max(jnp.where(diff == 0, 0.0, diff / ulp))


def fixed_selection(n: int):
    """Every client selected, distinct non-trivial eq. 3 coefficients."""
    import jax.numpy as jnp
    return (1.5 + 0.5 * jnp.arange(n, dtype=jnp.float32),
            jnp.ones((n,), jnp.float32), float(n))


def one_chip():
    import jax
    from repro.kernels.ops import favas_stream_flat

    cfg = cut_config(FULL_VOCAB // 4)
    state, engine = train(cfg, "host")
    peak_bytes("host plane")
    text = round_text(engine, state)
    check("tpu_custom_call" in text, "no Pallas kernel in the compiled round")
    print("[host plane] compiled round holds the Pallas kernel "
          "(tpu_custom_call)")

    spec = engine.spec
    alpha, mask, s = fixed_selection(spec.n_padded)

    @jax.jit
    def compare(server, clients, inits):
        args = (server, clients, inits, alpha, mask, s)
        got = favas_stream_flat(*args, use_kernel=True)
        want = favas_stream_flat(*args, use_kernel=False)
        return ulp_ratio(*args, got, want)

    for b in range(spec.n_buckets):
        r = float(compare(state.server[b], state.clients[b], state.inits[b]))
        print(f"[kernel vs oracle] bucket {b} ({spec.bucket_dtypes[b]}, "
              f"D={spec.bucket_padded[b]}): max error {r:.3f} ULP "
              f"(bound {ULP_BOUND})")
        check(r <= ULP_BOUND, f"kernel vs oracle {r} ULP > {ULP_BOUND}")
    del state, engine

    train(cfg, "device")
    peak_bytes("device plane")


def four_chips():
    import jax
    import jax.numpy as jnp
    from repro.core.round_engine import stream_bucket_update
    from repro.launch.roofline import collective_ops

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    cfg = cut_config(FULL_VOCAB)
    state, engine = train(cfg, "host", mesh="model=4")
    peak_bytes("sharded")
    spec, mesh = engine.spec, engine.mesh
    mesh_devices = set(mesh.devices.flat)
    check(len(mesh_devices) == 4, f"mesh spans {len(mesh_devices)} devices")
    print(f"[sharded] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"over devices {sorted(d.id for d in mesh_devices)}")
    check(any(spec.shards(b) > 1 for b in range(spec.n_buckets)),
          "no bucket is model-sharded")
    for name in ("server", "clients", "inits"):
        for b, buf in enumerate(getattr(state, name)):
            held = {sh.device for sh in buf.addressable_shards}
            check(held == mesh_devices,
                  f"{name}[{b}] held by {len(held)} devices")
            if spec.shards(b) > 1:
                widths = {sh.data.shape[-1] for sh in buf.addressable_shards}
                check(widths == {spec.bucket_padded[b] // 4},
                      f"{name}[{b}] shard widths {widths}")
    print("[sharded] every bucket spans all four devices; model-sharded "
          "buckets hold a quarter of their lanes per device")

    alpha, mask, s = fixed_selection(spec.n_padded)
    for b in range(spec.n_buckets):
        layout = ("4 shards" if spec.shards(b) > 1
                  else "replicated, whole bucket per device")

        @jax.jit
        def compare(server, clients, inits, b=b):
            args = (server, clients, inits, alpha, mask, s)
            got, want = (stream_bucket_update(
                spec, b, *args, n_logical=spec.n_clients, mesh=mesh,
                use_kernel=k) for k in (True, False))
            return ulp_ratio(*args, got, want)

        r = float(compare(state.server[b], state.clients[b], state.inits[b]))
        print(f"[sharded kernel vs pjit oracle] bucket {b} "
              f"(D={spec.bucket_padded[b]}, {layout}): max error {r:.3f} "
              f"ULP (bound {ULP_BOUND})")
        check(r <= ULP_BOUND, f"sharded kernel vs oracle {r} ULP")

    text = round_text(engine, state)
    check("tpu_custom_call" in text, "no Pallas kernel in the sharded round")
    full = min(spec.bucket_padded[b] * jnp.dtype(spec.bucket_dtypes[b]).itemsize
               for b in range(spec.n_buckets) if spec.shards(b) > 1)
    census = collective_ops(text)
    gathers = [nb for kind, nb in census if kind == "all-gather"]
    print(f"[sharded] collectives in the compiled round: "
          f"{sorted(set(k for k, _ in census))}; all-gather bytes max "
          f"{max(gathers, default=0)} vs full buffer {full}")
    check(all(nb < full for nb in gathers), "full-buffer all-gather")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    cache = setup_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}")
    print(f"compile cache: {cache}")
    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{jax.default_backend()!r}")
    four_chips() if args.chips == 4 else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
