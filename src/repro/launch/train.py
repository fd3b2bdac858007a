"""End-to-end FAVAS trainer CLI.

Runs on whatever devices exist: a 1-device CPU box (reduced configs, smoke/
example use) or the production mesh (full configs). One train step = one
FAVAS server round over the resident clients, driven by the flat-buffer
``core.round_engine.RoundEngine``: parameters live in contiguous flat
buffers across rounds, the jitted round donates them, and the fused
aggregation+reset runs as one pass (Pallas kernel on TPU, jnp oracle on
CPU; override with --use-kernel). With --mesh the engine is sharded: flat
buffers stay partitioned over the "model" mesh axis end-to-end
(docs/architecture.md §6) and the round never gathers them.

The host loop is pipelined (docs/architecture.md §7): with
``--rounds-per-step T`` every chunk of T rounds runs as ONE on-device
superstep dispatch (``RoundEngine.run``, bit-exact with T sequential
rounds), batch generation runs ahead on a background thread
(``data.pipeline.BatchPrefetcher``, H2D copies overlapped), and metrics
stay on device until a ``--log-every`` boundary — the loop never blocks
on a per-round ``float(loss)``. With ``--data-plane device`` batch
generation leaves the host entirely (docs/architecture.md §8): the token
corpus is uploaded once (``data.device_corpus``) and the superstep scan
samples every round's minibatch indices in-body (``RoundEngine.
run_device``) — no prefetcher, no per-chunk H2D batch copies.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 50 --n-clients 4 --s 2 --seq 128 --batch 4 --rounds-per-step 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import (save_engine_checkpoint, latest_checkpoint,
                                 load_engine_checkpoint)
from repro.configs import get_config, get_reduced_config
from repro.core import FavasConfig, RoundEngine, client_lambdas
from repro.data import make_lm_corpus
from repro.data.pipeline import BatchPrefetcher, lm_round_batch, \
    lm_superstep_batch
from repro.models.model import init_params, loss_fn
from repro.utils.compile_cache import setup_compile_cache
from repro.utils.metrics import MetricsLogger


def build_cli():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--n-clients", type=int, default=4)
    ap.add_argument("--s", type=int, default=2)
    ap.add_argument("--K", type=int, default=4)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4, help="per-client per-step")
    ap.add_argument("--reweight", default="stochastic",
                    choices=["stochastic", "deterministic"])
    ap.add_argument("--quant-bits", type=int, default=0)
    ap.add_argument("--quant-fused", action="store_true",
                    help="with --quant-bits > 0: transport the FAVAS[QNN] "
                         "progress as bit-packed LUQ codes + per-(row, "
                         "shard) scales all the way into the fused round "
                         "(dequantized per VMEM tile, no dense (n, D) f32 "
                         "progress buffer — docs/architecture.md §10); "
                         "default quantizes per leaf and hands the kernel "
                         "a dense dequantized buffer")
    ap.add_argument("--rounds-per-step", type=int, default=1,
                    help="rounds per superstep dispatch: T > 1 scans T "
                         "server rounds on-device in ONE jitted call "
                         "(bit-exact with T sequential rounds) and fetches "
                         "metrics once per chunk — removes per-round host "
                         "dispatch/sync overhead")
    ap.add_argument("--data-plane", default="host",
                    choices=["host", "device"],
                    help="host (default): numpy batch generation on the "
                         "background prefetch thread, batches shipped per "
                         "chunk; device: the token corpus is uploaded ONCE "
                         "and every round's minibatch indices are sampled "
                         "inside the on-device scan — zero host batch work "
                         "per round (docs/architecture.md §8; jax-PRNG "
                         "stream, statistically equivalent to host)")
    ap.add_argument("--residency", default="dense",
                    choices=["dense", "paged"],
                    help="client-state residency (docs/architecture.md §9): "
                         "dense keeps all n clients' full-precision (n, D) "
                         "buffers resident; paged keeps a hot working set "
                         "of --s-max rows plus a --cold-bits-encoded cold "
                         "pool covering all n clients — resident bytes drop "
                         "from O(n*D*4) to O(n*D*bits/8 + s_max*D*4)")
    ap.add_argument("--s-max", type=int, default=None,
                    help="hot working-set size for --residency paged "
                         "(default: n-clients, which is bit-exact with "
                         "dense when --cold-bits 0). Must be >= --s")
    ap.add_argument("--cold-bits", type=int, default=0,
                    choices=[0, 2, 4, 8],
                    help="cold-pool LUQ width for --residency paged: 0 = "
                         "passthrough (full precision, bit-exact parity "
                         "tool), 2/4/8 = bit-packed LUQ codes + per-(row, "
                         "shard) scales (kernels/luq.py math)")
    ap.add_argument("--cold-placement", default="device",
                    choices=["device", "host"],
                    help="where --residency paged keeps the cold pools "
                         "(docs/architecture.md §13): device (default) "
                         "holds them in HBM; host offloads them to host "
                         "memory and streams each superstep's churn-bounded "
                         "slab in/out around the dispatch — device bytes "
                         "scale with --s-max instead of --n-clients, "
                         "bit-exact with device placement")
    ap.add_argument("--use-kernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused Pallas aggregation kernel: auto = TPU only "
                         "(CPU gets the jnp oracle), on = force (interpret "
                         "mode off-TPU), off = always the oracle")
    ap.add_argument("--mesh", default="none",
                    help="device mesh for the sharded flat-buffer engine: "
                         "none (default, single-device), model / model=K "
                         "(1-D tensor-parallel mesh over local devices), "
                         "single, multi (production TPU meshes). Composes "
                         "with --use-kernel: the kernel runs per model "
                         "shard via shard_map, the oracle under pjit — "
                         "either way no full-buffer gather per round")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics", default=None, help="JSONL metrics path")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def run(args, cfg=None):
    """Train per the parsed CLI ``args``. ``cfg`` overrides the model
    configuration that ``--arch``/``--reduced`` would select (a caller that
    cuts a published config to size hands it in here). Returns ``(state,
    losses, engine)``: the final engine state, every round's loss, and the
    ``RoundEngine`` that ran them."""
    if cfg is None:
        cfg = (get_reduced_config(args.arch) if args.reduced
               else get_config(args.arch))
    fcfg = FavasConfig(n_clients=args.n_clients, s_selected=args.s,
                       local_steps=args.K, eta=args.eta,
                       reweight=args.reweight, quant_bits=args.quant_bits,
                       seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    lambdas = jnp.asarray(client_lambdas(fcfg))
    det_alpha = None
    if args.reweight == "deterministic":
        from repro.core import deterministic_alphas
        det_alpha = jnp.asarray(deterministic_alphas(fcfg))

    def lfn(p, b):
        return loss_fn(p, cfg, b)

    use_kernel = {"auto": None, "on": True, "off": False}[args.use_kernel]
    from repro.launch.mesh import mesh_from_arg, model_axis_size
    mesh = mesh_from_arg(args.mesh)
    if mesh is not None:
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"({model_axis_size(mesh)}-way model sharding of the engine)")
    engine = RoundEngine(params, fcfg, lfn, lambdas=lambdas,
                         det_alpha=det_alpha, use_kernel=use_kernel,
                         mesh=mesh, residency=args.residency,
                         s_max=args.s_max, cold_bits=args.cold_bits,
                         cold_placement=args.cold_placement,
                         quant_fused=args.quant_fused)
    if args.residency == "paged":
        print(f"residency: paged (s_max={engine.spec.s_max} hot rows, "
              f"cold codec {engine.spec.cold_codec}, "
              f"cold tier on {engine.spec.cold_placement})")
    state = engine.init_state(params, key)
    if args.residency == "paged":
        tiers = engine.resident_bytes_by_tier(state)
        print(f"resident bytes: device {tiers['device']:,} | "
              f"host {tiers['host']:,}")
    del params  # the flat buffers are now the authoritative copy

    if args.ckpt_dir:
        ck = latest_checkpoint(args.ckpt_dir)
        if ck:
            print(f"restoring {ck}")
            try:
                state = load_engine_checkpoint(ck, state)
            except (KeyError, ValueError) as e:
                raise SystemExit(
                    f"checkpoint {ck} does not match the flat-buffer "
                    f"EngineState layout ({e}). Checkpoints written before "
                    f"the round-engine change (pytree FavasState) or with a "
                    f"different parameter layout cannot be restored — start "
                    f"from a fresh --ckpt-dir.") from e

    tokens, domains = make_lm_corpus(cfg.vocab_size_raw, 400_000,
                                     n_domains=max(args.n_clients, 2),
                                     seed=args.seed)
    rng = np.random.default_rng(args.seed)
    logger = MetricsLogger(args.metrics)

    # chunk schedule: T-round supersteps plus a short remainder chunk
    T = max(args.rounds_per_step, 1)
    schedule = [T] * (args.steps // T)
    if args.steps % T:
        schedule.append(args.steps % T)

    device_plane = args.data_plane == "device"
    corpus = None
    if device_plane:
        # upload the corpus + per-client sampling tables ONCE; every chunk
        # is then a single dispatch with zero host batch-generation work
        from repro.data.device_corpus import make_lm_device_corpus
        corpus = make_lm_device_corpus(tokens, domains, fcfg.n_clients,
                                       args.batch, args.seq, mesh=mesh)

    def make_chunk(i):
        """Host batch generation for chunk i — runs on the prefetch thread,
        concurrently with the device's current superstep; the prefetcher
        also overlaps the H2D copy (device_put on that thread)."""
        W = schedule[i]
        if T == 1:
            b = lm_round_batch(tokens, domains, fcfg.n_clients, fcfg.R,
                               args.batch, args.seq, rng)
        else:
            b = lm_superstep_batch(tokens, domains, W, fcfg.n_clients,
                                   fcfg.R, args.batch, args.seq, rng)
        return {"tokens": b}

    losses = []
    pending = []      # (first_round_idx, W, device metrics) — NOT fetched yet
    rounds_done, next_log = 0, args.log_every
    next_ckpt = args.ckpt_every

    def flush():
        """Materialize pending chunk metrics (ONE host sync per flush) and
        emit the per-round JSONL records the per-round loop used to write."""
        nonlocal pending
        for start, W, m in pending:
            host = {k: np.atleast_1d(np.asarray(v)) for k, v in m.items()}
            for j in range(W):
                losses.append(float(host["loss"][j]))
                logger.log(start + j + 1, loss=host["loss"][j],
                           mean_steps=host["mean_steps"][j],
                           stale_rounds=host["stale_rounds"][j])
        pending = []

    prefetch = (None if device_plane
                else BatchPrefetcher(make_chunk, n_steps=len(schedule)))
    t0 = time.time()
    try:
        for W in schedule:
            if device_plane:
                state, metrics = engine.run_device(state, corpus, W)
            elif T == 1:
                batch = prefetch.get()
                state, metrics = engine.step(state, batch)
            else:
                batch = prefetch.get()
                state, metrics = engine.run(state, batch, n_rounds=W)
            pending.append((rounds_done, W, metrics))
            rounds_done += W
            # host syncs only at --log-every / --ckpt-every boundaries: the
            # loop above never blocks on a per-round float(loss). A chunk
            # can cross several boundaries at once; each gets its own
            # window mean. Client variance is measured once per crossing
            # chunk from the chunk-end state (the only state the host has)
            # and is labeled with THAT round number.
            need_var = rounds_done >= next_log
            rate = f"{rounds_done/(time.time()-t0):.2f} it/s"
            while rounds_done >= next_log:
                flush()
                window = losses[next_log - args.log_every:next_log]
                line = f"round {next_log:5d} | loss {np.mean(window):.4f}"
                if next_log == rounds_done:
                    # variance and throughput are measured at the chunk-end
                    # state/round — only printed on the line they belong to
                    var = float(engine.variance(state))
                    logger.log(rounds_done, client_variance=var)
                    line += f" | client-var {var:.3e} | {rate}"
                    need_var = False
                print(line)
                next_log += args.log_every
            if need_var:      # boundaries crossed mid-chunk only
                var = float(engine.variance(state))
                logger.log(rounds_done, client_variance=var)
                print(f"round {rounds_done:5d} | client-var {var:.3e} | {rate}")
            if args.ckpt_dir and rounds_done >= next_ckpt:
                # one snapshot per chunk (mid-chunk state never exists on
                # the host); keep the cadence anchored to --ckpt-every
                # multiples even when a chunk crosses several boundaries
                save_engine_checkpoint(args.ckpt_dir, rounds_done, state)
                while next_ckpt <= rounds_done:
                    next_ckpt += args.ckpt_every
    finally:
        if prefetch is not None:
            prefetch.close()
    flush()
    print(f"done: first-10 loss {np.mean(losses[:10]):.4f} -> "
          f"last-10 {np.mean(losses[-10:]):.4f}")
    return state, losses, engine


def main():
    args = build_cli().parse_args()
    setup_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
