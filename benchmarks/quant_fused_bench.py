"""Quantized-transport round benchmark: codes-in fused rounds vs the
unfused decode -> round -> requant composition (docs/architecture.md §10);
a CPU design aid (device numbers come from ``bench/`` on the chip).

Both arms run the same logical FAVAS[QNN] loop: the transmitted progress
lives as bit-packed LUQ codes + per-row scales between rounds, each round
aggregates the decoded progress and re-encodes the post-reset deltas for
the next round. The difference is the TRANSPORT:

* **unfused** (the pre-PR-7 composition) — three separate jitted
  dispatches per round: ``luq_decode_rows`` materializes the dense (n, D)
  f32 progress in HBM, the engine's round step
  (``round_engine.aggregate_and_reset``) consumes it, and
  ``luq_encode_rows`` re-encodes. The dense progress buffer crosses HBM
  twice per round (decode write + round read) on top of the dispatch
  overhead.
* **fused** — ONE jitted ``lax.scan`` over the whole chunk whose body
  feeds the codes straight into the round step (``progress_codes=``;
  dequantized inside the aggregation — per VMEM tile on the kernel path) and
  re-encodes via ``kernels.ops.cold_requant_rows``. No standalone decode
  dispatch, no host round-trips inside the chunk.

Acceptance (the ISSUE-7 gate, checked in smoke mode and recorded in the
artifact): fused rounds/sec >= unfused rounds/sec at chunk 32.

Results go to ``experiments/bench/quant_fused.json`` AND the repo-root
``BENCH_quant_fused.json`` (the perf-trajectory file).

  PYTHONPATH=src:. python benchmarks/quant_fused_bench.py [--full|--smoke]

``--smoke`` (the CI ``quant-kernel`` job) runs n = 256 only and exits
non-zero if the fused arm is slower; smoke artifacts go to
``quant_fused_smoke.json`` and never overwrite the canonical files.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.common import save_artifact
from repro.core import round_engine
from repro.core.paging import luq_decode_rows, luq_encode_rows
from repro.kernels.ops import cold_requant_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D = 2048
BITS = 4
CHUNK = 32
S_FRAC = 0.25


def _setup(n: int, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    server = jax.random.normal(ks[0], (D,), jnp.float32)
    clients = jax.random.normal(ks[1], (n, D), jnp.float32)
    inits = jax.random.normal(ks[2], (n, D), jnp.float32)
    alpha = jax.random.uniform(ks[3], (n,), minval=0.5, maxval=2.0)
    s = max(int(n * S_FRAC), 1)
    mask = (jnp.arange(n) < s).astype(jnp.float32)
    mask = jax.random.permutation(ks[4], mask)
    enc0 = luq_encode_rows(clients - inits, BITS, ks[5])
    return server, clients, inits, alpha, mask, float(s), enc0, key


def _step(n, alpha, mask, s, **progress):
    """The engine's round step (oracle path) on one (n, D) bucket; the
    selection is the rows ``mask`` selects."""
    spec = round_engine.make_flat_spec({"w": jnp.zeros((D,), jnp.float32)},
                                       n_clients=n)
    sel_idx = jnp.flatnonzero(mask, size=int(s))

    def step(srv, cli, ini, prog=None, enc=None):
        (srv,), (cli,), (ini,) = round_engine.aggregate_and_reset(
            spec, (srv,), (cli,), (ini,), alpha, mask, sel_idx, s,
            progress=None if prog is None else (prog,),
            progress_codes=None if enc is None else (enc,),
            progress_bits=BITS, n_logical=n, use_kernel=False)
        return srv, cli, ini
    return step


def _run_unfused(n: int, reps: int) -> dict:
    """Host loop, three dispatches per round: decode -> dense round ->
    requant. The (n, D) f32 progress exists in HBM between dispatches."""
    server, clients, inits, alpha, mask, s, enc0, key = _setup(n)

    decode = jax.jit(lambda e: luq_decode_rows(e, BITS, jnp.float32))
    rnd = jax.jit(_step(n, alpha, mask, s))
    requant = jax.jit(lambda cli, ini, k: luq_encode_rows(
        cli.astype(jnp.float32) - ini.astype(jnp.float32), BITS, k))

    def chunk(srv, cli, ini, enc):
        for r in range(CHUNK):
            prog = decode(enc)
            srv, cli, ini = rnd(srv, cli, ini, prog)
            enc = requant(cli, ini, jax.random.fold_in(key, r))
        return srv, cli, ini, enc

    out = chunk(server, clients, inits, enc0)          # compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = chunk(server, clients, inits, enc0)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return {"seconds": best, "rounds_per_sec": CHUNK / best,
            "dispatches_per_round": 3,
            "progress_hbm_bytes_per_round": n * D * 4}


def _run_fused(n: int, reps: int) -> dict:
    """One jitted scan per chunk; the body consumes codes directly."""
    server, clients, inits, alpha, mask, s, enc0, key = _setup(n)
    step = _step(n, alpha, mask, s)

    def body(carry, r):
        srv, cli, ini, enc = carry
        srv, cli, ini = step(srv, cli, ini, enc=enc)
        enc = cold_requant_rows(
            cli.astype(jnp.float32) - ini.astype(jnp.float32), BITS,
            jax.random.fold_in(key, r), use_kernel=False)
        return (srv, cli, ini, enc), jnp.zeros(())

    @jax.jit
    def chunk(srv, cli, ini, enc):
        (srv, cli, ini, enc), _ = jax.lax.scan(
            body, (srv, cli, ini, enc), jnp.arange(CHUNK))
        return srv, cli, ini, enc

    out = chunk(server, clients, inits, enc0)          # compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = chunk(server, clients, inits, enc0)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return {"seconds": best, "rounds_per_sec": CHUNK / best,
            "dispatches_per_round": 1.0 / CHUNK,
            "progress_hbm_bytes_per_round": n * (D * BITS // 8 + 4)}


def run(quick: bool = True, smoke: bool = False) -> dict:
    reps = 2 if (quick or smoke) else 4
    populations = [256] if smoke else ([256, 1024] if quick
                                       else [256, 1024, 4096])
    sweep = []
    for n in populations:
        unf = _run_unfused(n, reps)
        fus = _run_fused(n, reps)
        sweep.append({
            "n_clients": n,
            "unfused": unf, "fused": fus,
            "fused_over_unfused": (fus["rounds_per_sec"]
                                   / unf["rounds_per_sec"]),
            "progress_bytes_ratio": (unf["progress_hbm_bytes_per_round"]
                                     / fus["progress_hbm_bytes_per_round"]),
        })
    rows = {
        "config": {"D": D, "bits": BITS, "chunk": CHUNK,
                   "selected_fraction": S_FRAC,
                   "backend": jax.default_backend(),
                   "note": "jnp oracle path (CPU container); the kernel "
                           "path additionally dequantizes per VMEM tile "
                           "on TPU"},
        "sweep": sweep,
        "acceptance": "fused rounds/sec >= unfused rounds/sec at chunk 32",
    }
    if smoke:
        save_artifact("quant_fused_smoke", rows)
        return rows
    save_artifact("quant_fused", rows)
    with open(os.path.join(ROOT, "BENCH_quant_fused.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main() -> int:
    smoke = "--smoke" in sys.argv
    rows = run(quick="--full" not in sys.argv, smoke=smoke)
    ok = True
    for r in rows["sweep"]:
        rel = r["fused_over_unfused"]
        print(f"n={r['n_clients']:5d} | unfused "
              f"{r['unfused']['rounds_per_sec']:8.1f} r/s | fused "
              f"{r['fused']['rounds_per_sec']:8.1f} r/s | x{rel:.2f} | "
              f"progress bytes x{r['progress_bytes_ratio']:.1f} smaller")
        ok = ok and rel >= 1.0
    if not ok:
        print("FAIL: fused codes-in rounds slower than the unfused "
              "decode->round->requant composition at chunk 32")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
