"""Kernel microbenchmarks for the FAVAS round hot path (a CPU design aid;
device numbers come from ``bench/`` on the chip).

Measures the REAL round step the engine runs
(``round_engine.aggregate_and_reset`` — the aggregation, then the new
server row written into the selected rows; on CPU the jnp oracle
``favas_stream_ref``, on TPU the ``favas_stream`` Pallas kernel) against
the seed's unfused multi-pass arithmetic (eq. 3 msgs, line-10 sum, two
reset sweeps as separate full-buffer passes). A client-count sweep (n in
{64, 256, 1024, 4096}, constant n*D resident client elements) records
step-vs-seed bytes moved and throughput at production federation sizes —
the regime the tiled client-axis kernel exists for. Also validates the
step on the Pallas kernel in interpret mode at a small resident shape AND
a tiled (n > CLIENT_TILE) shape against the whole-round oracle
``favas_fused_ref`` (structural check; interpret-mode *timing* is
meaningless — TPU is the target). The artifact keeps its key names
(``favas_round_fused_*`` is the step).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed, save_artifact
from repro.core import round_engine
from repro.kernels import ref
from repro.kernels.favas_agg import CLIENT_TILE, TILE
from repro.kernels.ops import luq_quantize


def _round_step(server, clients, use_kernel=False):
    """The engine's round step on one bucket of raw (D,) / (n, D) buffers,
    jitted; the selection is the rows ``mask`` selects (its indices are
    taken on the host, so ``s`` is ``mask``'s sum)."""
    n = clients.shape[0]
    spec = round_engine.make_flat_spec({"w": server}, n_clients=n)

    def step(w, c, i, a, m, sel_idx):
        (srv,), (cli,), (ini,) = round_engine.aggregate_and_reset(
            spec, (w,), (c,), (i,), a, m, sel_idx, sel_idx.shape[0],
            n_logical=n, use_kernel=use_kernel)
        return srv, cli, ini
    return jax.jit(step)


def _sel(mask):
    return jnp.asarray(np.flatnonzero(np.asarray(mask)), jnp.int32)


def _round_unfused(server, clients, inits, alpha, mask, s):
    """The seed's per-pass round arithmetic on flat buffers: each line is a
    separate full-buffer sweep in the unfused HLO."""
    a = alpha[:, None]
    m = mask[:, None]
    prog = clients - inits                                   # pass 1
    msgs = inits + prog / a                                  # pass 2
    total = jnp.sum(m * msgs, axis=0)                        # pass 3 (reduce)
    server_new = (server + total) / (s + 1.0)
    clients_new = m * server_new[None] + (1.0 - m) * clients  # pass 4
    inits_new = m * server_new[None] + (1.0 - m) * inits      # pass 5
    return server_new, clients_new, inits_new


def run(quick=True):
    key = jax.random.PRNGKey(0)
    n, D = (8, 1 << 20) if quick else (32, 1 << 24)
    ks = jax.random.split(key, 5)
    server = jax.random.normal(ks[0], (D,))
    clients = jax.random.normal(ks[1], (n, D))
    inits = jax.random.normal(ks[2], (n, D))
    alpha = jax.random.uniform(ks[3], (n,), minval=1.0, maxval=8.0)
    mask = (jax.random.uniform(ks[4], (n,)) > 0.5).astype(jnp.float32)
    sel = _sel(mask)
    s = float(sel.shape[0])

    # full round: aggregation + reset — the engine's step vs seed multi-pass
    fused = _round_step(server, clients)
    unfused = jax.jit(lambda *a: _round_unfused(*a, s))
    t_fused = timed(fused, server, clients, inits, alpha, mask, sel, reps=10)
    t_unfused = timed(unfused, server, clients, inits, alpha, mask, reps=10)

    # aggregation only (the kernel's scope)
    agg_ref = jax.jit(lambda *a: ref.favas_stream_ref(*a, s))
    t_agg = timed(agg_ref, server, clients, inits, alpha, mask, reps=10)

    x = jax.random.normal(key, (D,))
    luq_ref_fn = jax.jit(lambda x, k: luq_quantize(x, 4, k, use_kernel=False))
    t_luq = timed(luq_ref_fn, x, key, reps=10)

    # structural validation of the step on the Pallas kernel (compiled on
    # a TPU, interpret mode elsewhere): one resident shape, one tiled shape
    # (client blocks + row padding)
    kernel_ok = True
    for nv, Dv in ((4, 5000), (CLIENT_TILE * 2 + 7, 3000)):
        kv = jax.random.split(jax.random.PRNGKey(1), 5)
        sv = jax.random.normal(kv[0], (Dv,))
        cv = jax.random.normal(kv[1], (nv, Dv))
        iv = jax.random.normal(kv[2], (nv, Dv))
        av = jax.random.uniform(kv[3], (nv,), minval=1.0, maxval=8.0)
        mv = (jax.random.uniform(kv[4], (nv,)) > 0.5).astype(jnp.float32)
        sl = _sel(mv)
        got = _round_step(sv, cv, use_kernel=True)(sv, cv, iv, av, mv, sl)
        want = ref.favas_fused_ref(sv, cv, iv, av, mv, float(sl.shape[0]))
        kernel_ok = kernel_ok and all(
            np.allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6)
            for g, w in zip(got, want))

    # client-count sweep at constant total resident bytes: the engine's
    # round step (what the tiled kernel streams on TPU) vs the seed's
    # multi-pass arithmetic, n from demo scale to production federations
    # 2^23 quick / 2^24 full keeps D >= TILE at n=4096, so every sweep point
    # really does hold the same element count (constant working set)
    sweep_elems = 1 << (23 if quick else 24)   # elements per (n, D) operand
    n_sweep = []
    for ns in (64, 256, 1024, 4096):
        Ds = max(sweep_elems // ns, TILE)
        kw = jax.random.split(jax.random.PRNGKey(ns), 5)
        sw = jax.random.normal(kw[0], (Ds,))
        cw = jax.random.normal(kw[1], (ns, Ds))
        iw = jax.random.normal(kw[2], (ns, Ds))
        aw = jax.random.uniform(kw[3], (ns,), minval=1.0, maxval=8.0)
        mw = (jax.random.uniform(kw[4], (ns,)) > 0.5).astype(jnp.float32)
        sl = _sel(mw)
        ssel = float(sl.shape[0])
        t_f = timed(_round_step(sw, cw), sw, cw, iw, aw, mw, sl, reps=5)
        t_u = timed(jax.jit(lambda *a: _round_unfused(*a, ssel)),
                    sw, cw, iw, aw, mw, reps=5)
        bytes_n = (4 * ns + 2) * Ds * 4
        n_sweep.append({
            "n": ns, "D": Ds, "bytes": bytes_n,
            "fused_us": t_f, "unfused_us": t_u,
            "fused_gbps": bytes_n / (t_f * 1e-6) / 1e9,
            "unfused_gbps": bytes_n / (t_u * 1e-6) / 1e9,
            "speedup": t_u / t_f,
        })

    # sharded-vs-replicated round: runs in a forced-8-device subprocess
    # (only launch/dryrun.py and spawned children ever fake the topology)
    sharded = _run_sharded_subprocess()

    bytes_round = (4 * n + 2) * D * 4        # r/w server + clients + inits
    bytes_agg = (2 * n + 2) * D * 4
    rows = {
        "favas_round_fused_jnp_us": t_fused,
        "favas_round_fused_gbps": bytes_round / (t_fused * 1e-6) / 1e9,
        "favas_round_unfused_jnp_us": t_unfused,
        "favas_round_unfused_gbps": bytes_round / (t_unfused * 1e-6) / 1e9,
        "favas_agg_jnp_us": t_agg,
        "favas_agg_gbps": bytes_agg / (t_agg * 1e-6) / 1e9,
        "luq_jnp_us": t_luq,
        "elements": D,
        "clients": n,
        "client_tile": CLIENT_TILE,
        "n_sweep": n_sweep,
        "sharded_round": sharded,
        "fused_kernel_interpret_matches_ref": bool(kernel_ok),
        "note": "fused = the engine's round step (aggregation, then the"
                " selected rows' reset); unfused = the seed's multi-pass"
                " arithmetic. n_sweep holds"
                " n*D (the resident client working set) constant while n"
                " scales to production federation sizes (the tiled"
                " client-axis regime). Pallas"
                " kernels validated vs these refs in tests/; interpret-mode"
                " timing is not meaningful, TPU is the target.",
    }
    save_artifact("kernel_bench", rows)
    return rows


# ---------------------------------------------------------------------------
# Sharded-vs-replicated round (docs/architecture.md §6)
# ---------------------------------------------------------------------------

def _run_sharded_subprocess(timeout: int = 900) -> dict:
    """Spawn ``python -m benchmarks.kernel_bench --sharded-child`` under
    XLA_FLAGS=--xla_force_host_platform_device_count=8 and parse its JSON.
    The fake topology must never leak into this process (see
    tests/conftest.py), hence the subprocess. The child audits the
    structure of a CPU mesh, so it is pinned to ``JAX_PLATFORMS=cpu``: it
    never competes with this process for an accelerator. A failed child
    fails the benchmark."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernel_bench", "--sharded-child"],
        capture_output=True, text=True, env=env, cwd=root, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"sharded child exited {out.returncode}:\n"
                           f"{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _sharded_child():
    """Child body: time the FAVAS round step on flat buffers sharded over
    an 8-way ("model",) mesh (shard_map/pjit dispatch via
    ``round_engine.aggregate_and_reset``) vs the replicated single-device
    engine at the same shapes, and audit the sharded HLO for all-gathers.
    CPU "devices" here are host threads, so the us columns measure overhead
    structure, not TPU speedup — the all_gather_bytes column is the point:
    the sharded round moves NO full buffer across the mesh."""
    from jax.sharding import NamedSharding
    from repro.core import round_engine
    from repro.launch.mesh import make_model_mesh
    from repro.launch.roofline import collective_ops

    mesh = make_model_mesh(8)
    rec = {"status": "ok", "devices": int(jax.device_count()), "sweep": []}
    for ns, Ds in ((32, 1 << 14), (256, 1 << 14)):
        tree = {"wq": {"w": jnp.zeros((Ds // 128, 128), jnp.float32)}}
        spec_s = round_engine.make_flat_spec(tree, n_clients=ns,
                                             shard_axes=[1], model_shards=8)
        spec_r = round_engine.make_flat_spec(tree, n_clients=ns)
        kw = jax.random.split(jax.random.PRNGKey(ns), 5)
        rows = spec_s.n_padded or ns
        srv = jax.random.normal(kw[0], (spec_s.bucket_padded[0],))
        cli = jax.random.normal(kw[1], (rows, spec_s.bucket_padded[0]))
        ini = jax.random.normal(kw[2], (rows, spec_s.bucket_padded[0]))
        alpha = jnp.pad(jax.random.uniform(kw[3], (ns,), minval=1.0,
                                           maxval=8.0), (0, rows - ns),
                        constant_values=1.0)
        mask = jnp.pad((jax.random.uniform(kw[4], (ns,)) > 0.5)
                       .astype(jnp.float32), (0, rows - ns))
        sel = _sel(mask)
        sh = round_engine.engine_sharding(spec_s, mesh)
        srv_s = jax.device_put(srv, sh.server[0])
        cli_s = jax.device_put(cli, sh.clients[0])
        ini_s = jax.device_put(ini, sh.inits[0])

        def step(spec, mesh):
            return jax.jit(
                lambda w, c, i, a, m: round_engine.aggregate_and_reset(
                    spec, (w,), (c,), (i,), a, m, sel, sel.shape[0],
                    n_logical=ns, mesh=mesh, use_kernel=False))
        step_sh, step_rep = step(spec_s, mesh), step(spec_r, None)
        t_sh = timed(step_sh, srv_s, cli_s, ini_s, alpha, mask, reps=5)
        t_rep = timed(step_rep, srv, cli, ini, alpha, mask, reps=5)
        hlo = step_sh.lower(srv_s, cli_s, ini_s, alpha, mask).compile().as_text()
        ag = [b for kind, b in collective_ops(hlo) if kind == "all-gather"]
        bytes_n = (4 * rows + 2) * spec_s.bucket_padded[0] * 4
        rec["sweep"].append({
            "n": ns, "D": spec_s.bucket_padded[0], "bytes": bytes_n,
            "sharded_us": t_sh, "replicated_us": t_rep,
            "sharded_gbps": bytes_n / (t_sh * 1e-6) / 1e9,
            "replicated_gbps": bytes_n / (t_rep * 1e-6) / 1e9,
            "all_gather_ops": len(ag),
            "all_gather_bytes_max": max(ag) if ag else 0,
            "full_buffer_bytes": spec_s.bucket_padded[0] * 4,
        })
    rec["note"] = ("8 forced host devices: timing shows structure/overhead "
                   "only (TPU is the target); all_gather_bytes_max == 0 is "
                   "the acceptance signal — the sharded round never "
                   "gathers a full flat buffer.")
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--sharded-child" in sys.argv:
        _sharded_child()
    else:
        run(quick="--full" not in sys.argv)
