"""Cluster orchestrator for the async FAVAS deployment (ROADMAP open
item 2's deliverable; docs/architecture.md §11).

Two runners over the SAME server/client actors:

* :func:`run_inproc` — everything on one :class:`InProcTransport` event
  loop: virtual clock, seeded faults, fully deterministic. The test
  substrate (tests/test_async_server.py) and the simulated baseline of the
  async benchmark.
* :func:`run_proc` — the server in THIS process, each client a real
  spawned OS process, wired in a star of duplex pipes with
  :class:`ProcEndpoint` pumps on both ends. Wall-clock latencies are
  injected by the shared :class:`FaultPlan`; teardown is
  stop-broadcast -> bye harvest -> join-with-deadline -> terminate
  stragglers, and the result reports per-child exit codes so CI can gate
  on a clean shutdown.

CLI (the CI 2-client smoke and the bench's workhorse)::

  PYTHONPATH=src python -m repro.launch.cluster --transport proc \
      --clients 2 --rounds 20 --latency 0.02 --out cluster_summary.json

exits non-zero unless every round completed and every child exited 0.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import multiprocessing as mp
import os
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comms import BackoffPolicy, FaultPlan, InProcTransport, ProcEndpoint
from repro.core import sampler
from repro.launch.client import LocalSGDClient
from repro.launch.server import (SERVER_ID, AsyncConfig, FavasAsyncServer,
                                 recover_server)
from repro.models.classifier import accuracy, mlp_apply, mlp_init
from repro.utils.compile_cache import setup_compile_cache


def default_backoff(cfg: AsyncConfig) -> BackoffPolicy:
    """Push-retry schedule scaled to the round: first retry at
    round_dur/4 (comfortably above a sane RTT, so an in-flight ack usually
    cancels it), doubling, capped at one round — several attempts still fit
    inside the harvest window on either clock."""
    return BackoffPolicy(base=max(cfg.round_dur / 4.0, 1e-3),
                         factor=2.0, max_delay=cfg.round_dur,
                         max_attempts=6)


def _client_seed(cfg: AsyncConfig, i: int) -> int:
    # distinct per-client batch streams, disjoint from the server chain
    return (cfg.seed * 1009 + 17 * i + 13) % (2 ** 31)


def build_deployment(cfg: AsyncConfig, data, *, d_hidden: int = 32,
                     backoff: Optional[BackoffPolicy] = None,
                     wal_dir: Optional[str] = None, ckpt_every: int = 0,
                     wal_fsync: bool = True, chaos=None):
    """Shared setup for both runners: the model init and server rng ride
    the exact fl_sim chain (``PRNGKey(cfg.seed)`` for both), the step-time
    vector is fl_sim's ``_step_times`` draw, and the integer tick grid
    comes from ``sampler.time_ticks`` — the preconditions of the
    equivalence contract. ``wal_dir`` arms the server's durability layer
    (docs/architecture.md §12). Returns ``(server, clients)``."""
    xtr, ytr, xte, yte, parts = data
    n_classes = int(ytr.max()) + 1
    params0 = mlp_init(jax.random.PRNGKey(cfg.seed), xtr.shape[1],
                       d_hidden, n_classes)
    step_time = cfg.step_times()
    step_ticks, round_ticks = sampler.time_ticks(step_time, cfg.round_dur)
    xte_j, yte_j = jnp.asarray(xte), jnp.asarray(yte)
    eval_fn = jax.jit(lambda p: accuracy(p, mlp_apply, xte_j, yte_j))
    server = FavasAsyncServer(cfg, params0, eval_fn=eval_fn,
                              wal_dir=wal_dir, ckpt_every=ckpt_every,
                              wal_fsync=wal_fsync, chaos=chaos)
    backoff = backoff or default_backoff(cfg)
    clients = [
        LocalSGDClient(server.client_ids[i], params0,
                       xtr[parts[i]], ytr[parts[i]],
                       n_clients=cfg.n_clients, batch_size=cfg.batch_size,
                       eta=cfg.eta, K=cfg.K,
                       step_ticks=int(step_ticks[i]),
                       round_ticks=round_ticks, n_classes=n_classes,
                       seed=_client_seed(cfg, i), backoff=backoff)
        for i in range(cfg.n_clients)]
    return server, clients


# ---------------------------------------------------------------------------
# deterministic in-process runner
# ---------------------------------------------------------------------------

def run_inproc(cfg: AsyncConfig, data, *, d_hidden: int = 32,
               plan: Optional[FaultPlan] = None, seed: int = 0,
               max_events: int = 2_000_000,
               wal_dir: Optional[str] = None, ckpt_every: int = 0,
               wal_fsync: bool = True) -> dict:
    """One deterministic virtual-clock run. Returns the server result plus
    per-client logs/stats and the transport counters; ``virtual_time`` is
    where the clock stopped."""
    server, clients = build_deployment(cfg, data, d_hidden=d_hidden,
                                       wal_dir=wal_dir,
                                       ckpt_every=ckpt_every,
                                       wal_fsync=wal_fsync)
    t = InProcTransport(plan, seed=seed)
    t.add_actor(server)
    for c in clients:
        t.add_actor(c)
    t.run(max_events=max_events)
    return {"server": server.result(),
            "client_logs": {c.node_id: list(c.log) for c in clients},
            "client_stats": {c.node_id: dict(c.stats) for c in clients},
            "transport": dict(t.stats),
            "virtual_time": t._now,
            "server_actor": server}


def recovered_server(cfg: AsyncConfig, data, *, d_hidden: int = 32,
                     wal_dir: str, ckpt_every: int = 0,
                     wal_fsync: bool = True, chaos=None) -> FavasAsyncServer:
    """Rebuild the server after a crash: re-derive the same ``params0`` /
    eval_fn as :func:`build_deployment` and recover state from the WAL
    directory (snapshot + replay)."""
    xtr, ytr, xte, yte, _ = data
    n_classes = int(ytr.max()) + 1
    params0 = mlp_init(jax.random.PRNGKey(cfg.seed), xtr.shape[1],
                       d_hidden, n_classes)
    xte_j, yte_j = jnp.asarray(xte), jnp.asarray(yte)
    eval_fn = jax.jit(lambda p: accuracy(p, mlp_apply, xte_j, yte_j))
    return recover_server(cfg, params0, wal_dir, eval_fn=eval_fn,
                          ckpt_every=ckpt_every, wal_fsync=wal_fsync,
                          chaos=chaos)


def run_inproc_chaos(cfg: AsyncConfig, data, *, d_hidden: int = 32,
                     wal_dir: str, ckpt_every: int = 0,
                     kills=(), plan: Optional[FaultPlan] = None,
                     seed: int = 0, max_events: int = 2_000_000) -> dict:
    """Deterministic kill/restart harness on the virtual clock.

    ``kills`` is a sequence of :class:`repro.comms.ServerCrashSwitch`es,
    armed one at a time: the run steps the clock in small slices; when the
    armed switch has fired (the server died at its durability point) the
    supervisor builds a :func:`recovered_server`, swaps it in via
    ``InProcTransport.revive``, and arms the next switch. Slices are a
    quarter round — shorter than the first push-retry backoff — so no
    client exhausts its retries against a dead server. The recovered
    trajectory's buckets are BIT-EXACT vs an uninterrupted run on the same
    seed (tests/test_chaos_recovery.py)."""
    switches = list(kills)
    chaos = switches.pop(0) if switches else None
    server, clients = build_deployment(cfg, data, d_hidden=d_hidden,
                                       wal_dir=wal_dir,
                                       ckpt_every=ckpt_every, chaos=chaos)
    t = InProcTransport(plan, seed=seed)
    t.add_actor(server)
    for c in clients:
        t.add_actor(c)
    step = cfg.round_dur / 4.0
    horizon = 0.0
    wedge = 100.0 * (cfg.rounds + 2) * cfg.round_dur
    recoveries = 0
    while True:
        horizon += step
        if horizon > wedge:
            raise RuntimeError("chaos run exceeded its virtual-time bound")
        t.run(until=horizon, max_events=max_events)
        if SERVER_ID in t.killed_nodes():
            chaos = switches.pop(0) if switches else None
            server = recovered_server(cfg, data, d_hidden=d_hidden,
                                      wal_dir=wal_dir,
                                      ckpt_every=ckpt_every, chaos=chaos)
            t.revive(server)
            recoveries += 1
        elif t.done():
            break
    return {"server": server.result(),
            "client_logs": {c.node_id: list(c.log) for c in clients},
            "client_stats": {c.node_id: dict(c.stats) for c in clients},
            "transport": dict(t.stats),
            "virtual_time": t._now,
            "recoveries": recoveries,
            "server_actor": server}


# ---------------------------------------------------------------------------
# real multi-process runner
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _clients_on_cpu():
    """Processes spawned inside the block start with ``JAX_PLATFORMS=cpu``.
    Clients model edge devices and run on the host CPU: an accelerator
    belongs to one process at a time, and that process is the server."""
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = old


def _client_main(conn, payload, plan, seed, until):
    """Spawned-child entry: rebuild the worker from the picklable payload
    (the model init is re-derived from the seed, not shipped) and pump its
    endpoint until stop/timeout."""
    cfg = payload["cfg"]
    params0 = mlp_init(jax.random.PRNGKey(cfg.seed), payload["d_in"],
                       payload["d_hidden"], payload["n_classes"])
    client = LocalSGDClient(payload["node_id"], params0,
                            payload["x"], payload["y"],
                            n_clients=cfg.n_clients,
                            batch_size=cfg.batch_size, eta=cfg.eta,
                            K=cfg.K, step_ticks=payload["step_ticks"],
                            round_ticks=payload["round_ticks"],
                            n_classes=payload["n_classes"],
                            seed=payload["seed"],
                            backoff=payload["backoff"])
    client.warmup(range(1, cfg.K + 1))
    ep = ProcEndpoint(payload["node_id"], {SERVER_ID: conn}, plan=plan,
                      seed=seed)
    try:
        ep.run(client, until=until)
    finally:
        ep.close()


def run_proc(cfg: AsyncConfig, data, *, d_hidden: int = 32,
             plan: Optional[FaultPlan] = None, seed: int = 0,
             timeout: Optional[float] = None,
             wal_dir: Optional[str] = None, ckpt_every: int = 0) -> dict:
    """Spawn ``cfg.n_clients`` worker processes, run the server endpoint in
    this process, harvest, and tear down. ``timeout`` bounds the server
    pump (default: the nominal schedule plus generous slack) so a wedged
    transport fails fast instead of hanging the caller."""
    xtr, ytr, _, _, parts = data
    n_classes = int(ytr.max()) + 1
    step_time = cfg.step_times()
    step_ticks, round_ticks = sampler.time_ticks(step_time, cfg.round_dur)
    backoff = default_backoff(cfg)
    if timeout is None:
        timeout = cfg.rounds * cfg.round_dur + 60.0
    server, _ = build_deployment(cfg, data, d_hidden=d_hidden,
                                 wal_dir=wal_dir, ckpt_every=ckpt_every)

    ctx = mp.get_context("spawn")    # fork is unsafe once jax is live
    conns, procs = {}, {}
    for i, cid in enumerate(server.client_ids):
        parent_c, child_c = ctx.Pipe(duplex=True)
        payload = {"cfg": cfg, "node_id": cid, "d_in": xtr.shape[1],
                   "d_hidden": d_hidden, "n_classes": n_classes,
                   "x": np.asarray(xtr[parts[i]]),
                   "y": np.asarray(ytr[parts[i]]),
                   "step_ticks": int(step_ticks[i]),
                   "round_ticks": round_ticks,
                   "seed": _client_seed(cfg, i), "backoff": backoff}
        p = ctx.Process(target=_client_main,
                        args=(child_c, payload, plan, seed, timeout + 30.0),
                        daemon=True)
        with _clients_on_cpu():
            p.start()
        child_c.close()
        conns[cid], procs[cid] = parent_c, p

    ep = ProcEndpoint(SERVER_ID, conns, plan=plan, seed=seed)
    t0 = time.monotonic()
    try:
        ep.run(server, until=timeout)
    finally:
        wall = time.monotonic() - t0
        ep.close()
    exitcodes = {}
    deadline = time.monotonic() + 15.0
    for cid, p in procs.items():
        p.join(timeout=max(deadline - time.monotonic(), 0.1))
        if p.is_alive():
            p.terminate()
            p.join(timeout=5.0)
        exitcodes[cid] = p.exitcode
    res = server.result()
    return {"server": res,
            "client_logs": dict(server.client_logs),
            "transport": dict(ep.stats),
            "wall_time": wall,
            "rounds_per_sec": res["rounds"] / max(wall, 1e-9),
            "exitcodes": exitcodes,
            "clean": all(ec == 0 for ec in exitcodes.values()),
            "server_actor": server}


# ---------------------------------------------------------------------------
# supervised real-process runner: killable, restartable server child
# ---------------------------------------------------------------------------

def _server_main(conns, payload, plan, seed, until, recover, result_conn):
    """Spawned SERVER entry for the supervised runner. ``recover=True``
    rebuilds state from the WAL directory; the final (uninterrupted)
    incarnation ships the result dict back over ``result_conn``. Earlier
    incarnations are SIGKILLed by the supervisor and ship nothing — which
    is the point."""
    cfg = payload["cfg"]
    params0 = mlp_init(jax.random.PRNGKey(cfg.seed), payload["d_in"],
                       payload["d_hidden"], payload["n_classes"])
    if recover:
        server = recover_server(cfg, params0, payload["wal_dir"],
                                ckpt_every=payload["ckpt_every"])
    else:
        server = FavasAsyncServer(cfg, params0,
                                  wal_dir=payload["wal_dir"],
                                  ckpt_every=payload["ckpt_every"])
    ep = ProcEndpoint(SERVER_ID, conns, plan=plan, seed=seed)
    try:
        ep.run(server, until=until)
    finally:
        ep.close()
    result_conn.send({"server": server.result(),
                      "client_logs": dict(server.client_logs),
                      "transport": dict(ep.stats)})
    result_conn.close()


def run_proc_supervised(cfg: AsyncConfig, data, *, d_hidden: int = 32,
                        plan: Optional[FaultPlan] = None, seed: int = 0,
                        timeout: Optional[float] = None,
                        wal_dir: str, ckpt_every: int = 0,
                        kill_at=()) -> dict:
    """Real-asynchrony chaos runner: the server lives in its OWN child
    process behind per-client pipe proxies held by this (supervisor)
    process, so SIGKILLing it at each offset in ``kill_at`` (wall seconds
    from start) leaves every client's connection intact. The supervisor
    respawns the server with ``recover=True`` (WAL snapshot + replay) and
    re-wires the server-side pipes; client pushes that died with the old
    process are simply retried into the new one, where the exactly-once
    ledger sorts them out. Returns the final incarnation's result plus
    ``crashes`` — CI gates on it being ``len(kill_at)``.

    The supervisor itself never initializes a JAX backend (it only moves
    pickled envelopes), so the server child is the one process that opens
    an accelerator; clients run on the host CPU."""
    from multiprocessing import connection as mpc
    xtr, ytr, _, _, parts = data
    n_classes = int(ytr.max()) + 1
    step_time = cfg.step_times()
    step_ticks, round_ticks = sampler.time_ticks(step_time, cfg.round_dur)
    backoff = default_backoff(cfg)
    if timeout is None:
        timeout = cfg.rounds * cfg.round_dur + 60.0 \
            + 2.0 * cfg.round_dur * len(tuple(kill_at))
    ctx = mp.get_context("spawn")    # fork is unsafe once jax is live
    client_ids = [f"client{i}" for i in range(cfg.n_clients)]

    # A-side: client child <-> supervisor (survives server restarts)
    proxy_a, client_procs = {}, {}
    for i, cid in enumerate(client_ids):
        parent_c, child_c = ctx.Pipe(duplex=True)
        payload = {"cfg": cfg, "node_id": cid, "d_in": xtr.shape[1],
                   "d_hidden": d_hidden, "n_classes": n_classes,
                   "x": np.asarray(xtr[parts[i]]),
                   "y": np.asarray(ytr[parts[i]]),
                   "step_ticks": int(step_ticks[i]),
                   "round_ticks": round_ticks,
                   "seed": _client_seed(cfg, i), "backoff": backoff}
        p = ctx.Process(target=_client_main,
                        args=(child_c, payload, plan, seed, timeout + 30.0),
                        daemon=True)
        with _clients_on_cpu():
            p.start()
        child_c.close()
        proxy_a[cid], client_procs[cid] = parent_c, p

    spayload = {"cfg": cfg, "d_in": xtr.shape[1], "d_hidden": d_hidden,
                "n_classes": n_classes, "wal_dir": wal_dir,
                "ckpt_every": ckpt_every}

    def spawn_server(recover: bool):
        # B-side: supervisor <-> server child (rebuilt on every respawn)
        proxy_b, child_conns = {}, {}
        for cid in client_ids:
            pb, sb = ctx.Pipe(duplex=True)
            proxy_b[cid], child_conns[cid] = pb, sb
        res_parent, res_child = ctx.Pipe(duplex=False)
        p = ctx.Process(target=_server_main,
                        args=(child_conns, spayload, plan, seed,
                              timeout, recover, res_child),
                        daemon=True)
        p.start()
        for c in child_conns.values():
            c.close()
        res_child.close()
        return p, proxy_b, res_parent

    srv_proc, proxy_b, res_conn = spawn_server(False)
    kills = sorted(float(k) for k in kill_at)
    t0 = time.monotonic()
    crashes = 0
    result = None
    while result is None and time.monotonic() - t0 < timeout:
        now = time.monotonic() - t0
        if kills and now >= kills[0]:
            kills.pop(0)
            srv_proc.kill()
            srv_proc.join(timeout=10.0)
            crashes += 1
            for c in proxy_b.values():
                c.close()
            res_conn.close()
            srv_proc, proxy_b, res_conn = spawn_server(True)
            continue
        wait_for = min(kills[0] - now if kills else 0.1, 0.1)
        try:
            ready = mpc.wait(list(proxy_a.values()) + list(proxy_b.values())
                             + [res_conn], timeout=max(wait_for, 0.0))
        except OSError:
            ready = []
        a_of = {id(v): k for k, v in proxy_a.items()}
        b_of = {id(v): k for k, v in proxy_b.items()}
        for conn in ready:
            try:
                if conn is res_conn:
                    result = conn.recv()
                elif id(conn) in a_of:       # client -> server
                    env = conn.recv()
                    dst = proxy_b.get(a_of[id(conn)])
                    if dst is not None and srv_proc.is_alive():
                        dst.send(env)        # dead server: drop, retries cope
                elif id(conn) in b_of:       # server -> client
                    proxy_a[b_of[id(conn)]].send(conn.recv())
            except (EOFError, OSError, BrokenPipeError):
                continue                     # a side died mid-transfer
    wall = time.monotonic() - t0
    srv_proc.join(timeout=10.0)
    if srv_proc.is_alive():
        srv_proc.terminate()
        srv_proc.join(timeout=5.0)
    for c in list(proxy_a.values()) + list(proxy_b.values()):
        try:
            c.close()
        except OSError:
            pass
    exitcodes = {}
    deadline = time.monotonic() + 15.0
    for cid, p in client_procs.items():
        p.join(timeout=max(deadline - time.monotonic(), 0.1))
        if p.is_alive():
            p.terminate()
            p.join(timeout=5.0)
        exitcodes[cid] = p.exitcode
    if result is None:
        return {"server": None, "crashes": crashes, "clean": False,
                "exitcodes": exitcodes, "wall_time": wall}
    res = result["server"]
    return {"server": res,
            "client_logs": result["client_logs"],
            "transport": result["transport"],
            "wall_time": wall,
            "rounds_per_sec": res["rounds"] / max(wall, 1e-9),
            "exitcodes": exitcodes,
            "crashes": crashes,
            "clean": all(ec == 0 for ec in exitcodes.values()),
            "server_actor": None}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _smoke_data(n_clients: int, seed: int, n_train: int = 400,
                n_test: int = 200):
    from repro.data.partition import partition_iid
    from repro.data.synthetic import make_classification
    x, y, xt, yt = make_classification("mnist-like", n_train=n_train,
                                       n_test=n_test, seed=seed)
    parts = partition_iid(len(y), n_clients, seed=seed)
    return x, y, xt, yt, parts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--transport", choices=("inproc", "proc"),
                    default="proc")
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--selected", type=int, default=0,
                    help="s per round (default: ceil(clients/2))")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--round-dur", type=float, default=0.5,
                    help="round cadence (wall s for proc, virtual for "
                         "inproc)")
    ap.add_argument("--latency", type=float, default=0.02,
                    help="injected base one-way latency")
    ap.add_argument("--jitter", type=float, default=0.0)
    ap.add_argument("--drop", type=float, default=0.0)
    ap.add_argument("--straggler", type=float, default=1.0,
                    help="latency multiplier for client0")
    ap.add_argument("--k-steps", type=int, default=4)
    ap.add_argument("--d-hidden", type=int, default=16)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="server pump bound in s (0: auto)")
    ap.add_argument("--wal-dir", default="",
                    help="arm the server's write-ahead log in this dir")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="snapshot + rotate the WAL every N closed rounds")
    ap.add_argument("--chaos", default="",
                    help="comma-separated wall-clock offsets (s) at which "
                         "the supervisor SIGKILLs and restarts the server "
                         "child (proc transport only; requires --wal-dir)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    setup_compile_cache()
    kill_at = tuple(float(x) for x in args.chaos.split(",") if x.strip())
    if kill_at and (args.transport != "proc" or not args.wal_dir):
        ap.error("--chaos needs --transport proc and --wal-dir")

    s = args.selected or max(1, (args.clients + 1) // 2)
    cfg = AsyncConfig(n_clients=args.clients, s_selected=s, K=args.k_steps,
                      batch_size=args.batch, rounds=args.rounds,
                      round_dur=args.round_dur,
                      fast_step_time=args.round_dur / max(args.k_steps, 1),
                      slow_step_time=args.round_dur / 2.0,
                      seed=args.seed)
    plan = FaultPlan(latency=args.latency, jitter=args.jitter,
                     drop=args.drop,
                     straggler=({"client0": args.straggler}
                                if args.straggler != 1.0 else {}))
    data = _smoke_data(args.clients, args.seed)
    if kill_at:
        out = run_proc_supervised(cfg, data, d_hidden=args.d_hidden,
                                  plan=plan, seed=args.seed,
                                  timeout=args.timeout or None,
                                  wal_dir=args.wal_dir,
                                  ckpt_every=args.ckpt_every,
                                  kill_at=kill_at)
        if out["server"] is None:
            print(json.dumps({"clean": False, "crashes": out["crashes"],
                              "exitcodes": out["exitcodes"]}, default=float))
            return 1
    elif args.transport == "proc":
        out = run_proc(cfg, data, d_hidden=args.d_hidden, plan=plan,
                       seed=args.seed,
                       timeout=args.timeout or None,
                       wal_dir=args.wal_dir or None,
                       ckpt_every=args.ckpt_every)
    else:
        out = run_inproc(cfg, data, d_hidden=args.d_hidden, plan=plan,
                         seed=args.seed,
                         wal_dir=args.wal_dir or None,
                         ckpt_every=args.ckpt_every)
        out["clean"] = True
    res = out["server"]
    summary = {
        "transport": args.transport,
        "config": {"clients": args.clients, "selected": s,
                   "rounds": args.rounds, "round_dur": args.round_dur,
                   "latency": args.latency, "drop": args.drop,
                   "straggler": args.straggler, "seed": args.seed},
        "rounds_completed": res["rounds"],
        "final_accuracy": res["final_accuracy"],
        "staleness": res["staleness"],
        "server_stats": res["stats"],
        "transport_stats": out["transport"],
        "wall_time": out.get("wall_time"),
        "rounds_per_sec": out.get("rounds_per_sec"),
        "exitcodes": out.get("exitcodes"),
        "crashes": out.get("crashes", 0),
        "clean": out["clean"],
    }
    line = json.dumps(summary, indent=2, default=float)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    ok = (out["clean"] and res["rounds"] >= args.rounds
          and out.get("crashes", 0) == len(kill_at))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
