"""Simulated-time federated-learning harness — reproduces the paper's
experimental protocol (Sec. 5 + App. C.1/C.2) for FAVAS and its baselines
(FedAvg, QuAFL, FedBuff, AsyncSGD) on the small classifier models.

Time model (App. C.2):
  * server waiting time 4, server interaction time 3;
  * deterministic per-step client runtimes: fast = 2, slow = 16 time units
    (1/3 slow unless stated);
  * FAVAS/QuAFL server rounds last wait+interact = 7; clients train
    concurrently, capped at K local steps since their last reset;
  * FedAvg rounds last interact + K * (slowest selected client's step time);
  * FedBuff rounds complete when Z client updates arrive (fast clients feed
    the buffer — the bias FAVAS removes);
  * AsyncSGD applies every arriving single-gradient update immediately.

This level is the *paper-experiment* engine (small models, CPU); the
distributed production trainer for the assigned architectures lives in
``repro.core.favas`` + ``repro.launch.train``.

The FAVAS and QuAFL branches run as **supersteps** (docs/architecture.md
§7): every eval-to-eval window of server rounds is ONE jitted, donated
``jax.lax.scan`` over the flat-buffer engine — client selection
(``sampler.sample_selection_indices``), the deterministic credit/step-time
clock (``sampler.credit_steps``, on exact integer ticks), eq. 3 alphas, the
engine's round step and the q bookkeeping all live on-device inside the
scan. The host only syncs at
eval boundaries. Batches come from one of two data planes
(``SimConfig.data_plane``, docs/architecture.md §8): ``"host"`` generates
them in numpy on a background-thread ``BatchPrefetcher`` while the current
window computes; ``"device"`` keeps the corpus RESIDENT
(``data.device_corpus.DeviceCorpus``) and samples each round's minibatch
indices inside the scan body — zero host work per round.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.classifier import mlp_init, mlp_apply, classifier_loss, accuracy
from repro.core.quant import quantize_tree
from repro.core import round_engine, sampler
from repro.utils.tree import tree_map

SERVER_WAIT = 4.0
SERVER_INTERACT = 3.0

# cap on rounds per scanned superstep dispatch: bounds batch memory (the
# prefetcher holds up to ~3 chunks of (C, n, R, B, ...) arrays) regardless
# of how long an eval-to-eval window gets; recording still happens only at
# window starts, so results are unaffected
MAX_SUPERSTEP_ROUNDS = 32


@dataclasses.dataclass
class SimConfig:
    method: str = "favas"            # favas|quafl|fedbuff|fedavg|asyncsgd
    n_clients: int = 30
    s_selected: int = 6
    K: int = 10
    buffer_z: int = 5                # FedBuff
    eta: float = 0.2
    server_eta: float = 1.0          # FedBuff global LR
    total_time: float = 2000.0
    eval_every: float = 100.0
    batch_size: int = 64
    fast_step_time: float = 2.0
    slow_step_time: float = 16.0
    slow_fraction: float = 1.0 / 3.0
    reweight: str = "stochastic"
    quant_bits: int = 0              # FAVAS[QNN]
    permute_speeds: bool = True      # False: clients [0, n_slow) are the slow
    #                                  ones (for speed/data-correlated setups)
    data_plane: str = "host"         # "host": numpy batches + prefetcher;
    #                                  "device": resident DeviceCorpus, the
    #                                  scan samples minibatches in-body
    #                                  (docs/architecture.md §8)
    seed: int = 0


def _step_times(cfg: SimConfig, rng) -> np.ndarray:
    n_slow = int(round(cfg.slow_fraction * cfg.n_clients))
    t = np.full(cfg.n_clients, cfg.fast_step_time)
    t[:n_slow] = cfg.slow_step_time
    return rng.permutation(t) if cfg.permute_speeds else t


def _local_sgd_batched(loss_fn, eta, R):
    """vmapped masked local SGD: params (n,...), data (n,R,B,...), steps (n,)."""
    def one(params, xs, ys, n_steps):
        def step(p, inp):
            k, x, y = inp
            g = jax.grad(loss_fn)(p, x, y)
            live = (k < n_steps).astype(jnp.float32)
            return tree_map(lambda pp, gg: pp - eta * live * gg, p, g), None
        p, _ = jax.lax.scan(step, params, (jnp.arange(R), xs, ys))
        return p
    return jax.jit(jax.vmap(one))


def _window_schedule(total_time: float, eval_every: float,
                     round_dur: float) -> List[int]:
    """Round counts of each eval-to-eval window, replicating the per-round
    loop's semantics exactly: a record fires before round r iff
    ``r * round_dur >= next_eval`` (then ``next_eval += eval_every``), and
    the loop exits once ``r * round_dur >= total_time``. Every window
    therefore STARTS with a record, and the trailing record after the loop
    is the caller's job."""
    windows, cur = [], 0
    r, next_eval = 0, 0.0
    while r * round_dur < total_time:
        if r * round_dur >= next_eval:
            if cur:
                windows.append(cur)
                cur = 0
            next_eval += eval_every
        cur += 1
        r += 1
    if cur:
        windows.append(cur)
    return windows


def _local_sgd_single(loss_fn, eta):
    def run(params, xs, ys):
        def step(p, inp):
            x, y = inp
            g = jax.grad(loss_fn)(p, x, y)
            return tree_map(lambda pp, gg: pp - eta * gg, p, g), None
        p, _ = jax.lax.scan(step, params, (xs, ys))
        return p
    return jax.jit(run)


def run_simulation(cfg: SimConfig, data, *, d_hidden: int = 128,
                   mesh=None) -> Dict:
    """data = (x_train, y_train, x_test, y_test, parts). Returns curves.

    ``mesh``: optional device mesh with a "model" axis — the FAVAS branch
    then builds a sharding-aware FlatSpec (hidden-dim leaves bucketed into
    model-sharded flat buffers, see sharding/rules.py) and runs the poll
    through ``round_engine.aggregate_and_reset`` without gathering the
    buffers. CPU default (mesh=None) is unchanged."""
    xtr, ytr, xte, yte, parts = data
    n_classes = int(ytr.max()) + 1
    d_in = xtr.shape[1]
    rng = np.random.default_rng(cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)

    loss_fn = lambda p, x, y: classifier_loss(p, mlp_apply, x, y, n_classes)
    server = mlp_init(key, d_in, d_hidden, n_classes)
    n = cfg.n_clients
    clients = tree_map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(),
                       server)
    inits = clients
    step_time = _step_times(cfg, rng)

    from repro.data.pipeline import FederatedBatcher
    batcher = FederatedBatcher(xtr, ytr, parts, cfg.batch_size, cfg.seed)

    eval_fn = jax.jit(lambda p: accuracy(p, mlp_apply, xte, yte))
    var_fn = jax.jit(lambda W, w: sum(jax.tree_util.tree_leaves(tree_map(
        lambda a, b: jnp.sum((a - b[None]) ** 2), W, w))))

    times, accs, variances, server_steps = [], [], [], []
    t_now, next_eval, srv_step = 0.0, 0.0, 0

    def record():
        times.append(t_now)
        accs.append(float(eval_fn(server)))
        variances.append(float(var_fn(clients, server)))
        server_steps.append(srv_step)

    if cfg.method in ("favas", "quafl"):
        round_dur = SERVER_WAIT + SERVER_INTERACT
        R = int(np.ceil(round_dur / step_time.min()))
        sgd = _local_sgd_batched(loss_fn, cfg.eta, R)
        # Flat-buffer engine state, held across rounds for BOTH methods: the
        # FAVAS poll (eq. 3 + line 10 + reset) is the engine's round step
        # (one aggregation pass per dtype bucket, then the selected rows'
        # reset) and the QuAFL convex-combination poll one flat
        # elementwise pass — no per-leaf tree_map sweeps on the hot path.
        # The spec is client-aware (row padding beyond one client tile) and,
        # with a mesh, sharding-aware (model-sharded hidden-dim buckets).
        spec = round_engine.make_flat_spec(server, n_clients=n, mesh=mesh)
        srv_f = round_engine.flatten_tree(spec, server)
        cli_f = round_engine.stack_server_rows(spec, srv_f, n)
        ini_f = round_engine.stack_server_rows(spec, srv_f, n)
        # App. C.2 clock on integer ticks: exact for rational step times
        # (0.3 == 3/10), no f32 drift vs the f64 host reference
        step_ticks_np, round_ticks = sampler.time_ticks(step_time, round_dur)
        step_ticks_j = jnp.asarray(step_ticks_np)
        if cfg.method == "favas" and cfg.reweight == "deterministic":
            det_alpha = jnp.asarray(
                np.maximum(_det_alpha(cfg, step_time, round_dur), 1e-6),
                jnp.float32)
        else:
            det_alpha = None

        def one_round(carry, batch):
            """Scan body: ONE server round, everything on-device — the
            credit clock, masked local SGD, Gumbel top-s selection, eq. 3
            alphas, the poll, and the q reset."""
            srv_f, cli_f, ini_f, q, credit, rkey = carry
            xs_t, ys_t = batch
            # SELECT FIRST (docs/architecture.md §9): the round's selection
            # is drawn before any client buffer is touched, mirroring the
            # paged engine's select -> gather -> aggregate -> scatter
            # order. The split positions are unchanged, so the streams (and
            # every regression baseline) are bit-identical to the old
            # train-then-select body; the mask is the indicator of sel_idx.
            rkey, k_sel, k_q = jax.random.split(rkey, 3)
            sel_idx, mj = sampler.sample_selection_indices(
                k_sel, n, cfg.s_selected)
            do, credit = sampler.credit_steps(credit, step_ticks_j, q,
                                              cfg.K, round_ticks)
            clients_t = round_engine.unflatten_stacked(spec, cli_f)
            clients_t = sgd(clients_t, xs_t, ys_t, do.astype(jnp.int32))
            q_new = q + do
            cli_f = round_engine._constrain_buckets(
                spec, mesh, round_engine.flatten_stacked(spec, clients_t),
                stacked=True)
            if cfg.method == "favas":
                if cfg.reweight == "deterministic":
                    alpha = det_alpha
                elif cfg.reweight == "none":
                    alpha = jnp.ones((n,), jnp.float32)  # ablation: no eq. 3
                else:
                    alpha = jnp.maximum(q_new, 1.0).astype(jnp.float32)
                prog_f = (None,) * spec.n_buckets
                if cfg.quant_bits > 0:
                    # FAVAS[QNN]: quantize the TRANSMITTED progress only
                    # (per-leaf LUQ scale, as in the seed) — unselected
                    # clients keep their full-precision local state
                    inits_t = round_engine.unflatten_stacked(spec, ini_f)
                    prog = quantize_tree(
                        tree_map(jnp.subtract, clients_t, inits_t),
                        cfg.quant_bits, k_q)
                    prog_f = round_engine._constrain_buckets(
                        spec, mesh, round_engine.flatten_stacked(spec, prog),
                        stacked=True)
                alpha_p = round_engine.pad_client_vec(spec, alpha, 1.0)
                mj_p = round_engine.pad_client_vec(spec, mj, 0.0)
                srv_f, cli_f, ini_f = round_engine.aggregate_and_reset(
                    spec, srv_f, cli_f, ini_f, alpha_p, mj_p, sel_idx,
                    cfg.s_selected, progress=prog_f, n_logical=n, mesh=mesh)
            else:  # QuAFL (Zakerinia et al. 2022): convex combos, no
                #    reweight — same flat buffers, one elementwise pass per
                #    bucket (padded rows have zero mask and stay zero)
                mj_p = round_engine.pad_client_vec(spec, mj, 0.0)[:, None]
                sp1 = cfg.s_selected + 1.0
                srv_new, cli_new = [], []
                for w, c in zip(srv_f, cli_f):
                    w2 = (w + jnp.sum(mj_p * c, axis=0)) / sp1
                    cli_new.append(jnp.where(
                        mj_p > 0, (w2[None] + cfg.s_selected * c) / sp1, c))
                    srv_new.append(w2)
                srv_f = round_engine._constrain_buckets(
                    spec, mesh, tuple(srv_new), stacked=False)
                cli_f = round_engine._constrain_buckets(
                    spec, mesh, tuple(cli_new), stacked=True)
            q = jnp.where(mj > 0, 0.0, q_new)
            return (srv_f, cli_f, ini_f, q, credit, rkey), None

        @functools.partial(jax.jit, donate_argnums=(0,))
        def superstep(carry, xs, ys):
            """One eval-to-eval window of rounds as a single donated
            dispatch: scan ``one_round`` over the leading rounds axis."""
            carry, _ = jax.lax.scan(one_round, carry, (xs, ys))
            return carry

        @functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
        def superstep_device(carry, corpus, C):
            """Device data plane (docs/architecture.md §8): same window
            scan, but each round's minibatches are SAMPLED IN THE SCAN BODY
            from the resident corpus (one batch key split off the carried
            chain per round) — no host batch generation, no prefetcher, no
            per-chunk H2D batch copies. The corpus rides as an argument so
            its buffers stay shared inputs, not baked-in constants."""
            def body(c, _):
                srv, cli, ini, q, credit, rkey = c
                rkey, k_batch = jax.random.split(rkey)
                b = corpus.sample_round_batch(k_batch, R)
                return one_round((srv, cli, ini, q, credit, rkey),
                                 (b["x"], b["y"]))
            carry, _ = jax.lax.scan(body, carry, None, length=C)
            return carry

        # split eval-to-eval windows into <= MAX_SUPERSTEP_ROUNDS sub-chunks
        # (bounded batch memory); only the first sub-chunk of a window
        # records, so the curves are identical to whole-window scans
        chunks = []
        for W in _window_schedule(cfg.total_time, cfg.eval_every, round_dur):
            first = True
            while W > 0:
                c = min(W, MAX_SUPERSTEP_ROUNDS)
                chunks.append((c, first))
                first, W = False, W - c
        use_device_plane = cfg.data_plane == "device"
        if use_device_plane:
            from repro.data.device_corpus import make_classification_corpus
            corpus = make_classification_corpus(xtr, ytr, parts,
                                                cfg.batch_size, mesh=mesh)
            prefetch = None
        else:
            from repro.data.pipeline import BatchPrefetcher
            prefetch = BatchPrefetcher(
                lambda i: batcher.superstep_batch(chunks[i][0], R),
                n_steps=len(chunks))
        carry = (srv_f, cli_f, ini_f,
                 jnp.zeros((n,), jnp.float32),       # q: steps since reset
                 jnp.zeros((n,), jnp.int32),         # time credit (ticks)
                 key)
        try:
            for C, at_record in chunks:
                if at_record:
                    # window starts are exactly where the per-round loop
                    # recorded (see _window_schedule)
                    srv_f, cli_f = carry[0], carry[1]
                    server = round_engine.unflatten_tree(spec, srv_f)
                    clients = round_engine.unflatten_stacked(spec, cli_f)
                    record()
                if use_device_plane:
                    carry = superstep_device(carry, corpus, C)
                else:
                    xs, ys = prefetch.get()
                    carry = superstep(carry, xs, ys)
                t_now += C * round_dur
                srv_step += C
        finally:
            if prefetch is not None:
                prefetch.close()
        server = round_engine.unflatten_tree(spec, carry[0])
        clients = round_engine.unflatten_stacked(spec, carry[1])

    elif cfg.method == "fedavg":
        sgd = _local_sgd_single(loss_fn, cfg.eta)
        while t_now < cfg.total_time:
            if t_now >= next_eval:
                record(); next_eval += cfg.eval_every
            sel = rng.choice(n, cfg.s_selected, replace=False)
            newp = []
            for i in sel:
                xs, ys = zip(*[batcher.client_batch(i) for _ in range(cfg.K)])
                newp.append(sgd(server, jnp.asarray(np.stack(xs)),
                                jnp.asarray(np.stack(ys))))
            server = tree_map(lambda *ps: sum(ps) / len(ps), *newp)
            t_now += SERVER_INTERACT + cfg.K * step_time[sel].max()
            srv_step += 1

    elif cfg.method == "fedbuff":
        sgd = _local_sgd_single(loss_fn, cfg.eta)
        # event queue: (finish_time, client); each job = K local steps
        heap = [(cfg.K * step_time[i] * (1 + 0.01 * rng.random()), i)
                for i in range(n)]
        heapq.heapify(heap)
        client_base = [server] * n
        buffer: List = []
        while t_now < cfg.total_time and heap:
            if t_now >= next_eval:
                record(); next_eval += cfg.eval_every
            t_done, i = heapq.heappop(heap)
            t_now = t_done
            xs, ys = zip(*[batcher.client_batch(i) for _ in range(cfg.K)])
            trained = sgd(client_base[i], jnp.asarray(np.stack(xs)),
                          jnp.asarray(np.stack(ys)))
            delta = tree_map(jnp.subtract, client_base[i], trained)  # = eta*sum g
            buffer.append(delta)
            if len(buffer) >= cfg.buffer_z:
                mean_d = tree_map(lambda *ds: sum(ds) / len(ds), *buffer)
                server = tree_map(lambda w, d: w - cfg.server_eta * d,
                                  server, mean_d)
                buffer = []
                srv_step += 1
                t_now += SERVER_INTERACT
            client_base[i] = server
            heapq.heappush(heap, (t_now + cfg.K * step_time[i], i))

    elif cfg.method == "asyncsgd":
        grad_fn = jax.jit(jax.grad(loss_fn))
        heap = [(step_time[i] * (1 + 0.01 * rng.random()), i) for i in range(n)]
        heapq.heapify(heap)
        client_model = [server] * n
        while t_now < cfg.total_time and heap:
            if t_now >= next_eval:
                record(); next_eval += cfg.eval_every
            t_done, i = heapq.heappop(heap)
            t_now = t_done
            x, y = batcher.client_batch(i)
            g = grad_fn(client_model[i], jnp.asarray(x), jnp.asarray(y))
            server = tree_map(lambda w, gg: w - cfg.eta * gg, server, g)
            client_model[i] = server
            heapq.heappush(heap, (t_now + step_time[i], i))
            srv_step += 1
    else:
        raise ValueError(cfg.method)

    record()
    return {"times": np.array(times), "accuracy": np.array(accs),
            "variance": np.array(variances),
            "server_steps": np.array(server_steps),
            "final_accuracy": accs[-1], "method": cfg.method,
            "server": server}


def _det_alpha(cfg: SimConfig, step_time: np.ndarray, round_dur: float):
    """Deterministic alpha = E[E ∧ K]: with deterministic step times and
    poll probability s/n per round, computed by the sampler's DP using the
    per-round step rate."""
    from repro.core.sampler import moments_at_poll
    out = np.empty(cfg.n_clients, np.float32)
    poll_p = cfg.s_selected / cfg.n_clients
    cache = {}
    for i, st in enumerate(step_time):
        lam = min(max(st / round_dur, 1e-3), 0.999)  # approx 1/steps-per-round
        if lam not in cache:
            cache[lam] = moments_at_poll(lam, cfg.K, poll_p)[1]
        out[i] = cache[lam]
    return out
