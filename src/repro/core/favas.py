"""FAVAS server round — Algorithm 1 of the paper, vectorized over resident
clients and jit/pjit-compatible.

State layout (all pytrees of jnp arrays):
  server    : current server model w_t                      (model-sharded)
  clients   : stacked client models w^i, leading axis n     (client+model sharded)
  inits     : stacked w_init^i (last server model received)
  counters  : q^i in {0..K} — local steps since last reset
  stale     : rounds since the client was last selected (observability)

One round (server timestep t -> t+1):
  1. draw per-round step increments d^i ~ shifted-Geom(lambda^i)  [App. C.2]
  2. every client runs up to R masked local SGD steps: step k executes iff
     q^i + k < min(q^i + d^i, K)  — stragglers simply mask out; cost is
     uniform across the client mesh axis (no stragglers on the TPU itself,
     heterogeneity is *modeled*, as in the paper's simulation)
  3. draw S_t (Gumbel top-s), each selected client submits
     w_unbiased^i = w_init^i + (w^i - w_init^i)/alpha^i        [eq. (3)]
  4. w_{t+1} = (w_t + sum_{i in S_t} w_unbiased^i) / (s+1)     [line 10]
  5. selected clients reset: w^i = w_init^i = w_{t+1}, q^i = 0

Steps 4–5 run as the engine's round step over flat parameter buffers
(``core.round_engine.aggregate_and_reset``: one aggregation pass per
buffer, Pallas kernel on TPU, jnp oracle on CPU; then the selected rows'
reset); this module
keeps the pytree API by flattening/unflattening at the call boundary. The
seed's per-leaf ``tree_map`` implementation survives only as
``favas_round_reference`` — the numerical oracle the engine is regression-
tested against (tests/test_round_engine.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sampler, reweight, round_engine
from repro.core.quant import quantize_tree
from repro.core.round_engine import EngineState, _local_training
from repro.utils.tree import tree_map


@dataclasses.dataclass(frozen=True)
class FavasConfig:
    n_clients: int = 16
    s_selected: int = 4
    local_steps: int = 8            # K
    max_steps_per_round: int = 0    # R; 0 -> R = K
    eta: float = 0.1                # client LR (plain SGD, as in the paper)
    reweight: str = "stochastic"    # "stochastic" | "deterministic"
    slow_fraction: float = 1.0 / 3.0
    lam_fast: float = 1.0 / 16.0
    lam_slow: float = 0.5
    quant_bits: int = 0             # >0: LUQ-quantize client messages
    server_momentum: float = 0.0    # beyond-paper server-side momentum (off)
    seed: int = 0

    @property
    def R(self) -> int:
        return self.max_steps_per_round or self.local_steps


def client_lambdas(cfg: FavasConfig) -> np.ndarray:
    return sampler.make_lambdas(cfg.n_clients, cfg.slow_fraction,
                                cfg.lam_fast, cfg.lam_slow, cfg.seed)


def deterministic_alphas(cfg: FavasConfig) -> np.ndarray:
    poll_prob = cfg.s_selected / cfg.n_clients
    return reweight.alpha_deterministic(client_lambdas(cfg), cfg.local_steps,
                                        poll_prob)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FavasState:
    server: Any
    clients: Any
    inits: Any
    counters: jnp.ndarray          # (n,) int32
    stale: jnp.ndarray             # (n,) int32 — rounds since last selection
    key: jnp.ndarray
    t: jnp.ndarray                 # scalar int32

    def tree_flatten(self):
        return ((self.server, self.clients, self.inits, self.counters,
                 self.stale, self.key, self.t), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def favas_init(params, cfg: FavasConfig, key) -> FavasState:
    """All clients start from the server model (Algorithm 1 line 16)."""
    n = cfg.n_clients
    def stack(x):
        return jnp.broadcast_to(x[None], (n,) + x.shape)
    # clients and inits are DISTINCT buffers so a donating jit (e.g.
    # launch/steps.py build_train_step) never sees the same buffer twice
    return FavasState(
        server=params,
        clients=tree_map(stack, params),
        inits=tree_map(lambda x: stack(x).copy(), params),
        counters=jnp.zeros((n,), jnp.int32),
        stale=jnp.zeros((n,), jnp.int32),
        key=key,
        t=jnp.zeros((), jnp.int32),
    )


def _on_engine(engine_fn, state: FavasState, batch, *, cfg: FavasConfig,
               mesh, **kw):
    """Run an engine entry point (``engine_round`` / ``engine_multi_round``)
    with the pytree API: flatten the FavasState to an EngineState at the
    call boundary and unflatten the result. The one place the
    FavasState <-> EngineState mapping lives."""
    spec = round_engine.make_flat_spec(state.server, n_clients=cfg.n_clients,
                                       mesh=mesh)
    est = EngineState(
        server=round_engine.flatten_tree(spec, state.server),
        clients=round_engine.flatten_stacked(spec, state.clients),
        inits=round_engine.flatten_stacked(spec, state.inits),
        counters=state.counters, stale=state.stale,
        key=state.key, t=state.t)
    est, metrics = engine_fn(spec, est, batch, cfg=cfg, mesh=mesh, **kw)
    new_state = FavasState(
        server=round_engine.unflatten_tree(spec, est.server),
        clients=round_engine.unflatten_stacked(spec, est.clients),
        inits=round_engine.unflatten_stacked(spec, est.inits),
        counters=est.counters, stale=est.stale, key=est.key, t=est.t)
    return new_state, metrics


def favas_round(state: FavasState, batch, *, cfg: FavasConfig, loss_fn: Callable,
                lambdas, det_alpha: Optional[jnp.ndarray] = None,
                use_kernel: Optional[bool] = None, mesh=None):
    """One server round on the flat-buffer engine, pytree API preserved.
    Returns (new_state, metrics). Jit/pjit this.

    ``use_kernel``: None -> Pallas kernel on TPU, jnp oracle elsewhere;
    True/False force the choice (True runs interpret mode off-TPU).
    ``mesh``: bucket the flat buffers by (dtype, sharding group) and keep
    model-sharded leaves sharded through the round (no full-buffer
    gather; see core/round_engine.py and docs/architecture.md §6)."""
    return _on_engine(round_engine.engine_round, state, batch, cfg=cfg,
                      mesh=mesh, loss_fn=loss_fn, lambdas=lambdas,
                      det_alpha=det_alpha, use_kernel=use_kernel)


def favas_multi_round(state: FavasState, batches=None, *, cfg: FavasConfig,
                      loss_fn: Callable, lambdas,
                      det_alpha: Optional[jnp.ndarray] = None,
                      use_kernel: Optional[bool] = None, mesh=None,
                      corpus=None, n_rounds: Optional[int] = None):
    """A chunk of server rounds as ONE on-device scan, pytree API preserved
    (``round_engine.engine_multi_round`` under the hood). ``batches`` leaves
    carry a leading (T,) rounds axis; metrics come back (T,)-stacked. Jit
    this with donation and a T-round chunk costs one dispatch — bit-exact
    with T sequential :func:`favas_round` calls (the per-round key split
    makes the RNG streams identical).

    Device data plane: pass ``corpus`` (a
    ``data.device_corpus.DeviceCorpus``) + a static ``n_rounds`` instead of
    ``batches`` — the scan body then samples each round's minibatches from
    the resident corpus (docs/architecture.md §8)."""
    return _on_engine(round_engine.engine_multi_round, state, batches,
                      cfg=cfg, mesh=mesh, loss_fn=loss_fn, lambdas=lambdas,
                      det_alpha=det_alpha, use_kernel=use_kernel,
                      corpus=corpus, n_rounds=n_rounds)


def favas_round_reference(state: FavasState, batch, *, cfg: FavasConfig,
                          loss_fn: Callable, lambdas,
                          det_alpha: Optional[jnp.ndarray] = None):
    """The seed's per-leaf tree_map round — NOT on the hot path. Kept as the
    numerical oracle for the engine's regression tests: same PRNG splits,
    same arithmetic, leaf-by-leaf."""
    n, s, K = cfg.n_clients, cfg.s_selected, cfg.local_steps
    key, k_inc, k_sel, k_q = jax.random.split(state.key, 4)

    d = sampler.sample_increments(k_inc, lambdas)              # (n,)
    new_counters = jnp.minimum(state.counters + d, K)

    trained, loss_sum, live = _local_training(
        loss_fn, cfg, state.clients, state.counters, new_counters, batch)

    if cfg.reweight == "deterministic":
        alpha = det_alpha
    else:
        alpha = reweight.alpha_stochastic(new_counters, p_pos=1.0)
    progress = tree_map(jnp.subtract, trained, state.inits)
    if cfg.quant_bits > 0:
        progress = quantize_tree(progress, cfg.quant_bits, k_q)
    msgs = tree_map(
        lambda init, prog: init + prog / alpha.reshape((n,) + (1,) * (prog.ndim - 1)),
        state.inits, progress)

    m = sampler.sample_selection(k_sel, n, s)                  # (n,) float
    def agg(server_leaf, msg_leaf):
        mm = m.reshape((n,) + (1,) * (msg_leaf.ndim - 1))
        total = jnp.sum(mm * msg_leaf.astype(jnp.float32), axis=0)
        return ((server_leaf.astype(jnp.float32) + total) / (s + 1.0)
                ).astype(server_leaf.dtype)
    server_new = tree_map(agg, state.server, msgs)

    def reset(new_global, cur):
        mm = m.reshape((n,) + (1,) * (cur.ndim - 1))
        return (mm * new_global[None].astype(jnp.float32)
                + (1.0 - mm) * cur.astype(jnp.float32)).astype(cur.dtype)
    clients_new = tree_map(reset, server_new, trained)
    inits_new = tree_map(reset, server_new, state.inits)
    counters_new = jnp.where(m > 0, 0, new_counters).astype(jnp.int32)
    stale_new = jnp.where(m > 0, 0, state.stale + 1).astype(jnp.int32)

    new_state = FavasState(server=server_new, clients=clients_new,
                           inits=inits_new, counters=counters_new,
                           stale=stale_new, key=key, t=state.t + 1)
    metrics = {
        "loss": jnp.sum(loss_sum) / jnp.maximum(jnp.sum(live), 1.0),
        "mean_steps": jnp.mean(new_counters.astype(jnp.float32)),
        "selected": jnp.sum(m),
        "stale_rounds": jnp.max(stale_new).astype(jnp.float32),
    }
    return new_state, metrics


def favas_variance(state: FavasState) -> jnp.ndarray:
    """Paper's reported dispersion  sum_i ||w^i - w_t||^2  (Sec. 5).
    Vectorized: sum over leaves of sum((W - w)^2)."""
    d = tree_map(lambda W, w: jnp.sum(
        jnp.square(W.astype(jnp.float32) - w[None].astype(jnp.float32))),
        state.clients, state.server)
    return sum(jax.tree_util.tree_leaves(d))


def favas_mu(state: FavasState):
    """mu_t = (w_t + sum_i w_t^i) / (n+1) — the averaged model the theory
    tracks (eq. 4)."""
    n = state.counters.shape[0]
    return tree_map(
        lambda w, W: (w.astype(jnp.float32) + jnp.sum(W.astype(jnp.float32), 0))
        / (n + 1.0), state.server, state.clients)
