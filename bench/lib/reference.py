"""The plain FAVAS reference: Algorithm 1 of the paper, one client and one
step at a time, on parameter pytrees, with a configuration's plain model
reference (``configs/<name>.ref.py``) for the local steps.

It follows the program's random streams through ``replay`` (a copy of
the sampler arithmetic) but shares no code with the program. Per round:

  1. draw the round's windows and step increments; q' = min(q + d, K);
  2. every client runs its live steps, ``w <- w - eta grad`` in float32
     (step k of client i is live iff q_i + k < q'_i; dead steps are not
     run at all);
  3. S_t = Gumbel top-s; each selected client sends
     ``init + (w - init) / alpha`` with ``alpha = max(q', 1e-6)`` (eq. 3);
  4. ``server <- (server + sum of the messages) / (s + 1)``;
  5. selected clients restart from the new server: w = init = server,
     q = 0; the others keep their trained weights and q'.

The round's loss is the sum of its live steps' losses over their count.

``fault`` plants one of the faults a training cell can have, in the
reference put in the program's place: ``unchanged`` (every chunk returns
the state it was given), ``half_batch`` (each step's loss is the mean
over the first half of the window's positions only) and ``agg_div_s``
(the aggregation divides by s instead of s + 1)."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from lib import mm as mm_mod
from lib import replay

FAULTS = ("unchanged", "half_batch", "agg_div_s")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "ref_" + path.name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def leaf_paths(tree) -> list:
    """'a/b/c' names of a pytree's leaves, in ``tree_leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def _shape_tree(ref, hf):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(tuple(s), jnp.float32),
        ref.weight_shapes(hf), is_leaf=lambda x: isinstance(x, tuple))


def make_weights(ref, hf: dict, key):
    """The configuration's weights, made on the device in one jitted call
    from ``key`` (float32, the type the trainer holds them in)."""
    shapes = _shape_tree(ref, hf)
    names = leaf_paths(shapes)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    @jax.jit
    def build(k):
        return jax.tree_util.tree_unflatten(treedef, [
            ref.init_leaf(name, leaf.shape, jax.random.fold_in(k, i))
            for i, (name, leaf) in enumerate(zip(names, leaves))])
    return build(key)


def weights_key(seed: int):
    return jax.random.fold_in(replay.state_key(seed), 0x5EED)


@functools.partial(jax.jit, static_argnames=("K", "s", "R", "B", "S"))
def _plan(key, q, tokens, lo, span, lambdas, *, K, s, R, B, S):
    """A round's draws and windows: (next key, q', q after the reset,
    selected ids, (n, R, B, S) token windows)."""
    key, k_batch, new, after, sel = replay.plan_round(key, q, lambdas, K=K,
                                                      s=s)
    starts = replay.window_starts(k_batch, lo, span, R, B)
    return key, new, after, sel, tokens[starts[..., None] + jnp.arange(S)]


@functools.partial(jax.jit, static_argnames=("div",))
def _aggregate(server, clients, inits, alphas, div: float):
    def leaf(w, *rows):
        m = len(rows) // 2
        tot = sum(i + (c - i) / a
                  for c, i, a in zip(rows[:m], rows[m:], alphas))
        return (w + tot) / div
    return jax.tree_util.tree_map(leaf, server, *clients, *inits)


class Reference:
    """A configuration's plain model reference (``configs/<name>.ref.py``)
    driven through FAVAS rounds of one traffic mix. Holds the jitted local
    step per precision and fault, so a process that runs many seeds traces
    each once."""

    def __init__(self, module, hf: dict, traffic: dict):
        self.module, self.hf, self.traffic = module, hf, traffic
        self._steps = {}

    def weights(self, key):
        return make_weights(self.module, self.hf, key)

    def _step(self, mode: str, fault):
        if (mode, fault) in self._steps:
            return self._steps[mode, fault]
        mm = mm_mod.matmul(mode)
        module, hf, eta = self.module, self.hf, self.traffic["eta"]

        def loss(p, tokens):
            ce = module.token_losses(p, tokens, hf, mm)
            if fault == "half_batch":
                ce = ce[:, :ce.shape[1] // 2]
            return jnp.mean(ce)

        @jax.jit
        def step(p, tokens):
            with jax.default_matmul_precision("highest"):
                val, g = jax.value_and_grad(loss)(p, tokens)
            return val, jax.tree_util.tree_map(lambda w, gw: w - eta * gw,
                                               p, g)
        self._steps[mode, fault] = step
        return step

    def run(self, weights, tokens, lo, span, lambdas, seed: int, *,
            n_chunks: int, keep=(1, 3), mode: str = "highest", fault=None):
        """FAVAS from ``weights`` for ``n_chunks`` chunks of the traffic's
        rounds per chunk. Returns ``{"losses": [...], "live": [...],
        "servers": {chunk: [leaves]}}`` for the chunk ends in
        ``keep``."""
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        tr = self.traffic
        n, s, K = tr["n_clients"], tr["s"], tr["K"]
        R, B, S, T = K, tr["batch"], tr["seq"], tr["rounds_per_chunk"]
        step = self._step(mode, fault)
        lambdas = jnp.asarray(lambdas)
        tokens, lo, span = (jnp.asarray(tokens), jnp.asarray(lo),
                            jnp.asarray(span))
        key = replay.state_key(seed)
        q = jnp.zeros((n,), jnp.int32)
        server = weights
        clients, inits = [server] * n, [server] * n
        out = {"losses": [], "live": [], "servers": {}}
        div = float(s) if fault == "agg_div_s" else float(s) + 1.0
        for c in range(1, n_chunks + 1):
            start = (server, list(clients), list(inits), q)
            for _ in range(T):
                key, new, after, sel, batch = _plan(
                    key, q, tokens, lo, span, lambdas, K=K, s=s, R=R, B=B, S=S)
                q_h, new_h = np.asarray(q), np.asarray(new)
                losses = []
                for i in range(n):
                    for k in range(R):
                        if q_h[i] + k < new_h[i]:
                            val, clients[i] = step(clients[i], batch[i, k])
                            losses.append(val)
                live = int(np.sum(new_h - q_h))
                out["losses"].append(sum(float(v) for v in losses)
                                     / max(live, 1))
                out["live"].append(live)
                sel_h = sorted(int(i) for i in np.asarray(sel))
                alphas = [max(float(new_h[i]), 1e-6) for i in sel_h]
                server = _aggregate(server, [clients[i] for i in sel_h],
                                    [inits[i] for i in sel_h],
                                    jnp.asarray(alphas, jnp.float32), div=div)
                for i in sel_h:
                    clients[i] = inits[i] = server
                q = after
            if fault == "unchanged":
                server, clients, inits, q = start
            if c in keep:
                out["servers"][c] = jax.tree_util.tree_leaves(server)
        return out
