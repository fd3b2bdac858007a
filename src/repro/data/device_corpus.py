"""On-device data plane: a resident corpus the superstep scan samples.

The host data plane (``data/pipeline.py``) generates every microbatch in
numpy and ships ``(T, n, R, B, ...)`` arrays to the device — after PR 4
that generation is the LAST host-side work per superstep chunk, and at
large ``n_clients`` it lags the device (ROADMAP "next lever"). This module
replaces it for the device plane (``--data-plane device``,
docs/architecture.md §8):

* the corpus (feature/label rows or the token stream) and the per-client
  partition **index tables** are uploaded ONCE (``jax.device_put``;
  replicated over the mesh when one is given, matching how
  ``round_engine.engine_sharding`` treats per-client auxiliaries);
* ragged partitions are padded to one rectangular ``(n, Lmax)`` int32
  table with a per-client ``lengths`` vector — padded entries are never
  sampled because every drawn local index ``j`` satisfies
  ``j < lengths[i]`` by construction (tests/test_device_corpus.py);
* :meth:`DeviceCorpus.sample_round_batch` draws the per-client minibatch
  indices INSIDE the jitted scan body from an explicit PRNG key and
  gathers the rows on device — zero host work per round.

Index-sampling math (the contract the numpy mirrors pin down bit-exactly):
one ``jax.random.uniform`` draw ``u`` of shape ``(n, R, B)`` maps to local
indices ``j = min(int(u * L_i), L_i - 1)`` — f32 multiply + truncation,
identical IEEE ops in jnp and numpy, so :func:`mirror_partition_indices` /
:func:`mirror_lm_starts` reproduce the device indices element-exactly from
the same uniforms. The stream is the jax PRNG (not numpy's), so the device
plane is *statistically equivalent* to the host plane, not
stream-identical — same contract PR 4 set for on-device selection. The
host batcher's ``stream="v2"`` path (``data/pipeline.py``) runs the exact
same index math on numpy's generator.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.profiling import scoped


# ---------------------------------------------------------------------------
# Index-sampling math + numpy mirrors
# ---------------------------------------------------------------------------

def uniform_to_indices(u, lengths):
    """Map uniforms ``u`` in [0, 1) to local indices ``j < lengths[i]``.

    ``u``: (n, ...) f32; ``lengths``: (n,) int32 (must be >= 1). The math is
    ``j = min(int(u * L), L - 1)`` — pure f32 multiply + int truncation, so
    the numpy mirror is element-exact for identical ``u``."""
    L = lengths.reshape(lengths.shape + (1,) * (u.ndim - 1))
    j = (u * L.astype(jnp.float32)).astype(jnp.int32)
    return jnp.minimum(j, L - 1)


def mirror_partition_indices(u: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Numpy mirror of :func:`uniform_to_indices` — element-exact against
    the device sampler for the same (f32) uniforms."""
    u = np.asarray(u, np.float32)
    L = np.asarray(lengths, np.int32).reshape(
        (len(lengths),) + (1,) * (u.ndim - 1))
    j = (u * L.astype(np.float32)).astype(np.int32)
    return np.minimum(j, L - 1)


def sample_partition_indices(key, lengths, n_steps: int, batch: int):
    """(n, n_steps, batch) int32 local indices, one uniform draw per slot."""
    u = jax.random.uniform(key, (lengths.shape[0], n_steps, batch))
    return uniform_to_indices(u, lengths)


def mirror_lm_starts(u: np.ndarray, lo: np.ndarray,
                     span: np.ndarray) -> np.ndarray:
    """Numpy mirror of the LM start sampling: ``lo + min(int(u*span),
    span-1)`` — element-exact against the device draw for the same u."""
    return (np.asarray(lo, np.int32).reshape(
        (len(lo),) + (1,) * (u.ndim - 1))
        + mirror_partition_indices(u, span))


# ---------------------------------------------------------------------------
# The corpus
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceCorpus:
    """Device-resident corpus + per-client sampling tables.

    Two kinds share one type (the static ``kind`` picks the sample path):

    * ``"classification"`` — ``x (N, d)``, ``y (N,)``, padded partition
      index table ``idx (n, Lmax)`` int32 and ``lengths (n,)`` int32
      (ragged partitions right-padded with 0; the pad is masked by the
      ``j < lengths`` invariant, never by branching);
    * ``"lm"`` — ``tokens (N,)`` int32 plus per-client window-start bounds
      ``lo (n,)`` / ``span (n,)`` int32 (client i samples starts uniformly
      from ``[lo_i, lo_i + span_i)``, the same domain-skew pools the host
      ``lm_round_batch`` uses).

    A ``DeviceCorpus`` is a pytree (arrays are leaves, ``kind``/``batch``/
    ``seq`` are static aux data), so it passes straight through ``jax.jit``
    / ``lax.scan`` closures without retracing per call.
    """
    kind: str                      # "classification" | "lm"  (static)
    batch: int                     # B, per-client per-step     (static)
    seq: int                       # S, LM window length        (static)
    x: Optional[jnp.ndarray] = None        # (N, d) features
    y: Optional[jnp.ndarray] = None        # (N,) labels
    idx: Optional[jnp.ndarray] = None      # (n, Lmax) int32 partition table
    lengths: Optional[jnp.ndarray] = None  # (n,) int32 partition sizes
    tokens: Optional[jnp.ndarray] = None   # (N,) int32 token stream
    lo: Optional[jnp.ndarray] = None       # (n,) int32 window-start lows
    span: Optional[jnp.ndarray] = None     # (n,) int32 window-start ranges

    def tree_flatten(self):
        children = (self.x, self.y, self.idx, self.lengths,
                    self.tokens, self.lo, self.span)
        return children, (self.kind, self.batch, self.seq)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], aux[1], aux[2], *children)

    @property
    def n_clients(self) -> int:
        v = self.lengths if self.kind == "classification" else self.lo
        return v.shape[0]

    @scoped("favas_sample")
    def sample_round_batch(self, key, n_steps: int, ids=None) -> Dict:
        """Draw one round's per-client microbatches ON DEVICE (jit/scan
        safe). Returns the same batch pytree the host plane ships:
        ``{"x": (n, R, B, d), "y": (n, R, B)}`` for classification,
        ``{"tokens": (n, R, B, S)}`` for LM.

        ``ids``: optional (s,) int32 client ids — return only those
        clients' rows (leading axis s), for the paged engine's hot working
        set. The index draw always covers ALL n clients so the PRNG stream
        is identical to the dense call; only the corpus DATA gather is
        restricted to ``ids`` (with ``ids == arange(n)`` the result is the
        full batch, value-for-value)."""
        if self.kind == "classification":
            j = sample_partition_indices(key, self.lengths, n_steps,
                                         self.batch)
            n = self.lengths.shape[0]
            cids = jnp.arange(n) if ids is None else ids
            rows = self.idx[cids[:, None, None], j[cids]]
            return {"x": self.x[rows], "y": self.y[rows]}
        u = jax.random.uniform(key, (self.lo.shape[0], n_steps, self.batch))
        lo, span = self.lo, self.span
        if ids is not None:
            u, lo, span = u[ids], lo[ids], span[ids]
        starts = lo[:, None, None] + uniform_to_indices(u, span)
        return {"tokens": self.tokens[starts[..., None]
                                      + jnp.arange(self.seq)]}

    def nbytes(self) -> int:
        """Total device bytes of the corpus arrays (the all-gather audit
        bound in tests/test_sharded_engine.py)."""
        tot = 0
        for leaf in self.tree_flatten()[0]:
            if leaf is not None:
                tot += leaf.size * jnp.dtype(leaf.dtype).itemsize
        return tot


def _put(arrays: Dict[str, np.ndarray], mesh) -> Dict[str, jnp.ndarray]:
    """Upload once; replicated over the mesh when one is given (the corpus
    is read-only side input — every model shard gathers locally)."""
    if mesh is None:
        return {k: jax.device_put(jnp.asarray(v)) for k, v in arrays.items()}
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    return {k: jax.device_put(jnp.asarray(v), rep) for k, v in arrays.items()}


def make_classification_corpus(x, y, parts: Sequence, batch: int,
                               *, mesh=None) -> DeviceCorpus:
    """Upload a classification corpus + ragged per-client partitions.

    ``parts``: list of per-client index arrays into ``x``/``y`` (ragged).
    Padded table entries are 0 but provably never sampled (``j <
    lengths[i]``)."""
    n = len(parts)
    if n == 0 or any(len(p) == 0 for p in parts):
        raise ValueError("every client partition must be non-empty")
    lmax = max(len(p) for p in parts)
    idx = np.zeros((n, lmax), np.int32)
    lengths = np.empty((n,), np.int32)
    for i, p in enumerate(parts):
        idx[i, :len(p)] = np.asarray(p, np.int32)
        lengths[i] = len(p)
    put = _put({"x": np.asarray(x), "y": np.asarray(y),
                "idx": idx, "lengths": lengths}, mesh)
    return DeviceCorpus(kind="classification", batch=batch, seq=0, **put)


def make_lm_device_corpus(tokens: np.ndarray, domains: np.ndarray,
                          n_clients: int, batch: int, seq: int,
                          *, mesh=None) -> DeviceCorpus:
    """Upload a token stream + per-client window-start bounds.

    Client i samples from domain ``i % n_domains`` over the SAME
    ``[lo, lo + span)`` start range as the host ``lm_round_batch``
    (``pipeline._lm_start_bounds`` — one shared formula, so the two planes
    draw from identical pools by construction)."""
    from repro.data.pipeline import _lm_start_bounds  # no import cycle
    lo, span = _lm_start_bounds(domains, n_clients, seq)
    put = _put({"tokens": np.asarray(tokens, np.int32),
                "lo": np.asarray(lo, np.int32),
                "span": np.asarray(span, np.int32)}, mesh)
    return DeviceCorpus(kind="lm", batch=batch, seq=seq, **put)
