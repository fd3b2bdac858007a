"""Batch pipelines.

* ``FederatedBatcher`` — per-client minibatch streams for the FL simulator
  and the distributed trainer: each call yields a (n_clients, R, B, ...)
  stack (one microbatch per client per potential local step).
  ``superstep_batch`` stacks T of those along a leading rounds axis for the
  on-device superstep scan (core/round_engine.py::engine_multi_round).
* ``lm_round_batch`` / ``lm_superstep_batch`` — token batches for the
  assigned-architecture trainer: clients are mapped to corpus domains
  (non-IID domain skew).
* ``BatchPrefetcher`` — double-buffered background-thread prefetcher: host
  batch generation (and the H2D ``jax.device_put``) overlaps device
  compute, so the superstep host loop never blocks on numpy sampling.
"""
from __future__ import annotations

import queue
import threading
import time
import warnings
from typing import Any, Callable, Optional

import numpy as np


class FederatedBatcher:
    """Per-client minibatch streams.

    ``stream`` versions the rng stream (docs/architecture.md §8):

    * ``"v1"`` (default) — the original per-(client, step)
      ``rng.choice`` loop: without-replacement minibatches whenever the
      partition is large enough, one generator call per cell. Kept as the
      reference stream — results of every pre-existing seed reproduce.
    * ``"v2"`` — fully vectorized: ONE uniform draw per round mapped
      through the padded partition table with the same index math the
      device plane uses (``data.device_corpus.uniform_to_indices``), so a
      round costs one numpy gather instead of ``n * R`` generator calls.
      Samples WITH replacement (like the device plane); the stream differs
      from v1, hence the explicit opt-in.
    """

    def __init__(self, x, y, parts, batch_size: int, seed: int = 0,
                 stream: str = "v1"):
        if stream not in ("v1", "v2"):
            raise ValueError(f"unknown stream version {stream!r}")
        self.x, self.y = x, y
        self.parts = parts
        self.B = batch_size
        self.rng = np.random.default_rng(seed)
        self.stream = stream
        self._lens = np.array([len(p) for p in parts], np.int64)
        if stream == "v2":
            lmax = int(self._lens.max())
            self._table = np.zeros((len(parts), lmax), np.int64)
            for i, p in enumerate(parts):
                self._table[i, :len(p)] = p

    def client_batch(self, i: int):
        idx = self.parts[i]
        take = self.rng.choice(idx, self.B, replace=len(idx) < self.B)
        return self.x[take], self.y[take]

    def round_batch(self, n_steps: int):
        """(n, R, B, d) x, (n, R, B) y for one server round."""
        n = len(self.parts)
        if self.stream == "v2":
            # one generator call + one gather per round: the numpy run of
            # the device plane's index math (j = min(int(u * L), L - 1))
            u = self.rng.random((n, n_steps, self.B))
            j = np.minimum((u * self._lens[:, None, None]).astype(np.int64),
                           self._lens[:, None, None] - 1)
            take = self._table[np.arange(n)[:, None, None], j]
            return self.x[take], self.y[take]
        xs = np.empty((n, n_steps, self.B) + self.x.shape[1:], self.x.dtype)
        ys = np.empty((n, n_steps, self.B), self.y.dtype)
        for i in range(n):
            for k in range(n_steps):
                xs[i, k], ys[i, k] = self.client_batch(i)
        return xs, ys

    def superstep_batch(self, n_rounds: int, n_steps: int):
        """(T, n, R, B, d) x, (T, n, R, B) y — ``n_rounds`` round batches
        stacked on a leading rounds axis, drawn in round order so the rng
        stream is identical to ``n_rounds`` sequential ``round_batch``
        calls."""
        n = len(self.parts)
        xs = np.empty((n_rounds, n, n_steps, self.B) + self.x.shape[1:],
                      self.x.dtype)
        ys = np.empty((n_rounds, n, n_steps, self.B), self.y.dtype)
        for t in range(n_rounds):
            xs[t], ys[t] = self.round_batch(n_steps)
        return xs, ys


def _lm_start_bounds(domains: np.ndarray, n_clients: int, seq: int):
    """Per-client window-start (lo, span): client i samples starts uniformly
    from [lo_i, lo_i + span_i) over domain i % n_domains (domain-skew
    non-IID). The ONE copy of the window-bound formula — shared by both
    host stream versions AND ``data.device_corpus.make_lm_device_corpus``,
    so the two data planes draw from identical pools by construction."""
    n_domains = int(domains.max()) + 1
    dom_index = [np.where(domains == d)[0] for d in range(n_domains)]
    lo = np.empty((n_clients,), np.int64)
    span = np.empty((n_clients,), np.int64)
    for i in range(n_clients):
        pool = dom_index[i % n_domains]
        a, b = int(pool.min()), int(pool.max()) - seq - 1
        lo[i], span[i] = a, max(b, a + 1) - a
    return lo, span


def lm_round_batch(tokens: np.ndarray, domains: np.ndarray, n_clients: int,
                   n_steps: int, batch: int, seq: int,
                   rng: np.random.Generator, stream: str = "v1"):
    """(n, R, B, S) int32 token batch; client i samples from domain
    i % n_domains (domain-skew non-IID).

    ``stream="v1"`` (default) keeps the original per-client
    ``rng.integers`` draws — the stream is IDENTICAL to the seed's triple
    Python loop; only the window gather is vectorized (pure indexing, no
    generator calls). ``"v2"`` draws one uniform block for all clients and
    maps it with the device plane's index math — one generator call per
    round, stream intentionally different."""
    lo, span = _lm_start_bounds(domains, n_clients, seq)
    if stream == "v2":
        u = rng.random((n_clients, n_steps, batch))
        starts = lo[:, None, None] + np.minimum(
            (u * span[:, None, None]).astype(np.int64),
            span[:, None, None] - 1)
    elif stream == "v1":
        starts = np.empty((n_clients, n_steps, batch), np.int64)
        for i in range(n_clients):
            # one rng.integers call per client, exactly as the old loop made
            starts[i] = rng.integers(lo[i], lo[i] + span[i],
                                     (n_steps, batch))
    else:
        raise ValueError(f"unknown stream version {stream!r}")
    return tokens[starts[..., None] + np.arange(seq)].astype(np.int32)


def lm_superstep_batch(tokens: np.ndarray, domains: np.ndarray,
                       n_rounds: int, n_clients: int, n_steps: int,
                       batch: int, seq: int, rng: np.random.Generator,
                       stream: str = "v1"):
    """(T, n, R, B, S) int32 — ``n_rounds`` LM round batches stacked on a
    leading rounds axis, same rng stream as sequential ``lm_round_batch``
    calls."""
    return np.stack([lm_round_batch(tokens, domains, n_clients, n_steps,
                                    batch, seq, rng, stream=stream)
                     for _ in range(n_rounds)])


class BatchPrefetcher:
    """Double-buffered background-thread batch prefetcher.

    ``make_batch(i)`` runs on ONE background thread for i = 0, 1, ... and
    its results queue up to ``depth`` chunks ahead of the consumer;
    :meth:`get` pops the next one. While the device runs superstep i, the
    host is already generating (and, with ``to_device``, ``jax.device_put``-
    copying) superstep i+1 — batch generation leaves the critical path.

    Contract (docs/architecture.md §7):

    * **order & determinism** — generation happens strictly in index order
      on a single thread, so a seeded ``np.random.Generator`` owned by
      ``make_batch`` produces exactly the stream the synchronous loop would;
    * **bounded lookahead** — at most ``depth`` chunks are ever buffered
      (``depth=2`` is classic double buffering: one in flight to the
      device, one being built), so host memory stays bounded;
    * **errors surface at get()** — an exception in ``make_batch`` is
      re-raised on the consumer thread at its position in the stream
      (batches built before the failure are still served first), never
      swallowed;
    * ``n_steps=None`` streams forever; otherwise :meth:`get` raises
      ``StopIteration`` after ``n_steps`` chunks. :meth:`close` stops the
      producer promptly (it may still finish the chunk it is building).

    ``to_device`` applies ``jax.device_put`` on the producer thread, which
    overlaps the host->device copy with compute as well (JAX transfers are
    thread-safe and async).
    """

    def __init__(self, make_batch: Callable[[int], Any],
                 n_steps: Optional[int] = None, depth: int = 2,
                 to_device: bool = True):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._n = n_steps
        self._served = 0
        self._done = object()           # sentinel: producer exhausted
        self._make = make_batch
        self._to_device = to_device
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _produce(self):
        try:
            i = 0
            while not self._stop.is_set() and (self._n is None or i < self._n):
                b = self._make(i)
                if self._to_device:
                    import jax
                    b = jax.device_put(b)
                # bounded put that still honors close(): poll the stop flag
                while not self._stop.is_set():
                    try:
                        self._q.put(b, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                i += 1
        except BaseException as e:  # noqa: BLE001 — re-raised at get()
            self._err = e
        finally:
            try:
                self._q.put(self._done, timeout=0.1)
            except queue.Full:
                pass

    def get(self):
        """Next batch, blocking until the producer has one ready. Batches
        built before a producer failure are still served (FIFO); the error
        surfaces at its position in the stream."""
        import jax
        with jax.profiler.TraceAnnotation("favas_prefetch_wait"):
            while True:
                if self._n is not None and self._served >= self._n:
                    raise StopIteration
                try:
                    b = self._q.get(timeout=0.1)
                except queue.Empty:
                    if self._err is not None:
                        err, self._err = self._err, None
                        raise err
                    if not self._thread.is_alive():
                        raise StopIteration from None
                    continue
                if b is self._done:
                    if self._err is not None:
                        err, self._err = self._err, None
                        raise err
                    raise StopIteration
                self._served += 1
                return b

    def __iter__(self):
        while True:
            try:
                yield self.get()
            except StopIteration:
                return

    def close(self, timeout: float = 30.0) -> bool:
        """Stop the producer and drop buffered chunks. Returns True once
        the producer thread has actually exited.

        Deadlock-safe even when the producer is blocked on a FULL queue:
        the stop flag is set first (the producer's ``put`` polls it every
        0.1 s), then drain-and-join repeats until the thread exits — a
        single drain could race a producer that was mid-``put`` and leave
        it parked behind a re-filled queue. The deadline is measured on
        ``time.monotonic`` (NOT join-call counts, which under-measure when
        a drain or a slow ``device_put`` eats wall time between joins); on
        expiry a ``RuntimeWarning`` is emitted and False returned, so a
        leaked producer is observable instead of silently orphaned
        (tests/test_async_server.py asserts the no-leak contract). A
        pending producer error is NOT cleared here; :meth:`__exit__`
        re-raises it so failures can't vanish when the consumer stops
        early."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive():
            while True:
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            self._thread.join(timeout=min(0.25, remaining))
        # drop anything the producer managed to enqueue while exiting
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            warnings.warn(
                f"BatchPrefetcher.close(): producer thread still alive "
                f"after {timeout:.1f}s (slow make_batch/device_put?)",
                RuntimeWarning, stacklevel=2)
            return False
        return True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        """Close, then PROPAGATE a pending producer error (one that was
        raised on the producer thread but never surfaced through ``get``)
        — unless the body is already unwinding with its own exception."""
        self.close()
        if self._err is not None and exc_type is None:
            err, self._err = self._err, None
            raise err
        return False
