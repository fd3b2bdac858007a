"""The token corpus the cells train on, made on the host from the seed.

A copy of the program's ``data/synthetic.py::make_lm_corpus`` (per-domain
unigram mixtures) and of ``data/pipeline.py::_lm_start_bounds`` (client i
samples windows from domain ``i % n_domains``), kept here so that a later
change to the program cannot change the benchmark's data."""
from __future__ import annotations

import numpy as np


def make_lm_corpus(vocab: int, n_tokens: int, n_domains: int, seed: int):
    """(tokens int32, domain ids int32) — domain d draws ``n_tokens //
    n_domains`` tokens from its own unigram law, logits ~ N(0, 2)."""
    rng = np.random.default_rng(seed)
    per = n_tokens // n_domains
    toks, doms = [], []
    for d in range(n_domains):
        logits = rng.normal(0, 2.0, vocab)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        toks.append(rng.choice(vocab, per, p=probs).astype(np.int32))
        doms.append(np.full(per, d, np.int32))
    return np.concatenate(toks), np.concatenate(doms)


def start_bounds(domains: np.ndarray, n_clients: int, seq: int):
    """Per-client window-start range ``[lo, lo + span)`` over domain
    ``i % n_domains``."""
    n_domains = int(domains.max()) + 1
    lo = np.empty((n_clients,), np.int32)
    span = np.empty((n_clients,), np.int32)
    for i in range(n_clients):
        pool = np.where(domains == i % n_domains)[0]
        a, b = int(pool.min()), int(pool.max()) - seq - 1
        lo[i], span[i] = a, max(b, a + 1) - a
    return lo, span
