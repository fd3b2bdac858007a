"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs (``mfu``). Only matrix products count, at 2 FLOPs per
multiply-add; a training step costs three forward passes (forward, and
the two products of the backward pass). Recomputation and masked steps
do not count: a step counts only when it is live (see ``replay``). The
per-architecture forward count lives in each configuration's reference
module (``configs/<name>.ref.py::forward_flops_per_token``), built from
the helpers here:

* a dense product of an ``m``-wide input into ``n`` outputs: ``2 m n``
  per token;
* the tied LM head: one ``d x V`` product per token over the ``V`` ids
  the configuration holds (the table's padded rows are not needed work);
  the embedding lookup is a gather and costs nothing;
* causal self-attention over ``S`` positions: ``QK^T`` and ``PV`` each
  ``2 S H hd`` per query token for the full square, half of it for the
  causal triangle, so ``2 S H hd`` per token in all;
* the SSD scan (Mamba2, arXiv:2405.21060 §6) with chunk length ``Q``:
  per token ``Q N`` for the causal half of ``C B^T``, ``H P Q`` for the
  causal half of the intra-chunk output, ``2 H P N`` each for the chunk
  states and the inter-chunk output, and ``2 H P N / Q`` for passing the
  state between chunks. The count takes the ``Q`` that needs least, so it
  never charges more than any chunking must do.

Aggregation bytes (``agg_roofline``). One FAVAS round reads the server
row and writes the new one, and reads the ``s`` selected rows of clients
and of inits: ``(2 s + 2) D`` f32 words, ``D`` the logical parameter
count. Unselected rows are not needed, so a kernel that reads only the
selected rows stays under 100%."""
from __future__ import annotations


def dense(m: int, n: int) -> float:
    return 2.0 * m * n


def causal_attention(seq: int, heads: int, head_dim: int) -> float:
    return 2.0 * seq * heads * head_dim


def ssd_scan(seq: int, heads: int, head_dim: int, state: int) -> float:
    H, P, N = heads, head_dim, state
    return min(Q * N + H * P * Q + 4.0 * H * P * N + 2.0 * H * P * N / Q
               for Q in range(1, seq + 1))


def train_flops_per_token(forward_flops_per_token: float) -> float:
    return 3.0 * forward_flops_per_token


def agg_bytes_per_round(n_params: int, s: int, word_bytes: int = 4) -> float:
    return (2.0 * s + 2.0) * n_params * word_bytes
