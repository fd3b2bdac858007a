"""The comparison that decides ``correct`` for a training cell.

The program's readings come from the chunks set-up drives through the
window's own call (see ``harness``); the reference follows the same
chunks from the same weights, windows and draws. Three numbers:

* ``loss``: the largest relative gap between the program's and the
  reference's loss, over every round of those chunks that ran a live step;
* ``first_change``: what FAVAS's server update got from the first chunk —
  the server's change over it — compared leaf by leaf: the gap between
  the program's and the reference's norm of a leaf's change, over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger; the worst leaf counts;
* ``change_3``: the same for the server's change after the third chunk,
  read before the fourth chunk takes the state.

Leaves whose first change in the reference is under a thousandth of the
median leaf's are left out of both changes: they move by round-off only.
A number without a limit (the cell's limits not yet set) cannot pass."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss", "first_change", "change_3")


@jax.jit
def _dist(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


def _norm(a, b) -> float:
    """||a - b|| on the device (float32 tree reduction: about 1e-6 of
    the norm, far below the gaps compared)."""
    return float(_dist(jnp.asarray(a), jnp.asarray(b)))


def loss_gap(prog_losses, ref_losses, ref_live) -> float:
    gaps = [abs(p - r) / max(abs(r), 1e-30)
            for p, r, n in zip(prog_losses, ref_losses, ref_live) if n > 0]
    if len(prog_losses) != len(ref_losses) or not gaps:
        return float("inf")
    if not all(np.isfinite(prog_losses)):
        return float("inf")
    return max(gaps)


def change_norms(leaves, w0) -> np.ndarray:
    return np.array([_norm(x, w) for x, w in zip(leaves, w0)])


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    """(gap, index of the worst leaf)."""
    med = float(np.median(ref[keep]))
    gaps = np.where(keep, np.abs(prog - ref) / np.maximum(ref, med), -1.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def compare(prog: dict, ref: dict, w0: list, names: list) -> dict:
    """``prog``/``ref``: {"losses": [...], "servers": {1: leaves, 3:
    leaves}} (``ref`` also "live"). Returns the numbers and, for the
    changes, the worst leaf's name."""
    r1 = change_norms(ref["servers"][1], w0)
    keep = r1 >= 1e-3 * float(np.median(r1))
    out = {"loss": loss_gap(prog["losses"], ref["losses"], ref["live"])}
    for c, name in ((1, "first_change"), (3, "change_3")):
        p_n = change_norms(prog["servers"][c], w0)
        r_n = r1 if c == 1 else change_norms(ref["servers"][c], w0)
        gap, i = worst_leaf_gap(p_n, r_n, keep)
        if not np.all(np.isfinite(p_n)):
            gap = float("inf")
        out[name] = gap
        out[name + "_leaf"] = names[i]
    out["left_out"] = [n for n, k in zip(names, keep) if not k]
    return out


def judge(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]) — every number at or under its
    limit; a missing limit fails."""
    rows = [(k, numbers[k], limits.get(k)) for k in NUMBERS]
    ok = all(lim is not None and np.isfinite(v) and v <= lim
             for _, v, lim in rows)
    return ok, rows
