"""Plain float32 reference of a Mamba2 language model
(state-spaces/mamba2-1.3b; arXiv:2405.21060).

Written from the published description, not from the program: token
embedding; per layer RMSNorm -> Mamba2 mixer -> residual; final RMSNorm;
logits against the tied embedding table. The mixer projects the input to
the gate z, the inner stream x, the shared B and C (ngroups 1) and one dt
per head; x, B and C go through a causal depthwise convolution of width
d_conv with bias and a SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log).
The selective state space runs in its quadratic ("dual") form, which is
exact and needs no chunking:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < k <= t} dt_k A) dt_s x_s
          + D x_t,

then y * SiLU(z), RMSNorm over the inner width (the gated norm, with its
own epsilon ``gated_norm_epsilon``), and the output projection. Every
product goes through ``mm``. The training objective is next-token
cross-entropy over all positions, the last position's label being id 0
(the objective the trainer states)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _dims(hf):
    d = hf["d_model"]
    din = hf["expand"] * d
    return d, din, din // hf["headdim"], hf["headdim"], hf["d_state"], \
        hf["ngroups"], hf["d_conv"]


def weight_shapes(hf: dict) -> dict:
    d, din, H, P, N, G, K = _dims(hf)
    L = hf["n_layer"]
    return {
        "embed": {"table": (hf["table_rows"], d)},
        "layers": {
            "norm1": {"scale": (L, d)},
            "ssm": {
                "in_z": {"w": (L, d, din)}, "in_x": {"w": (L, d, din)},
                "in_B": {"w": (L, d, G * N)}, "in_C": {"w": (L, d, G * N)},
                "in_dt": {"w": (L, d, H)},
                "conv_x": {"w": (L, K, din), "b": (L, din)},
                "conv_B": {"w": (L, K, G * N), "b": (L, G * N)},
                "conv_C": {"w": (L, K, G * N), "b": (L, G * N)},
                "A_log": (L, H), "D": (L, H), "dt_bias": (L, H),
                "norm": {"scale": (L, din)},
                "out_proj": {"w": (L, din, d)},
            },
        },
        "final_norm": {"scale": (d,)},
    }


def init_leaf(path: str, shape: tuple, key):
    """Random weights as the published initialisation draws them: A from
    U[1, 16], dt_bias the inverse softplus of dt ~ logU[1e-3, 1e-1], D and
    the norms 1, the table N(0, 0.02), projections N(0, 1 / fan_in),
    convolution taps N(0, 1 / d_conv) with zero bias."""
    if path.endswith("scale") or path.endswith("/D"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if path.endswith("dt_bias"):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    if path.endswith("table"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if "/conv_" in path:
        if path.endswith("/b"):
            return jnp.zeros(shape, jnp.float32)
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])


def _conv(x, w, b):
    """Causal depthwise convolution: out_t = sum_i w_i x_{t - K + 1 + i}."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i] for i in range(K)) + b


def _segsum(a):
    """a: (..., S) -> (..., S, S) with [t, s] = sum_{s < k <= t} a_k for
    s <= t and -inf above the diagonal; each entry is its own sum, not a
    difference of running totals."""
    S = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (S,))
    rep = jnp.where(jnp.tril(jnp.ones((S, S), bool), -1), rep, 0.0)
    seg = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), seg, -jnp.inf)


def _mixer(p, h, hf, mm):
    d, din, H, P, N, G, K = _dims(hf)
    B, S, _ = h.shape
    z = mm("bsd,df->bsf", h, p["in_z"]["w"])
    x = mm("bsd,df->bsf", h, p["in_x"]["w"])
    Bm = mm("bsd,df->bsf", h, p["in_B"]["w"])
    Cm = mm("bsd,df->bsf", h, p["in_C"]["w"])
    dt = mm("bsd,df->bsf", h, p["in_dt"]["w"])
    x = jax.nn.silu(_conv(x, p["conv_x"]["w"], p["conv_x"]["b"]))
    Bm = jax.nn.silu(_conv(Bm, p["conv_B"]["w"], p["conv_B"]["b"]))
    Cm = jax.nn.silu(_conv(Cm, p["conv_C"]["w"], p["conv_C"]["b"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (B, S, H)
    A = -jnp.exp(p["A_log"])                                     # (H,)
    xh = x.reshape(B, S, H, P)
    Bg = Bm.reshape(B, S, G, N)
    Cg = Cm.reshape(B, S, G, N)
    ys = []
    for g in range(G):                       # heads of group g share B, C
        hs = slice(g * H // G, (g + 1) * H // G)
        cb = mm("btn,bsn->bts", Cg[:, :, g], Bg[:, :, g])        # (B, S, S)
        decay = jnp.exp(_segsum(jnp.moveaxis(dt[..., hs] * A[hs], 1, 2)))
        w = cb[:, None] * decay * jnp.moveaxis(dt[..., hs], 1, 2)[:, :, None]
        ys.append(mm("bhts,bshp->bthp", w, xh[:, :, hs]))
    y = jnp.concatenate(ys, axis=2) + xh * p["D"][:, None]
    y = y.reshape(B, S, din) * jax.nn.silu(z)
    y = _rms(y, p["norm"]["scale"], hf["gated_norm_epsilon"])
    return mm("bsf,fd->bsd", y, p["out_proj"]["w"])


def token_losses(p, tokens, hf: dict, mm):
    """Per-position cross-entropy (B, S) of one window of token ids."""
    table = p["embed"]["table"]
    x = table[tokens]
    for l in range(p["layers"]["norm1"]["scale"].shape[0]):
        lp = jax.tree_util.tree_map(lambda a: a[l], p["layers"])
        x = x + _mixer(lp["ssm"], _rms(x, lp["norm1"]["scale"],
                                        hf["layer_norm_epsilon"]), hf, mm)
    x = _rms(x, p["final_norm"]["scale"], hf["layer_norm_epsilon"])
    logits = mm("bsd,vd->bsv", x, table)
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jax.nn.logsumexp(logits, -1) - picked


def forward_flops_per_token(hf: dict, seq: int, counting) -> float:
    """Matrix-product FLOPs of one token's forward pass (see
    ``lib/counting.py``): the five input projections and the output
    projection of every layer, the SSD scan at its least chunked cost, and
    the tied head over the configuration's ``vocab_size`` ids. The
    depthwise convolution is not a matrix product and is not counted."""
    d, din, H, P, N, G, K = _dims(hf)
    layer = (2 * counting.dense(d, din) + 2 * counting.dense(d, G * N)
             + counting.dense(d, H) + counting.dense(din, d)
             + counting.ssd_scan(seq, H, P, N))
    return hf["n_layer"] * layer + counting.dense(d, hf["vocab_size"])
