"""Roofline analysis from compiled dry-run artifacts (terms are *derived*
from shapes and compiled HLO, not timed).

Terms per (arch, shape, mesh), all in seconds, against the target chip's
published peaks (:data:`PEAKS`, keyed by ``device_kind``):
  compute    = FLOPs_per_chip / flops          (bf16 peak)
  memory     = HBM_bytes_per_chip / hbm_bw
  collective = collective_bytes_per_chip / ici_bw (per-link ICI)

Sources:
* collective bytes — parsed from ``compiled.as_text()``; XLA:CPU while loops
  carry ``backend_config={"known_trip_count":{"n":N}}``, so collectives inside
  scan bodies are multiplied by their (possibly nested) trip counts. This
  fixes the body-counted-once problem exactly for comms.
* ``compiled.cost_analysis()`` flops/bytes are recorded raw but — caveat —
  XLA's HloCostAnalysis counts while bodies ONCE; for scanned layers/steps
  the raw number underestimates by ~L*K. The roofline compute/memory terms
  therefore use the ANALYTIC estimators below (6*N*D etc.), and the raw
  numbers are kept as a cross-check column.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. TPU v5e:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s ICI per chip over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``; a kind without published peaks in
    :data:`PEAKS` is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->.*\{\s*$")
_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*(\w+)\[([\d,]*)\][^ ]*\s+(" + "|".join(COLLECTIVES) + r")\(")
_WHILE_RE = re.compile(r"while\(.*?body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?\s*(\w+)\[([\d,]*)\]")
_DOT_RE = re.compile(
    r"=\s*(\w+)\[([\d,]*)\][^ ]*\s+dot\(\s*%?([\w.\-]+)\s*,")
_DOT_LHS_CONTR_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_PARAM_RE = re.compile(r"%?([\w.\-]+)\s*:\s*\(?(\w+)\[([\d,]*)\]")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)          # [n_groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 2                                     # conservative default


def _wire_factor(kind: str, g: int) -> float:
    """Per-device ICI wire bytes as a multiple of the op's OUTPUT bytes.
    S = gathered (full) size: AG out = S, RS out = S/g, AR out = S."""
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g
    if kind == "all-gather":
        return (g - 1) / g
    if kind == "reduce-scatter":
        return float(g - 1)                      # input = g * output
    if kind == "all-to-all":
        return (g - 1) / g
    return 1.0                                   # collective-permute


# JAX einsum subscripts whose outputs are compute-dtype (bf16) on TPU.
# XLA:CPU float-normalizes bf16 dots to f32, so the CPU-compiled HLO shows
# f32 collectives where the TPU program moves bf16 — collectives whose
# op_name metadata stems from these einsums are counted at half width.
BF16_DOT_TAGS = ("...d,df->...f", "ecd,edf->ecf", "ecf,efd->ecd")


def collective_ops(hlo_text: str) -> List[Tuple[str, int]]:
    """Flat (kind, output_bytes) list of every collective op in an HLO text,
    ignoring trip counts — the raw census the sharded-engine acceptance
    check reads (tests assert no all-gather at full-flat-buffer size; see
    docs/architecture.md §6 and tests/test_sharded_engine.py)."""
    out = []
    for ln in hlo_text.splitlines():
        m = _COLL_RE.search(ln)
        if m:
            dtype, dims, kind = m.groups()
            out.append((kind, _shape_bytes(dtype, dims)))
    return out


def dense_materializations(hlo_text: str, *, rows: int, min_cols: int = 128,
                           dtypes: Tuple[str, ...] = ("f32", "bf16")
                           ) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """Census of full-precision (rows, >=min_cols, ...) arrays DEFINED
    anywhere in an HLO text — the quantized-transport acceptance gate
    (docs/architecture.md §10, tests/test_quant_fused.py).

    A compiled codes-in round must never materialize the transmitted
    progress (or a cold pool) as a dense float array over the full client
    population: every op whose output is ``f32/bf16[rows, C>=min_cols,
    ...]`` is returned as ``(op_name, dtype, dims)``. ``rows`` is the
    population being gated (n for the whole round, s_max for the isolated
    cold promote/evict cycle); ``min_cols`` filters out (rows,)-shaped
    bookkeeping vectors and (rows, 1) scale columns, which are legitimate
    full-precision residents. uint8 code buffers at any shape pass — they
    ARE the storage format."""
    out = []
    for ln in hlo_text.splitlines():
        m = _DEF_RE.match(ln)
        if not m:
            continue
        name, dtype, dims = m.groups()
        if dtype not in dtypes or not dims.strip():
            continue
        d = tuple(int(x) for x in dims.split(","))
        if len(d) >= 2 and d[0] == rows and max(d[1:]) >= min_cols:
            out.append((name, dtype, d))
    return out


# entry-output defining opcodes that do NOT rewrite the full buffer: the
# output either aliases a donated input directly or is produced by an
# in-place churn-bounded update (scatter / dynamic-update-slice; XLA:CPU
# either expands a row scatter to a while loop whose result surfaces
# through get-tuple-element, or wraps it in a loop fusion whose root is the
# scatter — a fusion counts as the opcode of its computation's ROOT).
# Everything else writes the whole buffer.
_IN_PLACE_OPS = frozenset({
    "parameter", "get-tuple-element", "dynamic-update-slice", "scatter",
    "bitcast", "copy-start", "copy-done", "optimization-barrier", "tuple",
})
_OPCODE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(?:\([^)]*\)|[\w\[\]{},]+)\s+([\w\-]+)\(")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_FUSION_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def pass_through_copies(hlo_text: str, *, rows: int, min_cols: int = 128,
                        dtypes: Tuple[str, ...] = ("f32", "bf16")
                        ) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """Write-traffic audit of a compiled round (docs/architecture.md §13):
    entry outputs of shape ``(rows, >=min_cols, ...)`` in a full-precision
    dtype whose defining op REWRITES the whole buffer.

    The round's contract on large stacks is that the donated client/init
    stacks are only ever touched by churn-bounded in-place updates
    (scatter / dynamic-update-slice on the aliased input), so unselected
    rows are never rewritten; a full ``(n, D)`` elementwise fusion there
    (such as the blend ``m*s_new + (1-m)*x``) costs ~1 extra read + 1
    extra write per resident byte.
    Returns ``(output_name, defining_opcode, dims)`` per violation; a
    compiled streamed round whose stacks take the scatter reset must
    return ``[]``, and one whose stacks are small enough for the masked
    reset (``round_engine.reset_path``) flags exactly those passes (both
    pinned in tests/test_streaming.py beside the
    ``dense_materializations`` gate this mirrors). ``rows`` is the
    client-stack row count (n padded, or s_max-stack rows for a paged
    round)."""
    comps: Dict[str, List[str]] = {}
    entry = cur = None
    for ln in hlo_text.splitlines():
        s = ln.strip()
        if cur is None:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", s)
            if m and s.endswith("{") and "->" in s:
                cur = m.group(1)
                comps[cur] = []
                entry = cur if s.startswith("ENTRY") else entry
        elif s == "}":
            cur = None
        else:
            comps[cur].append(s)
    if entry is None:
        return []

    def root_opcode(comp):
        m = next((_OPCODE_RE.match(s) for s in comps.get(comp, ())
                  if s.startswith("ROOT")), None)
        return m.group(2) if m else "?"

    defs: Dict[str, Tuple[str, str, str, str]] = {}    # name -> (op, dtype,
    for s in comps[entry]:                              #  dims, line)
        m, d = _OPCODE_RE.match(s), _DEF_RE.match(s)
        if m and d:
            defs[m.group(1)] = (m.group(2), d.group(2), d.group(3), s)
    root = next((s for s in comps[entry] if s.startswith("ROOT")), "")
    m = _OPCODE_RE.match(root)
    if m is None:
        return []
    # the entry outputs: the ROOT tuple's operands, or the ROOT itself.
    # Operands are looked up by name: XLA prints them untyped
    # (``tuple(%a, %b)``)
    outputs = ([m.group(1)] if m.group(2) != "tuple"
               else _OPERAND_NAME_RE.findall(root.split("(", 2)[-1]))
    out = []
    for name in outputs:
        if name not in defs:
            continue
        op, dtype, dims, line = defs[name]
        if dtype not in dtypes or not dims.strip():
            continue
        d = tuple(int(x) for x in dims.split(","))
        if len(d) < 2 or d[0] != rows or max(d[1:]) < min_cols:
            continue
        if op == "fusion":
            callee = _FUSION_CALLS_RE.search(line)
            if callee and root_opcode(callee.group(1)) in _IN_PLACE_OPS:
                continue
        if op not in _IN_PLACE_OPS:
            out.append((name, op, d))
    return out


def parse_hlo_collectives(hlo_text: str, *, bf16_dot_comms: bool = False) -> Dict:
    """Trip-count-aware collective byte accounting (per-device program).

    Returns {kind: bytes} plus per-kind op counts and the top shapes.
    ``bf16_dot_comms``: apply the TPU-dtype correction above (set when the
    model's compute dtype is bf16).
    """
    # 1. split into computations: header = "<name> (sig) -> ... {",
    #    body runs until a lone "}" (HLO computations are flat).
    comps: Dict[str, List[str]] = {}
    entry = None
    cur = None
    name_re = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
    for line in hlo_text.splitlines():
        s = line.strip()
        if cur is None:
            if s.endswith("{") and "->" in s:
                m = name_re.match(s)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
                    if s.startswith("ENTRY"):
                        entry = cur
        else:
            if s == "}":
                cur = None
            else:
                comps[cur].append(line)

    # 2. per-computation collectives, dots, and calls
    comp_coll: Dict[str, List[Tuple[str, int, float]]] = {}
    comp_flops: Dict[str, float] = {}
    comp_calls: Dict[str, List[Tuple[str, int]]] = {}   # (callee, multiplier)
    for name, lines in comps.items():
        colls, calls = [], []
        flops = 0.0
        # local symbol table: op/param name -> (dtype, dims) for dot operands
        symtab: Dict[str, Tuple[str, str]] = {}
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if dm:
                symtab[dm.group(1)] = (dm.group(2), dm.group(3))
        for ln in lines:
            cm = _COLL_RE.search(ln)
            if cm:
                dtype, dims, kind = cm.groups()
                out_bytes = _shape_bytes(dtype, dims)
                if (bf16_dot_comms and dtype == "f32"
                        and any(t in ln for t in BF16_DOT_TAGS)):
                    out_bytes //= 2              # bf16 on the TPU target
                wire = out_bytes * _wire_factor(kind, _group_size(ln))
                colls.append((kind, out_bytes, wire))
            dot = _DOT_RE.search(ln)
            if dot:
                _, out_dims, lhs_name = dot.groups()
                out_elems = 1
                for d in out_dims.split(","):
                    if d:
                        out_elems *= int(d)
                contr = 1
                lhs = symtab.get(lhs_name)
                cdm = _DOT_LHS_CONTR_RE.search(ln)
                if lhs and cdm and cdm.group(1):
                    lhs_dims = [int(d) for d in lhs[1].split(",") if d]
                    for ci in cdm.group(1).split(","):
                        ci = int(ci)
                        if ci < len(lhs_dims):
                            contr *= lhs_dims[ci]
                flops += 2.0 * out_elems * contr
            if " while(" in ln:
                wm = _WHILE_RE.search(ln)
                tm = _TRIP_RE.search(ln)
                if wm:
                    calls.append((wm.group(1), int(tm.group(1)) if tm else 1))
            else:
                for callee in _CALL_RE.findall(ln):
                    calls.append((callee, 1))
        comp_coll[name] = colls
        comp_calls[name] = calls
        comp_flops[name] = flops

    # 3. walk from ENTRY with multipliers
    totals: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    wire_totals: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    shapes: Dict[str, float] = {}
    seen_stack = []
    dot_flops = [0.0]

    def walk(name: str, mult: float):
        if name not in comps or name in seen_stack:
            return
        seen_stack.append(name)
        for kind, out_bytes, wire in comp_coll.get(name, ()):
            totals[kind] += mult * out_bytes
            wire_totals[kind] += mult * wire
            counts[kind] += int(mult)
            key = f"{kind}:{out_bytes}"
            shapes[key] = shapes.get(key, 0) + mult * wire
        dot_flops[0] += mult * comp_flops.get(name, 0.0)
        for callee, m in comp_calls.get(name, ()):
            walk(callee, mult * m)
        seen_stack.pop()

    if entry is None and comps:
        entry = list(comps)[-1]
    walk(entry, 1.0)
    top = sorted(shapes.items(), key=lambda kv: -kv[1])[:8]
    return {"bytes_by_kind": totals, "op_counts": counts,
            "wire_bytes_by_kind": wire_totals,
            "total_bytes": sum(wire_totals.values()),
            "output_bytes": sum(totals.values()),
            "dot_flops": dot_flops[0],
            "top_contributors": top}


# ---------------------------------------------------------------------------
# Analytic FLOPs / bytes estimators
# ---------------------------------------------------------------------------

def model_param_counts(cfg) -> Dict[str, int]:
    """Exact param counts via eval_shape (no allocation)."""
    import jax
    import functools
    from repro.models.model import init_params
    from repro.utils.tree import tree_param_count
    key = jax.ShapeDtypeStruct((2,), "uint32")
    sds = jax.eval_shape(functools.partial(init_params, cfg=cfg), key)
    total = tree_param_count(sds)
    embed = tree_param_count(sds["embed"])
    expert = 0
    if cfg.arch_type == "moe":
        def moe_leaves(t):
            out = 0
            layers = t["layers"]
            mlp = layers["mlp"] if isinstance(layers, dict) else None
            if mlp is not None:
                for k in ("gate", "up", "down"):
                    out += mlp[k].size if hasattr(mlp[k], "size") else 0
            return out
        expert = moe_leaves(sds)
    active = total - expert + (expert * cfg.top_k // max(cfg.n_experts, 1)
                               if expert else 0)
    return {"total": total, "embed": embed, "expert": expert, "active": active}


def analytic_flops(cfg, shape_info: dict, n_chips: int, local_steps: int = 0,
                   window_override: Optional[int] = None) -> Dict[str, float]:
    """MODEL_FLOPS per the task spec + attention extras, whole-program."""
    counts = model_param_counts(cfg)
    N = counts["active"] if cfg.arch_type == "moe" else counts["total"]
    S, B = shape_info["seq"], shape_info["global_batch"]
    kind = shape_info["kind"]
    hd = cfg.head_dim or 0
    Hq = cfg.n_heads
    L_attn = sum(1 for k in cfg.layer_kinds() if k in ("attn", "local_attn"))
    win = window_override if window_override is not None else cfg.window

    if kind == "train":
        tokens = B * S * max(local_steps, 1)
        flops = 6.0 * N * tokens
        kv_span = min(win, S) if win else S
        flops += 3 * 2 * 2 * B * max(local_steps, 1) * Hq * hd * S * kv_span \
            / 2 * L_attn
    elif kind == "prefill":
        tokens = B * S
        flops = 2.0 * N * tokens
        kv_span = min(win, S) if win else S
        flops += 2 * 2 * B * Hq * hd * S * kv_span / 2 * L_attn
    else:  # decode: one token, cache of length S
        tokens = B
        flops = 2.0 * N * tokens
        span = min(win, S) if win else S
        if cfg.arch_type == "hybrid":
            span = min(2048, S)
        flops += 2 * 2 * B * Hq * hd * span * L_attn
    return {"model_flops": flops, "per_chip": flops / n_chips,
            "params": counts}


def analytic_bytes(cfg, shape_info: dict, n_chips: int, model_shards: int,
                   local_steps: int = 0, param_bytes: int = 4) -> float:
    """Dominant HBM traffic per chip: weight traffic (+cache for decode)."""
    counts = model_param_counts(cfg)
    N = counts["total"]
    kind = shape_info["kind"]
    S, B = shape_info["seq"], shape_info["global_batch"]
    w_per_chip = N * param_bytes / model_shards
    if kind == "train":
        # fwd read + bwd read + grad write + update r/w, per local step,
        # x3 resident copies touched at aggregation
        return (4 * w_per_chip * max(local_steps, 1) + 3 * w_per_chip)
    if kind == "prefill":
        act = B * S * cfg.d_model * 2 * max(cfg.n_layers, 1) * 4 / n_chips
        return w_per_chip + act
    # decode
    kv_layers = sum(1 for k in cfg.layer_kinds() if k in ("attn", "local_attn"))
    span = min(cfg.window, S) if cfg.window else S
    if cfg.arch_type == "hybrid":
        span = min(2048, S)
    kv_elt = 1 if cfg.kv_cache_dtype == "int8" else 2
    cache = B * span * cfg.n_kv_heads * ((cfg.head_dim or 0) * kv_elt
                                         + (2 if kv_elt == 1 else 0)) \
        * 2 * kv_layers
    if cfg.arch_type == "ssm":
        cache = B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4 \
            * cfg.n_layers * 2
    return w_per_chip * 2 / param_bytes + cache / n_chips  # bf16 weights read


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    raw_cost_flops: float
    raw_cost_bytes: float
    collective_bytes: float
    dominant: str
    useful_ratio: float

    def row(self) -> str:
        return (f"{self.arch},{self.shape},{self.mesh},{self.compute_s:.3e},"
                f"{self.memory_s:.3e},{self.collective_s:.3e},{self.dominant},"
                f"{self.model_flops:.3e},{self.useful_ratio:.3f}")


def build_report(arch: str, shape_name: str, mesh_name: str, cfg, shape_info,
                 n_chips: int, model_shards: int, cost: dict, coll: dict,
                 local_steps: int = 0, param_bytes: int = 4, *,
                 device_kind: str) -> RooflineReport:
    """Roofline terms on ``device_kind`` chips (see :func:`chip_peaks`)."""
    peaks = chip_peaks(device_kind)
    fl = analytic_flops(cfg, shape_info, n_chips, local_steps)
    by = analytic_bytes(cfg, shape_info, n_chips, model_shards, local_steps,
                        param_bytes)
    # compute term: prefer the trip-adjusted per-device dot FLOPs parsed from
    # the compiled HLO (counts remat recompute!); analytic as floor/fallback.
    hlo_flops_chip = float(coll.get("dot_flops", 0.0) or 0.0)
    compute_s = max(hlo_flops_chip, fl["per_chip"]) / peaks["flops"]
    memory_s = by / peaks["hbm_bw"]
    coll_s = coll["total_bytes"] / peaks["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    raw_flops = float(cost.get("flops", 0.0) or 0.0)
    useful = fl["model_flops"] / max(hlo_flops_chip * n_chips, 1.0)
    return RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, n_chips=n_chips,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        model_flops=fl["model_flops"], raw_cost_flops=raw_flops,
        raw_cost_bytes=float(cost.get("bytes accessed", 0.0) or 0.0),
        collective_bytes=coll["total_bytes"], dominant=dominant,
        useful_ratio=min(useful, 1e6),
    )
