"""A tiny copy of the benchmark for rehearsals off the chip.

``make(dst)`` copies ``bench/`` under ``dst`` and writes beside it a
``BENCHMARK.json`` whose cells run the Mamba2 configuration's plain
reference and the program at toy widths (CPU-sized), under two tiny
traffic mixes: fan-in (K 1) and few clients with long local steps (K 4).
The harness then runs them end to end with ``allow_cpu=True``; the
command ``bench/run.py`` itself still refuses a CPU. Nothing measured on
such a copy is a device number."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from lib.cell import BENCH

CONFIGS = {
    "tiny-mamba2": {
        "reference": "mamba2-1.3b.ref.py", "d_model": 64, "n_layer": 1,
        "vocab_size": 300, "d_state": 16, "d_conv": 4, "expand": 2,
        "headdim": 16, "ngroups": 1, "layer_norm_epsilon": 1e-5,
        "gated_norm_epsilon": 1e-6,
        "table_rows": 512,
        "program": {"arch": "mamba2-1.3b", "overrides": {
            "n_layers": 1, "d_model": 64, "ssm_state": 16,
            "ssm_head_dim": 16, "ssm_expand": 2, "vocab_size_raw": 300,
            "norm_eps": 1e-5}}},
}
TRAFFIC = {
    "tiny-silo": {"n_clients": 2, "s": 1, "K": 4, "batch": 1, "seq": 64,
                  "rounds_per_chunk": 2},
    "tiny-fanin": {"n_clients": 8, "s": 2, "K": 1, "batch": 1, "seq": 64,
                   "rounds_per_chunk": 4},
}
COMMON = {"eta": 0.05, "slow_fraction": 1 / 3, "lam_fast": 0.0625,
          "lam_slow": 0.5, "reweight": "stochastic", "quant_bits": 0,
          "residency": "dense", "data_plane": "device",
          "corpus_tokens": 20000}
# Limits for the toy cells, set as the real cells' are (above the
# program's largest reading over seeds, below the fp8 control's smallest),
# from CPU readings at these widths: program up to 1e-4 / 3.3e-3 / 3.7e-3,
# control from 6.9e-4 / 2.4e-2 / 2.6e-2 (loss / first_change / change_3).
LIMITS = {"loss": 3e-4, "first_change": 1e-2, "change_3": 1.2e-2}
CELLS = {"tiny-mamba2.tiny-silo": ("tiny-mamba2", "tiny-silo"),
         "tiny-mamba2.tiny-fanin": ("tiny-mamba2", "tiny-fanin")}


def make(dst: Path, limits: dict | None = None) -> Path:
    """Write the tiny copy under ``dst``; returns its ``bench`` directory.
    ``limits``: the numbers' limits given to every tiny cell."""
    dst = Path(dst)
    bench = dst / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for name, cfg in CONFIGS.items():
        (bench / "configs" / f"{name}.json").write_text(
            json.dumps(dict(cfg, name=name)))
    for name, tr in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(COMMON, **tr, name=name)))
    for cell in CELLS:
        (bench / "cells" / f"{cell}.json").write_text(
            json.dumps({"limits": limits or {}}))
    spec["configs"] = [{"name": n, "source": "toy widths",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "rehearsal"} for n in CONFIGS]
    spec["workloads"] = [{"name": c, "config": a, "traffic": b, "chips": 1,
                          "why": "rehearsal"} for c, (a, b) in CELLS.items()]
    for m in spec["per_layer"]:
        m["workloads"] = list(CELLS)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    src = BENCH.parent / "src"
    if not (dst / "src").exists():
        (dst / "src").symlink_to(src)
    return bench
