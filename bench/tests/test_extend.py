"""Files alone extend the benchmark: a dummy configuration, traffic mix,
per-layer metric and cell dropped into their directories are found by
name and run through the harness, with no existing file edited."""
import hashlib
import json
import shutil
import time
from pathlib import Path

from lib import harness, tiny
from lib.cell import BENCH


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_dummy_files_are_found_by_name(tmp_path):
    bench = tiny.make(tmp_path, limits=tiny.LIMITS)
    before = _digest(bench)
    cfg = json.loads((bench / "configs" / "tiny-mamba2.json").read_text())
    cfg.update(name="dummy-model", d_state=8)
    cfg["program"]["overrides"]["ssm_state"] = 8
    (bench / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "tiny-fanin.json").read_text())
    tr.update(name="dummy-mix", n_clients=4, s=1, K=2)
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(tr))
    (bench / "metrics" / "dummy_chunks.py").write_text(
        "def read(facts):\n    return float(facts.chunks)\n")
    (bench / "cells" / "dummy-model.dummy-mix.json").write_text(
        json.dumps({"limits": tiny.LIMITS}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy-model", "source": "toy",
                            "file": "bench/configs/dummy-model.json",
                            "reduced": [], "why": "extension test"})
    spec["workloads"].append({"name": "dummy-model.dummy-mix",
                              "config": "dummy-model", "traffic": "dummy-mix",
                              "chips": 1, "why": "extension test"})
    spec["per_layer"].append({"name": "dummy_chunks", "unit": "chunks",
                              "better": "higher", "source": "host_clock",
                              "layer": "trainer loop", "moves": "rounds_per_s",
                              "workloads": ["dummy-model.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    r = harness.run("dummy-model.dummy-mix", 123, 0.5, True,
                    t_start=time.perf_counter(), bench=bench, allow_cpu=True)
    assert r["correct"], r["compared"]
    assert r["metrics"]["dummy_chunks"]["value"] >= 1
    assert "device_idle_share" not in r["metrics"]   # not listed for it
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    shutil.rmtree(tmp_path / "bench")
