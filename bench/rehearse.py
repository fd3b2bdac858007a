"""Rehearsals that need no chip.

  python bench/rehearse.py described [--workload <name> ...]
      Compile each cell's superstep — the program the window drives — at
      its real size for device 0 of a described v5e:2x2 and print the
      compiler's ``memory_analysis()``. Nothing runs: this says whether the
      program fits and whether the chip's compiler takes it, not how fast
      it is. Run it with JAX_PLATFORMS=cpu.

  python bench/rehearse.py cpu [--seconds 2]
      Run the harness end to end on the CPU over a tiny copy of the
      benchmark (``lib/tiny.py``): toy widths, the same code path. The
      command ``bench/run.py`` itself still refuses a CPU."""
import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

from lib import cell as cell_mod


def described(workloads) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = json.loads((cell_mod.BENCH.parent / "BENCHMARK.json").read_text())
    workloads = workloads or [w["name"] for w in spec["workloads"]]
    from lib import harness, replay
    ctx = harness.prepare(workloads[0], allow_cpu=True)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.data.device_corpus import DeviceCorpus
    from repro.kernels import ops
    # the program picks its Pallas kernels when it sees a TPU backend; the
    # described chip is not one, so steer that choice here
    ops._is_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    out = {}
    for w in workloads:
        ctx = harness.prepare(w, allow_cpu=True)
        tr = ctx.cell.traffic
        shapes = jax.eval_shape(ctx.ref.weights,
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
        engine = harness.make_engine(ctx, shapes)
        state = jax.eval_shape(engine.init_state, shapes,
                               replay.state_key(0))

        def on_chip(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
        state = jax.tree_util.tree_map(on_chip, state)
        n = tr["n_clients"]
        i32 = jnp.int32
        corpus = DeviceCorpus(
            kind="lm", batch=tr["batch"], seq=tr["seq"],
            tokens=on_chip(jax.ShapeDtypeStruct((tr["corpus_tokens"],), i32)),
            lo=on_chip(jax.ShapeDtypeStruct((n,), i32)),
            span=on_chip(jax.ShapeDtypeStruct((n,), i32)))
        t = time.perf_counter()
        compiled = engine._multi_device.lower(
            state, corpus=corpus, n_rounds=tr["rounds_per_chunk"]).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        row = {k: getattr(ma, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
        row["peak_estimate_bytes"] = (row["argument_size_in_bytes"]
                                      + row["output_size_in_bytes"]
                                      - row["alias_size_in_bytes"]
                                      + row["temp_size_in_bytes"])
        row["pallas_kernel"] = "tpu_custom_call" in text
        row["compile_s"] = time.perf_counter() - t
        out[w] = row
        print(w, json.dumps(row), flush=True)
    return out


def cpu(seconds: float) -> None:
    from lib import harness, tiny
    with tempfile.TemporaryDirectory() as d:
        bench = tiny.make(Path(d), limits=tiny.LIMITS)
        for w in tiny.CELLS:
            r = harness.run(w, 2 ** 31 + 11, seconds, False,
                            t_start=time.perf_counter(), bench=bench,
                            allow_cpu=True)
            # a CPU run's times are not device numbers: show the check only
            print(w, json.dumps({k: r[k] for k in (
                "correct", "attempted", "failed", "window_compiles",
                "compared")}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("described", "cpu"))
    ap.add_argument("--workload", action="append", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    if args.mode == "described":
        described(args.workload)
    else:
        cpu(args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
