# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness (deliverable d): one entry per paper table/figure plus
the roofline/kernel harnesses. ``--full`` runs paper-scale FL simulations
(slow); the default quick mode keeps CPU CI in minutes.

  PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--only NAME]

``--smoke`` asks each benchmark that supports it (data_plane_bench,
paged_state_bench, streaming_bench, quant_fused_bench, async_server_bench,
recovery_bench) for its cheapest defensible check;
smoke artifacts go
to ``*_smoke.json`` and never overwrite the canonical files. Benchmarks
without a smoke path just run their quick mode.
"""
from __future__ import annotations

import argparse
import time
import traceback


# benchmarks re-run on the accelerator tier (``--tier device``): the
# kernel-facing subset whose numbers change with a real backend
DEVICE_TIER = {"kernel_bench", "round_loop_bench", "paged_state_bench",
               "streaming_bench", "roofline_table"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--tier", default="host", choices=["host", "device"],
                    help="host (default): the CPU-oracle suite. device: "
                         "re-run the kernel-facing benchmarks on the TPU; "
                         "fails when JAX finds no TPU")
    args, _ = ap.parse_known_args()
    quick = not args.full
    smoke = args.smoke

    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.tier == "device":
        import jax
        backend = jax.default_backend()
        if backend != "tpu":
            raise SystemExit(f"tier=device needs a TPU; JAX found "
                             f"{backend!r}")

    from benchmarks import (fl_paper, theory_table, kernel_bench,
                            roofline_table, ablation_reweight,
                            round_loop_bench, data_plane_bench,
                            paged_state_bench, quant_fused_bench,
                            async_server_bench, recovery_bench,
                            streaming_bench)

    suite = [
        ("table1_theory", lambda: theory_table.run(quick)),
        ("kernel_bench", lambda: kernel_bench.run(quick)),
        ("round_loop_bench", lambda: round_loop_bench.run(quick)),
        ("data_plane_bench", lambda: data_plane_bench.run(quick,
                                                          smoke=smoke)),
        ("paged_state_bench", lambda: paged_state_bench.run(quick,
                                                            smoke=smoke)),
        ("streaming_bench", lambda: streaming_bench.run(quick, smoke=smoke)),
        ("quant_fused_bench", lambda: quant_fused_bench.run(quick,
                                                            smoke=smoke)),
        ("async_server_bench", lambda: async_server_bench.run(quick,
                                                              smoke=smoke)),
        ("recovery_bench", lambda: recovery_bench.run(quick, smoke=smoke)),
        ("roofline_table", lambda: roofline_table.run(quick)),
        ("fig1_table2_mnist", lambda: fl_paper.fig1_table2(quick)),
        ("fig2_stragglers_1of9fast", lambda: fl_paper.fig2_stragglers(quick)),
        ("fig3a_cifar", lambda: fl_paper.fig3a_cifar(quick)),
        ("fig3b_tinyimagenet_proxy", lambda: fl_paper.fig3b_tiny(quick)),
        ("fig7_quant_luq", lambda: fl_paper.fig7_quant(quick)),
        ("ablation_reweight", lambda: ablation_reweight.run(quick)),
    ]
    if args.tier == "device":
        suite = [(n, f) for n, f in suite if n in DEVICE_TIER]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suite:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            out = fn()
            us = (time.perf_counter() - t0) * 1e6
            derived = _derive(name, out)
            print(f"{name},{us:.0f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"{name},NA,ERROR:{type(e).__name__}")
    raise SystemExit(1 if failures else 0)


def _derive(name: str, out) -> str:
    """A one-cell human-meaningful summary per benchmark."""
    try:
        if name.startswith("table1"):
            t = out["table1"]
            best = min(t, key=t.get)
            return f"best_bound={best}"
        if name == "kernel_bench":
            return (f"round_fused={out['favas_round_fused_jnp_us']:.0f}us"
                    f";unfused={out['favas_round_unfused_jnp_us']:.0f}us")
        if name == "round_loop_bench":
            o = out["cpu_oracle"]
            s32 = o["superstep"].get("32", {})
            return (f"host={o['host_loop']['rounds_per_sec']:.0f}r/s"
                    f";superstep32={s32.get('rounds_per_sec', 0):.0f}r/s"
                    f";x{s32.get('speedup_vs_host_loop', 0):.2f}")
        if name == "data_plane_bench":
            rows32 = [r for r in out["chunk_sweep_n64"] if r["chunk"] == 32]
            r = rows32[0]
            return (f"host={r['host_v1']['rounds_per_sec']:.0f}r/s"
                    f";device={r['device']['rounds_per_sec']:.0f}r/s"
                    f";x{r['device']['speedup_vs_host_v1']:.2f}")
        if name == "paged_state_bench":
            if "ratio" in out:                       # --smoke shape
                return f"smoke_bytes_ratio=x{out['ratio']:.2f}"
            pop = out["max_population_at_fixed_memory"]
            t = out["throughput_n1024_chunk32"]
            return (f"pop=x{pop['population_ratio_paged_vs_dense']:.1f}"
                    f";rps=x{t['paged_over_dense']:.2f}")
        if name == "streaming_bench":
            if "host_over_device" in out:            # --smoke shape
                return f"smoke_host_rps=x{out['host_over_device']:.2f}"
            pop = out["max_population_at_fixed_device_memory"]
            t = out["throughput_n1024_chunk32"]
            return (f"pop=x{pop['population_ratio_host_vs_device']:.0f}"
                    f";rps=x{t['host_over_device']:.2f}")
        if name == "quant_fused_bench":
            r32 = out["sweep"][-1]
            return (f"n{r32['n_clients']}_fused="
                    f"{r32['fused']['rounds_per_sec']:.0f}r/s"
                    f";x{r32['fused_over_unfused']:.2f}"
                    f";bytes_x{r32['progress_bytes_ratio']:.1f}")
        if name == "ablation_reweight":
            return ";".join(
                f"{k}={v['final_mean']:.3f}/rec{v['slow_class_recall']:.3f}"
                for k, v in out.items())
        if name == "async_server_bench":
            return (f"real={out['real']['rounds_per_sec']:.1f}r/s"
                    f";sim={out['simulated']['rounds_per_sec']:.1f}r/s"
                    f";sel_eq={out['selection_identical']}"
                    f";clean={out['clean']}")
        if name == "recovery_bench":
            ov = out["overhead"]
            rec = out["recovery_vs_length"][-1]
            return (f"wal_overhead={ov['overhead_frac'] * 100:.1f}%"
                    f";bit_exact={ov['bit_exact']}"
                    f";recover_{rec['rounds']}r="
                    f"{rec['recovery_s'] * 1e3:.0f}ms")
        if name == "roofline_table":
            ok = sum(1 for r in out if r["status"] == "ok")
            sk = sum(1 for r in out if r["status"] == "skipped")
            return f"ok={ok};skipped={sk}"
        if name.startswith("fig7"):
            fp = out.get("favas_bits32", {}).get("final_mean")
            q4 = out.get("favas_bits4", {}).get("final_mean")
            return f"fp32={fp:.3f};luq4={q4:.3f}"
        finals = {m: r["final_mean"] for m, r in out.items()}
        order = sorted(finals, key=finals.get, reverse=True)
        return ";".join(f"{m}={finals[m]:.3f}" for m in order)
    except Exception:  # noqa: BLE001
        return "ok"


if __name__ == '__main__':
    main()
