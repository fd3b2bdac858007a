"""Readings that set a cell's limits, on the chip, in one process.

  python bench/control.py --workload <name> --seeds 1,2,... \
      --control-seeds 7,8,9 [--witness key=value,...] \
      [--out control_<name>.json]

For every seed of ``--seeds`` the program runs the chunks the check
follows, exactly as a benchmark run's set-up does (one engine serves every
seed), and the plain float32 reference follows them: these are the sound
runs whose largest numbers give each limit's lower reading. For every seed
of ``--control-seeds`` the reference is also put in the program's place
(``lib/check.py`` compares it with the float32 reference) computed in
scaled fp8 — the control, whose smallest numbers give the upper reading —
and with each fault a one-chip training cell can have planted in it
(``lib/reference.py``: ``half_batch``, ``agg_div_s``; ``unchanged`` reads
1 on both changes by construction and is read once to show it).
``--witness`` runs a second reference beside the first for every seed of
``--seeds``, with those keys of the configuration changed, and compares
the program with it too (``program_witness``).

Every reading is judged against the cell's limits (``cells/<name>.json``)
as a run judges it, and the verdicts are kept (``verdicts``): the command
exits 1 when a program seed comes out not correct or a control or a fault
comes out correct. The benchmark's own runs never run this."""
import argparse
import json
import os
import sys
import time
from pathlib import Path

from lib import check, harness, reference

CONTROLS = ("fp8", "half_batch", "agg_div_s", "unchanged")

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(workload: str, seeds, control_seeds, *, witness=None,
             allow_cpu=False, log=sys.stderr) -> dict:
    ctx = harness.prepare(workload, allow_cpu=allow_cpu, log=log)
    out = {"workload": workload, "device": ctx.device.device_kind,
           "limits": ctx.cell.limits, "program": {}, "fp8": {},
           "half_batch": {}, "agg_div_s": {}, "unchanged": {}}
    wit_ref = None
    if witness:
        wit_ref = reference.Reference(ctx.ref.module,
                                  dict(ctx.ref.hf, **witness), ctx.ref.traffic)
        out["witness"], out["program_witness"] = witness, {}
    engine = None
    for seed in seeds:
        t = time.perf_counter()
        inp = harness.make_inputs(ctx, seed)
        if engine is None:
            engine = harness.make_engine(ctx, inp.weights)
        state, prog = harness.checked_chunks(ctx, engine, inp)
        del state
        inp.corpus = None
        ref, w0, names = harness.reference_run(ctx, inp)
        out["program"][seed] = dict(_numbers(prog, ref, w0, names),
                                    losses=prog["losses"],
                                    ref_losses=ref["losses"],
                                    live=ref["live"])
        print(f"seed {seed}: program {out['program'][seed]} "
              f"({time.perf_counter() - t:.1f} s)", file=log)
        if wit_ref is not None:
            wit, _, _ = harness.reference_run(ctx, inp, ref=wit_ref)
            out["program_witness"][seed] = _numbers(prog, wit, w0, names)
            print(f"seed {seed}: program vs witness "
                  f"{out['program_witness'][seed]}", file=log)
        if seed not in control_seeds:
            continue
        for name, kw in (("fp8", {"mode": "fp8"}),
                         ("half_batch", {"fault": "half_batch"}),
                         ("agg_div_s", {"fault": "agg_div_s"}),
                         ("unchanged", {"fault": "unchanged"})):
            if name == "unchanged" and out["unchanged"]:
                continue
            alt, _, _ = harness.reference_run(ctx, inp, **kw)
            out[name][seed] = _numbers(alt, ref, w0, names)
            print(f"seed {seed}: {name} {out[name][seed]}", file=log)
    out["verdicts"] = verdicts(out, ctx.cell.limits)
    return out


def verdicts(out: dict, limits: dict) -> dict:
    """``correct`` of every reading as a run would judge it."""
    return {name: {seed: check.judge(nums, limits)[0]
                   for seed, nums in out[name].items()}
            for name in ("program", "program_witness") + CONTROLS
            if name in out}


def misjudged(verdict: dict) -> list:
    """Readings that come out the wrong way: a program seed not correct,
    or a control or a fault correct."""
    bad = [("program", s) for s, ok in verdict["program"].items() if not ok]
    return bad + [(name, s) for name in CONTROLS
                  for s, ok in verdict.get(name, {}).items() if ok]


def _numbers(prog, ref, w0, names) -> dict:
    got = check.compare(prog, ref, w0, names)
    return {k: got[k] for k in check.NUMBERS + ("first_change_leaf",
                                                "change_3_leaf")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness", default="",
                    help="key=value,...: configuration keys of a second "
                         "reference (numbers)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctl = {int(x) for x in args.control_seeds.split(",") if x}
    witness = {k: float(v) for k, v in
               (kv.split("=") for kv in args.witness.split(",") if kv)}
    try:
        out = readings(args.workload, seeds, ctl, witness=witness)
    except harness.DeviceError as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    text = json.dumps(out, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    bad = misjudged(out["verdicts"])
    for name, seed in bad:
        print(f"control: {name} on seed {seed} comes out "
              f"{'not ' if name == 'program' else ''}correct", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
