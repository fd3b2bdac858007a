"""The benchmark's one command.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on (see
``lib/harness.py``) and prints one JSON object as the last line of
standard output: ``correct``, ``attempted`` and ``failed`` (rounds),
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``compared``: each number of the check beside its limit. The same
numbers are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# the TPU runtime would otherwise write its logs to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dump", default=None,
                    help="with --trace 1: write the trace's summary and an "
                         "excerpt of it to this JSON file")
    args = ap.parse_args(argv)
    from lib import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             trace_dump=args.trace_dump)
    except harness.DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
