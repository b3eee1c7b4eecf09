#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a ``workloads`` entry of BENCHMARK.json. The run makes its
histories from ``--seed``, warms up, checks them in a closed loop for
``--seconds`` (whole passes over its pool of histories), compares every
answer with the plain reference, and prints one JSON line last on
standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics from a profiled window),
``device`` and, last, ``compared``: each number compared with its limit.
Those numbers are also the last lines on standard error.

A machine where JAX finds no TPU, or fewer chips than the cell asks for,
gets exit code 2 and no result line.

``--control N`` puts the control (the checker module's: for the
registers, the reference with real-time order dropped) in the program's
place for N checks of the cell's histories and compares it like a run;
its ``correct`` must come out false.
"""
import argparse
import json
import sys
import time
from pathlib import Path

_T0 = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, default=0, metavar="N")
    args = ap.parse_args(argv)

    from benchmark import compare, harness
    try:
        out = harness.run(harness.load(), args.workload, args.seed,
                          args.seconds, bool(args.trace),
                          control_checks=args.control, t_start=_T0)
    except harness.NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for line in compare.stderr_lines(out["compared"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
