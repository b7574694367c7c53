#!/usr/bin/env python3
"""On-chip benchmark of the PoCL-R offloading runtime: one run of one
cell of ``BENCHMARK.json``.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

Set-up builds the cell's deployment from the seed and warms it up; then a
closed loop drives it for ``--seconds``; then what the window produced is
compared with the plain reference. The last line of standard output is
the result as JSON; the last lines of standard error are the numbers
compared, each with its limit. ``--trace 1`` records the window with the
profiler and reports the per-layer metrics in place of the end-to-end
ones. Without a TPU, or with fewer chips than the cell asks for, it
exits 1 and prints no result.

``--control 1`` puts the cell's control in the program's place (its
entry's ``control()``: for the CFD deployment the reference's step in
bfloat16 in place of ``lbm_step``, for the pass-through deployment a copy
made through float32), and its check has to come out not correct. The
benchmark's own runs never pass it; the upper reading of each limit in
``PERF.md`` comes from such runs on the chip, at the cell's size.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402

# the TPU runtime logs to a fixed /tmp/tpu_logs unless told otherwise
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = os.path.join(tempfile.gettempdir(),
                                             "chipbench_tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(harness.load_json(harness.spec_path()),
                             args.workload)
    with cell.entry.control() if args.control else nullcontext():
        return harness.run(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)


if __name__ == "__main__":
    sys.exit(main())
