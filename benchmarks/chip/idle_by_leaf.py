#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, that also
says where the device's idle time went:

  python3 benchmarks/chip/idle_by_leaf.py --workload <name> --seed <n> \\
      --seconds <s>

Besides the run's own output it prints to standard error one line,
``idle_by_leaf {...}``: ``progspans.idle_by_leaf`` of the window (idle
seconds by the innermost program span, by request span outside every
program span, and outside requests), with the window's seconds and its
requests' number and summed traced seconds. It is a reading for
``PERF.md``, not a metric.
"""
import json
import sys

import run as bench             # sets the paths and the set-up clock

import progspans
import tracereduce

_load = tracereduce.load


def load(path: str, device_ids):
    red = _load(path, device_ids)
    reqs = [e - s for _, s, e in red.spans]
    print("idle_by_leaf " + json.dumps({
        "idle_by_leaf": progspans.idle_by_leaf(red),
        "window_s": tracereduce.window_s(red),
        "requests": len(reqs), "request_s": sum(reqs) / 1e9}),
        file=sys.stderr, flush=True)
    return red


if __name__ == "__main__":
    tracereduce.load = load
    sys.exit(bench.main(sys.argv[1:] + ["--trace", "1"]))
