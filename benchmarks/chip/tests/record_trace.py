#!/usr/bin/env python3
"""Record the small chip traces that ``test_bench_tracereduce.py`` reads.

Each is the profiler's trace of a short closed-loop window of one entry at
a small size, with the benchmark's own spans, taken on a TPU:

  python3 benchmarks/chip/tests/record_trace.py <out_dir>

writes ``<out_dir>/cfd.xplane.pb`` (CFD jobs of two steps on a 256x512
lattice, two servers on one chip, for 0.05 s) and
``<out_dir>/passthrough.xplane.pb`` (pass-through chains for 0.01 s), and
prints each trace's planes and lines.
"""
import copy
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                   "src")]

import harness  # noqa: E402


def summary(path: str):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            print(f"  line {ln.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} {dict(ev.stats)}")


def record(cell, config: dict, traffic: dict, seconds: float, out: str):
    import jax
    sut = cell.entry.Deployment(config, traffic, 7, jax.devices()[:1])
    tmp = tempfile.mkdtemp(prefix="chipbench_record_")
    try:
        win = harness.closed_loop(sut.serve, seconds, sut.span, tmp)
        shutil.copy(win.trace_file, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {len(win.work)} requests, {win.seconds:.6f} s, "
          f"work {sum(win.work)}")
    summary(out)


def main():
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    harness.enable_compile_cache()
    spec = harness.load_json(harness.spec_path())
    cfd = harness.load_cell(spec, "cfd_d2q9_8k_2srv.ckpt5")
    cfg = copy.deepcopy(cfd.config)
    cfg["lattice"].update(height=256, width=512)
    record(cfd, cfg, dict(cfd.traffic, steps_per_job=2), 0.05,
           os.path.join(out, "cfd.xplane.pb"))
    pt = harness.load_cell(spec, "passthrough_int32.chain")
    record(pt, pt.config, dict(pt.traffic, warmup_chains=5), 0.01,
           os.path.join(out, "passthrough.xplane.pb"))


if __name__ == "__main__":
    main()
