"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes
benchmarks/results.json (consumed by EXPERIMENTS.md).

  PYTHONPATH=src python -m benchmarks.run [--only fig11]

``--check-baselines`` instead validates every ``benchmarks/BENCH_*.json``
regression baseline against the shared schema (``common.py``: ``_meta``
stamp with schema version, owning benchmark, metric, direction,
tolerance, regeneration command; positive finite row values) and exits
non-zero on any drift — scripts/ci.sh runs it before the gated smokes so
a mangled baseline fails fast instead of silently gating nothing. With
``--drift-ref`` (or ``$CI_BASE_REF``) it additionally compares each
baseline against that git revision: row values that changed without a
fresh ``_meta.generated_at``/``regenerate`` stamp mean someone nudged a
gate by hand instead of regenerating through ``--write-baseline``.

``--trace=FILE`` installs a process-wide default tracer (DESIGN.md §9)
before any benchmark runs: every cluster built without an explicit
``trace=`` argument attaches to it, and on exit the combined trace is
written to FILE as Perfetto ``trace_event`` JSON (schema-validated,
loadable at https://ui.perfetto.dev; a ``.gz`` suffix gzips it). Pair
with ``--only`` — a full sweep's trace is huge.

``--blame`` prints the causal critical-path attribution table for the
combined trace (core/critpath.py), and ``--whatif=nic_bandwidth=2``
projects the makespan under hypothetical substrate changes — both
install a default tracer themselves, so ``--trace`` is optional.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MODULES = [
    ("fig8", "benchmarks.cmd_overhead"),
    ("dispatch", "benchmarks.dispatch_throughput"),
    ("fig9", "benchmarks.passthrough"),
    ("fig10", "benchmarks.migration_latency"),
    ("migpipe", "benchmarks.migration_pipeline"),
    ("mt", "benchmarks.multi_tenant"),
    ("slo", "benchmarks.slo_burst"),
    ("cfdhalo", "benchmarks.cfd_halo"),
    ("chaos", "benchmarks.chaos"),
    ("fleet", "benchmarks.fleet_sweep"),
    ("breakdown", "benchmarks.latency_breakdown"),
    ("fig11", "benchmarks.rdma_vs_tcp"),
    ("fig12", "benchmarks.matmul_scaling"),
    ("fig13", "benchmarks.rdma_matmul"),
    ("fig15", "benchmarks.ar_pipeline"),
    ("fig16", "benchmarks.cfd_scaling"),
]


def _baseline_rows(data: dict) -> dict:
    if "_meta" in data:
        return data.get("rows", {})
    return {k: v for k, v in data.items() if k != "_meta"}


def _drift_errors(path: str, ref: str) -> list:
    """Baseline-drift guard: against ``ref``'s copy of the file, changed
    row values must arrive with a fresh ``_meta.generated_at`` (or
    ``regenerate``) stamp — i.e. through the owning module's
    ``--write-baseline``, not a hand edit that quietly moves the CI
    gate. Silently passes when git, the ref, or the old copy is
    unavailable (fresh baselines are always fine)."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(path)))
    rel = os.path.relpath(os.path.abspath(path), root)
    try:
        old = subprocess.run(
            ["git", "show", f"{ref}:{rel}"], cwd=root, timeout=30,
            capture_output=True, text=True)
    except (OSError, subprocess.TimeoutExpired):
        return []
    if old.returncode != 0:
        return []                   # new file, or ref not fetched
    try:
        with open(path) as f:
            new_data = json.load(f)
        old_data = json.loads(old.stdout)
    except ValueError:
        return []                   # schema validation reports this
    if not isinstance(new_data, dict) or not isinstance(old_data, dict):
        return []
    if _baseline_rows(new_data) == _baseline_rows(old_data):
        return []
    new_meta = new_data.get("_meta") or {}
    old_meta = old_data.get("_meta") or {}
    if (new_meta.get("generated_at") == old_meta.get("generated_at")
            and new_meta.get("regenerate") == old_meta.get("regenerate")):
        return [f"row values differ from {ref} but the "
                f"_meta.generated_at/regenerate stamp does not — "
                f"hand-edited baseline? regenerate with: "
                f"{new_meta.get('regenerate', '--write-baseline')}"]
    return []


def check_baselines(drift_ref=None) -> int:
    """Validate every BENCH_*.json against the shared baseline schema
    (plus, given a git ref, the stamp-drift guard); returns the number
    of invalid files (0 = all good)."""
    import glob

    from benchmarks import common

    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "BENCH_*.json")))
    if not paths:
        print("no BENCH_*.json baselines found", file=sys.stderr)
        return 1
    bad = 0
    for path in paths:
        errs = common.validate_baseline(path)
        if drift_ref:
            errs = errs + _drift_errors(path, drift_ref)
        rel = os.path.relpath(path)
        if errs:
            bad += 1
            for e in errs:
                print(f"# {rel}: {e}", file=sys.stderr)
            print(f"# {rel}: INVALID", file=sys.stderr)
        else:
            print(f"# {rel}: ok", file=sys.stderr)
    return bad


def _parse_whatif(spec: str) -> dict:
    """Parse ``--whatif`` knob=value pairs (``nic_bandwidth=2,wire=0``)."""
    valid = {"nic_bandwidth": float, "device_speed": float,
             "wire": float, "overlap_halo": lambda v: v.lower() in
             ("1", "true", "yes", "on")}
    knobs: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        key = key.strip()
        if key not in valid:
            raise SystemExit(f"--whatif: unknown knob {key!r} "
                             f"(choose from {sorted(valid)})")
        try:
            knobs[key] = valid[key](val.strip())
        except ValueError:
            raise SystemExit(f"--whatif: bad value for {key}: {val!r}")
    if not knobs:
        raise SystemExit("--whatif: empty spec")
    return knobs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--check-baselines", action="store_true",
                    help="validate benchmarks/BENCH_*.json against the "
                         "shared schema and exit")
    ap.add_argument("--drift-ref", default=os.environ.get("CI_BASE_REF"),
                    metavar="GITREF",
                    help="with --check-baselines: also fail baselines "
                         "whose row values changed vs this git ref "
                         "without a fresh _meta.generated_at stamp "
                         "(default: $CI_BASE_REF)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile each selected benchmark and print the "
                         "top 25 functions by cumulative time to stderr "
                         "(pair with --only to profile one)")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="also append each profile's top-25 table to this "
                         "file (implies --profile)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="trace every benchmark cluster and write combined "
                         "Perfetto trace_event JSON to FILE on exit "
                         "(.gz suffix gzips the export)")
    ap.add_argument("--blame", action="store_true",
                    help="after the run, print the causal critical-path "
                         "blame table (core/critpath.py) for the combined "
                         "trace — installs a tracer even without --trace")
    ap.add_argument("--whatif", default=None, metavar="SPEC",
                    help="after the run, print what-if makespan projections "
                         "for the combined trace; SPEC is comma-separated "
                         "knob=value (nic_bandwidth=2, device_speed=2, "
                         "wire=0, overlap_halo=1) — implies --blame's "
                         "tracer")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "results.json"))
    args = ap.parse_args()
    if args.check_baselines:
        sys.exit(1 if check_baselines(args.drift_ref) else 0)
    if args.profile_out:
        args.profile = True
    from repro.utils import enable_compile_cache
    enable_compile_cache()

    whatif_knobs = None
    if args.whatif is not None:
        whatif_knobs = _parse_whatif(args.whatif)

    tracer = None
    if args.trace or args.blame or whatif_knobs is not None:
        from repro.core import trace as trace_mod
        tracer = trace_mod.Tracer()
        trace_mod.set_default(tracer)

    import importlib
    all_rows = []
    prof_f = open(args.profile_out, "w") if args.profile_out else None
    try:
        print("name,us_per_call,derived")
        for tag, modname in MODULES:
            if args.only and args.only != tag:
                continue
            t0 = time.time()
            mod = importlib.import_module(modname)
            if args.profile:
                import cProfile
                import pstats
                prof = cProfile.Profile()
                rows = prof.runcall(mod.run)
                header = (f"# profile: {tag} ({modname}) "
                          "top 25 by cumulative")
                for stream in (sys.stderr, prof_f):
                    if stream is None:
                        continue
                    print(header, file=stream)
                    pstats.Stats(prof, stream=stream) \
                        .sort_stats("cumulative").print_stats(25)
            else:
                rows = mod.run()
            all_rows.extend({"name": r.name, "us_per_call": r.us_per_call,
                             "derived": r.derived} for r in rows)
            print(f"# {tag} done in {time.time()-t0:.1f}s",
                  file=sys.stderr)
    finally:
        if prof_f is not None:
            prof_f.close()
        if tracer is not None:
            from repro.core import trace as trace_mod
            trace_mod.set_default(None)
            if args.trace:
                from benchmarks import common
                tracer.write_perfetto(args.trace)
                errs = common.validate_perfetto(args.trace)
                for e in errs:
                    print(f"# trace: {e}", file=sys.stderr)
                print(f"# trace: {len(tracer.cmds)} commands -> "
                      f"{args.trace} "
                      f"({'INVALID' if errs else 'schema ok'})",
                      file=sys.stderr)
            if args.blame or whatif_knobs is not None:
                title = f"--only {args.only}" if args.only else "full sweep"
                print(tracer.format_blame(title=title), file=sys.stderr)
            if whatif_knobs is not None:
                w = tracer.whatif(**whatif_knobs)
                print(f"# whatif {args.whatif}: recorded "
                      f"{w['recorded_s'] * 1e3:.3f} ms -> projected "
                      f"{w['projected_s'] * 1e3:.3f} ms "
                      f"(speedup {w['speedup']:.3f}x)", file=sys.stderr)
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=1)


if __name__ == "__main__":
    main()
