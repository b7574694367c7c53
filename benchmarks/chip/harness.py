"""The benchmark's shared core: find a cell's files by name, set it up,
drive its closed loop for the measured window, read its metrics and
decide ``correct``.

Everything that belongs to one item lives in a file of its own, found by
the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: a deployment (layout, sizes, links, the
  guarantee its check holds the program to, and ``entry``);
- ``entries/<entry>.py``: the code that sets a deployment up, serves one
  request through the program's entry points, and compares what the
  window produced with the plain reference;
- ``traffic/<traffic>.json``: a traffic mix, parameters only;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``, which
  returns a number or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by its path, under a name of its own."""
    name = "chipbench_" + os.path.relpath(path, HERE)[:-3].replace(
        os.sep, "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def spec_path() -> str:
    return os.path.join(ROOT, "BENCHMARK.json")


def config_file(name: str) -> str:
    return os.path.join(HERE, "configs", f"{name}.json")


def traffic_file(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def entry_file(name: str) -> str:
    return os.path.join(HERE, "entries", f"{name}.py")


def metric_file(name: str) -> str:
    return os.path.join(HERE, "metrics", f"{name}.py")


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: object               # the entries/<entry>.py module
    end_to_end: list            # metric entries that this cell reports
    per_layer: list


def reports(metric: dict, workload: str) -> bool:
    """Whether ``workload`` reports ``metric``: the metric lists it, or
    lists no cells at all."""
    return workload in metric.get("workloads", [workload])


def load_cell(spec: dict, workload: str) -> Cell:
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    config = load_json(config_file(w["config"]))
    e2e = [m for m in spec["end_to_end"] if reports(m, workload)]
    per_layer = [m for m in spec["per_layer"] if reports(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=load_json(traffic_file(w["traffic"])),
                entry=load_module(entry_file(config["entry"])),
                end_to_end=e2e, per_layer=per_layer)


# ---------------- compile cache ----------------

def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, or else at
    the fixed ``<checkout>/.jax_cache``: the path is part of the key, so it
    never moves. Every program is kept, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------- the measured window ----------------

@dataclasses.dataclass
class Window:
    t0: float                   # perf_counter at the window's start
    t1: float                   # ... at the end of its last request
    starts: list                # per request: perf_counter at its start
    ends: list
    work: list                  # per request: units of work (entry's own)
    trace_file: str | None = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def closed_loop(serve, seconds: float, span: str,
                trace_dir: str | None = None) -> Window:
    """The general generator: a closed loop with one request in flight.
    Each request is ``serve()``, which returns its units of work once its
    result is on the client. The window is made of whole requests:
    it ends with the first request that ends ``seconds`` or more after
    the start. With ``trace_dir`` the profiler records the window, and
    every request is a ``TraceAnnotation`` named ``span`` inside one
    named ``bench.window``."""
    import jax
    starts, ends, work = [], [], []
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # spans, not every Python call
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
        outer = annotate("bench.window")
    else:
        annotate = None
        outer = nullcontext()
    clock = time.perf_counter
    with outer:
        t0 = clock()
        t = t0
        while t - t0 < seconds:
            s = clock()
            if annotate is None:
                n = serve()
            else:
                with annotate(span):
                    n = serve()
            t = clock()
            starts.append(s)
            ends.append(t)
            work.append(n)
    trace_file = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
        trace_file = find_xplane(trace_dir)
    return Window(t0=t0, t1=t, starts=starts, ends=ends, work=work,
                  trace_file=trace_file)


def find_xplane(trace_dir: str) -> str:
    found = []
    for d, _, files in os.walk(trace_dir):
        found += [os.path.join(d, f) for f in files
                  if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


# ---------------- metrics ----------------

@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    window: Window
    setup_s: float
    device_kind: str
    device_ids: list            # ids of the devices the cell runs on
    trace: object = None        # tracereduce.Reduced, in a traced run


def read_metrics(entries: list, ctx: Context) -> dict:
    out = {}
    for m in entries:
        value = load_module(metric_file(m["name"])).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------- one run ----------------

def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def host_memory(status: str = "/proc/self/status") -> str:
    """The process's resident host memory now and at its peak. A kernel
    may leave out any line of ``status`` (some sandboxes have no
    ``VmHWM``), so the peak comes from ``getrusage``, and what cannot be
    read is said so; this is a log line and never stops a run."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    try:
        with open(status) as f:
            kb = dict(ln.split(":", 1) for ln in f if ":" in ln)
        now = f"{int(kb['VmRSS'].split()[0]) / 2**20:.2f} GiB"
    except (OSError, KeyError, ValueError, IndexError):
        now = "not readable"
    return f"host RSS {now}, peak {peak:.2f} GiB"


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True) -> int:
    """One run of a cell; prints the result line and returns the exit
    code. ``require_tpu=False`` lets the tests drive a whole run on the
    CPU; the command never passes it."""
    import jax
    import tracereduce

    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        log(f"no TPU: JAX finds platform {platform!r}; this benchmark "
            f"measures the chip and has no CPU fallback")
        return 1
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}")
        return 1
    log(f"cache: {enable_compile_cache()}")
    used = devices[:cell.chips]
    kind = used[0].device_kind

    sut = cell.entry.Deployment(cell.config, cell.traffic, seed, used)
    setup_s = time.perf_counter() - t_start
    log(f"{cell.name}: set-up {setup_s:.3f} s on {platform} {kind} "
        f"x{len(devices)}; {host_memory()}")

    tmp = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        win = closed_loop(sut.serve, seconds, sut.span, tmp)
        log(f"{cell.name}: window {win.seconds:.3f} s, "
            f"{len(win.work)} requests; {host_memory()}")
        for line in sut.describe():
            log(line)
        memory_peak = max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in used)
        device = {"platform": platform, "kind": kind,
                  "count": len(devices), "memory_peak_bytes": memory_peak}
        ctx = Context(cell=cell, window=win, setup_s=setup_s,
                      device_kind=kind, device_ids=[d.id for d in used])
        breakdown = None
        if trace:
            ctx.trace = tracereduce.load(win.trace_file, ctx.device_ids)
            metrics = read_metrics(cell.per_layer, ctx)
            breakdown = {"device_ops": tracereduce.top_ops(ctx.trace),
                         "idle_gaps": tracereduce.idle_gaps(ctx.trace)}
            device["busy_s"] = tracereduce.busy_s(ctx.trace)
            device["window_s"] = tracereduce.window_s(ctx.trace)
        else:
            metrics = read_metrics(cell.end_to_end, ctx)
        del ctx
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

    # the program's state goes before the reference runs
    gc.collect()
    checks, failed = sut.check()
    del sut
    gc.collect()
    log(f"{cell.name}: checked; {host_memory()}")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(win.work),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
