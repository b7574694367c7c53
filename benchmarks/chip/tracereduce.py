"""Reduce a JAX profiler trace (``.xplane.pb``) of one measured window to
what the metric readers need: per device, the union of the intervals in
which an operation ran; the device's op and module events by name; and
the benchmark's own host spans with the host events beside them, all on
the trace's one clock.

The window is the host span ``bench.window`` that the harness opens
around the measured requests. Every interval is clipped to it.

On a TPU a device plane ``/device:TPU:<id>`` has a line of XLA modules (one
event per program run) and a line of the ops inside them. A program too
small to show ops, such as a copy of four bytes, shows only its module,
so busy time is the union of both lines.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    t0: int                     # the window, ns on the trace's clock
    t1: int
    busy: dict                  # device id -> merged [(start, end)] ns
    ops: list                   # (device id, name, start, end)
    modules: list               # (device id, name, start, end)
    spans: list                 # (name, start, end): the bench.* spans
    host: list                  # (name, start, end): other events on
    #                             the thread that holds the spans


def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def load(path: str, device_ids) -> Reduced:
    """Read the trace at ``path`` and keep the devices in ``device_ids``."""
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), device_ids)


def reduce(data, device_ids) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to the devices in
    ``device_ids``."""
    window = None
    spans, host = [], []
    device_lines = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev in device_ids:
                device_lines[dev] = {ln.name: ln for ln in plane.lines}
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = list(_events(ln))
            mine = [e for e in evs if e[0].startswith(SPAN_PREFIX)]
            if not mine:
                continue
            spans += mine
            host += [e for e in evs if not e[0].startswith(SPAN_PREFIX)]
            for name, s, e in mine:
                if name == WINDOW_SPAN:
                    window = (s, e)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    missing = set(device_ids) - set(device_lines)
    if missing:
        raise ValueError(f"the trace has no device plane for "
                         f"{sorted(missing)}")
    t0, t1 = window

    def clipped(dev, line_name):
        line = device_lines[dev].get(line_name)
        if line is None:
            return []
        return [(dev, n, max(s, t0), min(e, t1)) for n, s, e in _events(line)
                if e > t0 and s < t1]

    ops, modules, busy = [], [], {}
    for dev in device_ids:
        o, m = clipped(dev, OPS_LINE), clipped(dev, MODULES_LINE)
        ops += o
        modules += m
        busy[dev] = merge((s, e) for _, _, s, e in o + m)
    by_start = lambda e: e[1]
    spans = sorted((e for e in spans if e[0] != WINDOW_SPAN), key=by_start)
    return Reduced(t0=t0, t1=t1, busy=busy, ops=ops, modules=modules,
                   spans=spans, host=sorted(host, key=by_start))


def window_s(red: Reduced) -> float:
    return (red.t1 - red.t0) / 1e9


def busy_s(red: Reduced) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = [sum(e - s for s, e in iv) for iv in red.busy.values()]
    return sum(per) / len(per) / 1e9


def idle_share(red: Reduced) -> float:
    """1 - busy / window, averaged over the devices."""
    return 1.0 - busy_s(red) / window_s(red)


def module_name(event_name: str) -> str:
    """``jit_lbm_step(1234)`` -> ``jit_lbm_step``."""
    return event_name.split("(", 1)[0]


def module_events(red: Reduced, name: str) -> list:
    return [m for m in red.modules if module_name(m[1]) == name]


def busy_within(red: Reduced, spans: list) -> list:
    """For each ``(s, e)`` of ``spans``: ns in it in which any of the
    devices ran an operation."""
    union = merge(iv for ivs in red.busy.values() for iv in ivs)
    starts = [a for a, _ in union]
    out = []
    for s, e in spans:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        ns = 0
        while i < len(union) and union[i][0] < e:
            a, b = union[i]
            ns += max(0, min(b, e) - max(a, s))
            i += 1
        out.append(ns)
    return out


def span_durations(red: Reduced, name: str) -> list:
    return [(s, e) for n, s, e in red.spans if n == name]


def op_name(event_name: str) -> str:
    """``%fusion.10 = f32[...] fusion(...), kind=kLoop`` -> ``%fusion.10``."""
    return event_name.split(" = ", 1)[0]


def top_ops(red: Reduced, n: int = 10) -> list:
    """The device operations that took most time, summed over the devices,
    each named ``<module>/<op>``; a module that shows no op counts as one
    op of its own name. ``[[name, seconds], ...]``."""
    mods = sorted(red.modules, key=lambda m: (m[0], m[2]))
    keys = [(d, s) for d, _, s, _ in mods]
    tot: dict = {}
    has_ops = set()
    for dev, name, s, e in red.ops:
        i = bisect.bisect_right(keys, (dev, s)) - 1
        mod = "?"
        if i >= 0 and mods[i][0] == dev and mods[i][3] >= s:
            mod = module_name(mods[i][1])
            has_ops.add(i)
        key = f"{mod}/{op_name(name)}"
        tot[key] = tot.get(key, 0) + (e - s)
    for i, (_, name, s, e) in enumerate(mods):
        if i not in has_ops:
            key = module_name(name)
            tot[key] = tot.get(key, 0) + (e - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def gaps(red: Reduced) -> list:
    """Idle intervals of every device inside the window: (start, end)."""
    out = []
    for ivs in red.busy.values():
        t = red.t0
        for s, e in ivs:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if red.t1 > t:
            out.append((t, red.t1))
    return out


def _covering(events: list, starts: list, longest: int, s: int, e: int):
    """The event of ``events`` (sorted by start, none longer than
    ``longest``) that overlaps ``[s, e]`` the most; the shorter one wins
    a tie."""
    best, key = None, None
    lo = bisect.bisect_left(starts, s - longest)
    hi = bisect.bisect_left(starts, e)
    for name, a, b in events[lo:hi]:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        k = (ov, -(b - a))
        if key is None or k > key:
            best, key = name, k
    return best


def idle_gaps(red: Reduced, n: int = 10) -> list:
    """The longest idle gaps, each named by what the host was doing in
    it: the benchmark's span and the host event that overlaps the gap
    most. ``[[name, seconds], ...]``."""
    top = sorted(gaps(red), key=lambda g: g[0] - g[1])[:n]
    found = []
    for events in (red.spans, red.host):
        found.append((events, [a for _, a, _ in events],
                      max((b - a for _, a, b in events), default=0)))
    out = []
    for s, e in top:
        span = _covering(*found[0], s, e) or "outside spans"
        what = _covering(*found[1], s, e)
        out.append([f"{span}: {what}" if what else span, (e - s) / 1e9])
    return out
