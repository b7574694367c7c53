"""The program's own spans in a reduced trace: the wall-clock spans that
the program opens (``repro.core.trace.span``) in its command path
(``pocl.*``) and in the CFD offload loop (``lbm.*``), on the clock of the
device planes.

They are the events of ``Reduced.host`` (the thread that holds the
benchmark's request spans) whose names carry those prefixes, clipped to
the window. Spans of one thread nest or lie apart, so each instant in
them belongs to one innermost span: a span's self time is its time less
that of the program spans nested in it. A trace of a program that opens
no such span gives none, and the readers built on this module then
return ``None``.
"""
from __future__ import annotations

import bisect

import tracereduce

PREFIXES = ("pocl.", "lbm.")
OUTSIDE = "outside requests"


def program_spans(red) -> list:
    """``(name, start, end)`` of the program spans, clipped to the
    window."""
    t0, t1 = red.t0, red.t1
    return [(n, max(s, t0), min(e, t1)) for n, s, e in red.host
            if n.startswith(PREFIXES) and e > t0 and s < t1]


def leaves(spans) -> list:
    """Each instant of ``spans`` (one thread's: nested or apart) given to
    the innermost span that covers it, as ``(name, start, end)`` pieces,
    sorted and disjoint. A span's pieces sum to its self time."""
    out: list = []
    stack: list = []            # [name, end, resume] of the open spans

    def close(t):
        while stack and stack[-1][1] <= t:
            name, end, resume = stack.pop()
            if end > resume:
                out.append((name, resume, end))
            if stack:
                stack[-1][2] = end      # the parent resumes here

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            parent = stack[-1]
            if s > parent[2]:
                out.append((parent[0], parent[2], s))
            e = min(e, parent[1])
        stack.append([name, e, s])
    close(float("inf"))
    return out


def _of(red, names) -> list:
    return [(s, e) for n, s, e in program_spans(red) if n in names]


def time_ns(red, names):
    """Summed time of the program spans named in ``names``; ``None``
    where the trace has none of them."""
    spans = _of(red, names)
    if not spans:
        return None
    return sum(e - s for s, e in spans)


def host_ns(red, names):
    """``time_ns`` less the time in which a device ran an operation
    inside those spans: what the host spent in them of its own."""
    spans = _of(red, names)
    if not spans:
        return None
    busy = tracereduce.busy_within(red, spans)
    return sum(e - s for s, e in spans) - sum(busy)


def self_ns(red, names):
    """Summed self time of the program spans named in ``names``;
    ``None`` where the trace has none of them."""
    spans = program_spans(red)
    if not any(n in names for n, _, _ in spans):
        return None
    return sum(e - s for n, s, e in leaves(spans) if n in names)


def per_request(red, request: str, ns):
    """``ns`` over the number of the window's ``request`` spans; ``None``
    where either is missing."""
    n = len(tracereduce.span_durations(red, request))
    if ns is None or n == 0:
        return None
    return ns / n


def stat_sum(path: str, red, key: str):
    """The sum of the stat ``key`` over the program spans that start in
    the window, read again from the trace file at ``path`` (the
    reduction keeps no stats); ``None`` where no such span carries it."""
    from jax.profiler import ProfileData
    total, found = 0, False
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not (ev.name.startswith(PREFIXES)
                        and red.t0 <= ev.start_ns < red.t1):
                    continue
                value = dict(ev.stats).get(key)
                if value is not None:
                    total += int(value)
                    found = True
    return total if found else None


def idle_by_leaf(red) -> list:
    """Device-idle seconds of the window by what the host was doing then:
    the innermost program span; the request span (``bench.*``) where the
    host was in a request and in no program span; or ``OUTSIDE``.
    Averaged over the devices, as ``tracereduce.idle_share`` is, so the
    seconds sum to the window's idle time. ``[[name, seconds], ...]``,
    most first."""
    t0, t1 = red.t0, red.t1
    requests = [(n, max(s, t0), min(e, t1)) for n, s, e in red.spans]
    pieces = leaves(program_spans(red) + requests)
    starts = [s for _, s, _ in pieces]
    tot: dict = {}
    for a, b in tracereduce.gaps(red):
        covered = 0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][1] < b:
            name, s, e = pieces[i]
            ns = min(e, b) - max(s, a)
            if ns > 0:
                tot[name] = tot.get(name, 0) + ns
                covered += ns
            i += 1
        if b - a > covered:
            tot[OUTSIDE] = tot.get(OUTSIDE, 0) + (b - a - covered)
    ndev = len(red.busy)
    return [[name, ns / ndev / 1e9]
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])]
