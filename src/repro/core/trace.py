"""Cluster-wide tracing & metrics plane (DESIGN.md §9).

A ``Tracer`` records one span per command lifecycle stage — enqueue,
placement decision, client→server wire (incl. NIC egress/ingress
queueing and per-chunk landfall), device run-queue wait, execution,
completion routing — plus transfer spans, dedup events, requeue
annotations, and fault markers from the membership plane. Everything is
stamped with *simulated* time, so a trace is as deterministic and
bit-reproducible as the run that produced it.

Two invariants, both load-bearing:

* **Tracing off is free.** Every hook site in the runtime is gated the
  same way ``PlacementEngine.telemetry_active`` gates the placement
  tally: one attribute load and a ``None`` check on the hot path, no
  call, no allocation. A ``Cluster`` built without ``trace=`` carries
  ``trace=None`` and executes byte-identical code.
* **Tracing on never perturbs simulated time.** Hooks *observe* the
  clock (or are handed timestamps the caller already computed); the
  tracer never calls ``clock.schedule*``, so the event sequence — and
  therefore every simulated timestamp — is identical with tracing on
  and off.

Exporters: Chrome/Perfetto ``trace_event`` JSON (``write_perfetto``;
load the file in https://ui.perfetto.dev) and a terminal latency-
breakdown table (``format_breakdown``) reproducing the paper's Fig. 9
command-latency decomposition. ``MetricsRegistry`` layers windowed
p50/p95/p99 histograms per tenant/server/device/link on top of the raw
spans and can flatten ``Cluster.stats()`` counters into the same
namespace, unifying the ad-hoc scoreboards.

The wall-clock half: ``span(name, **meta)`` marks a stretch of the
host's real work (a command's enqueue, a kernel call, a host copy) as a
``jax.profiler.TraceAnnotation``, so it lands in the profiler's own
trace on the clock the device planes use, and a gap in device activity
can be put down to what the host was doing in it. The keyword arguments
become the event's stats. Off — no profiler running — ``span`` is a gate
check that returns one shared no-op context, so the same rule holds:
tracing off is free. An operator records the spans with
``jax.profiler.trace(dir)`` around the work and opens the trace in
Perfetto or XProf.
"""
from __future__ import annotations

import gzip
import json
import math
from fractions import Fraction
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "CmdRecord", "MetricsRegistry", "Histogram",
           "set_default", "get_default", "span", "STAGES"]

# Lifecycle stages of the latency decomposition, in causal order. Each
# is the delta between two adjacent stamps of the forward-filled stamp
# chain (see Tracer.breakdown): queued → submitted → ready → start →
# end-of-lifecycle (client ack when observed, else device completion).
STAGES = ("submit_wire", "dep_wait", "queue_wait", "execute",
          "completion")

# ---------------------------------------------------------------------------
# module-level default tracer: ``Cluster(trace=None)`` falls back to
# this, so harnesses like ``benchmarks/run.py --trace=FILE`` can trace
# every cluster a benchmark builds without threading a parameter
# through each module.
_DEFAULT: Optional["Tracer"] = None


def set_default(tracer: Optional["Tracer"]) -> None:
    global _DEFAULT
    _DEFAULT = tracer


def get_default() -> Optional["Tracer"]:
    return _DEFAULT


class _Off:
    """The span handed out while no profiler runs: enters, exits and
    takes metadata, and records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        pass


_OFF = _Off()


def span(name: str, **meta):
    """A wall-clock span of the host's work, on the profiler's clock:
    ``jax.profiler.TraceAnnotation(name, **meta)`` while a profiler
    trace is being recorded, else the shared no-op. Use it as a context
    manager; ``set_metadata(**meta)`` on what it enters adds stats known
    only inside the span (an event id, the bytes a copy moved)."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **meta)
    return _OFF


def _round_shares(shares: list, decimals: int = 2) -> list:
    """Largest-remainder rounding of percentage shares: the returned
    values, each a multiple of ``10**-decimals``, sum to exactly 100 at
    that precision — so a printed share column never drifts off 100.0
    by display rounding. Tolerates inputs whose float sum is slightly
    off 100 (telescoping error): the correction lands on the entries
    with the largest (or smallest) fractional remainders."""
    scale = 10 ** decimals
    scaled = [s * scale for s in shares]
    floors = [math.floor(x) for x in scaled]
    short = round(100 * scale) - sum(floors)
    order = sorted(range(len(shares)),
                   key=lambda i: (scaled[i] - floors[i], shares[i]),
                   reverse=True)
    out = list(floors)
    i = 0
    while short > 0 and order:
        out[order[i % len(order)]] += 1
        short -= 1
        i += 1
    i = len(order) - 1
    while short < 0 and order:
        out[order[i % len(order)]] -= 1
        short += 1
        i -= 1
    return [v / scale for v in out]


class CmdRecord:
    """Per-command lifecycle record. Timestamps other than ``t_ready``
    live on the ``Event`` itself (``t_queued``/``t_submitted``/
    ``t_start``/``t_end``/``t_client_ack``); the tracer only adds what
    the Event does not carry: the run-queue entry time, the placed
    server/device, the modeled execution cost, and any drain requeues.

    Causal edges for the critical-path analyzer (DESIGN.md §11) ride
    the same record: ``deps`` holds the dependency event ids the client
    classified at enqueue time, ``slices`` the actual device occupancy
    intervals when a preemptive policy ran the command in chunks."""

    __slots__ = ("ev", "tenant", "t_ready", "server", "device", "cost",
                 "requeues", "deps", "slices")

    def __init__(self, ev, tenant: str):
        self.ev = ev
        self.tenant = tenant
        self.t_ready: Optional[float] = None
        self.server: Optional[str] = None
        self.device: Optional[str] = None
        self.cost = 0.0
        self.requeues = None          # lazily [(t, src_server, reason)]
        self.deps = None              # lazily [dep_event_id, ...]
        self.slices = None            # lazily [(t0, t1), ...] llf slices


class Histogram:
    """Windowed histogram over ``(sim_time, value)`` samples. Nearest-
    rank percentiles, optional ``[t0, t1)`` window — cheap and exact
    (samples are kept; the benchmark scales here are thousands, not
    billions)."""

    __slots__ = ("samples",)

    def __init__(self):
        self.samples: list = []       # (t, value) in observation order

    def add(self, t: float, value: float) -> None:
        self.samples.append((t, value))

    def _window(self, t0: Optional[float], t1: Optional[float]) -> list:
        vals = [v for t, v in self.samples
                if (t0 is None or t >= t0) and (t1 is None or t < t1)]
        vals.sort()
        return vals

    def percentile(self, q: float, t0: Optional[float] = None,
                   t1: Optional[float] = None) -> float:
        vals = self._window(t0, t1)
        if not vals:
            return 0.0
        # nearest-rank: smallest value with cum. frequency >= q%
        rank = max(1, -(-len(vals) * q // 100))  # ceil without floats
        return vals[int(rank) - 1]

    def summary(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> dict:
        vals = self._window(t0, t1)
        if not vals:
            return {"count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
                    "p99": 0.0}

        def pct(q):
            rank = max(1, -(-len(vals) * q // 100))
            return vals[int(rank) - 1]

        return {"count": len(vals), "mean": sum(vals) / len(vals),
                "p50": pct(50), "p95": pct(95), "p99": pct(99)}


class MetricsRegistry:
    """Namespaced histograms + flat counters. ``observe`` feeds a
    ``(metric, key)`` histogram; ``ingest_stats`` flattens a nested
    ``stats()`` dict into dotted counters, so the scoreboards scattered
    across runtime/netsim/scheduler/store/placement all land in one
    queryable namespace."""

    def __init__(self):
        self._hists: dict = {}        # (metric, key) -> Histogram
        self.counters: dict = {}      # dotted name -> number

    def hist(self, metric: str, key: str = "") -> Histogram:
        h = self._hists.get((metric, key))
        if h is None:
            h = self._hists[(metric, key)] = Histogram()
        return h

    def observe(self, metric: str, key: str, t: float,
                value: float) -> None:
        self.hist(metric, key).add(t, value)

    def ingest_stats(self, prefix: str, stats: dict) -> None:
        for k, v in stats.items():
            name = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, dict):
                self.ingest_stats(name, v)
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                self.counters[name] = self.counters.get(name, 0) + v

    def rate(self, metric: str, key: str = "",
             t0: Optional[float] = None,
             t1: Optional[float] = None) -> float:
        """Windowed rate of a 0/1 sample stream: the fraction of samples
        in ``[t0, t1)`` that are nonzero; 0.0 when no samples landed in
        the window. Feeds the per-class SLO violation-rate gates
        (DESIGN.md §10)."""
        h = self._hists.get((metric, key))
        if h is None:
            return 0.0
        vals = h._window(t0, t1)
        if not vals:
            return 0.0
        return sum(1 for v in vals if v > 0.0) / len(vals)

    def summary(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> dict:
        return {f"{m}[{k}]" if k else m: h.summary(t0, t1)
                for (m, k), h in sorted(self._hists.items())}


class Tracer:
    """Append-only span store + exporters. One tracer may serve several
    clusters (``benchmarks/fleet_sweep.py`` builds one per fleet size);
    entities of the second and later clusters are namespaced with a
    ``c<i>:`` prefix by the cluster itself at hook time."""

    def __init__(self):
        self.cmds: dict = {}          # event id -> CmdRecord
        self.transfers: list = []     # (kind, link, tenant, t0, t1,
                                      #  nbytes, ev_id, chunk_arrivals)
        self.nic_spans: list = []     # (label, t0, busy_dur)
        self.placements: list = []    # (t, tenant, name, chosen, policy)
        self.dedups: list = []        # (t, tenant, signed nbytes)
        self.faults: list = []        # (t, kind, target, detail)
        self.slo: list = []           # (t, tenant, ev_id, latency, slo)
        self.admissions: list = []    # (t, tenant, status, predicted_s,
                                      #  requested_slo_s, slo_s, reason)
        self.link_spans: list = []    # (label, wire_t0, wire_busy)
        self.runq: list = []          # (label, t, queued_depth)
        self.links: dict = {}         # label -> (latency_s, bandwidth_Bps)
        self._clusters: list = []

    # ---- wiring ----
    def register_cluster(self, cluster) -> int:
        self._clusters.append(cluster)
        return len(self._clusters) - 1

    # ---- hot-path hooks (called only when the gate saw non-None) ----
    def cmd_queued(self, ev, tenant: str) -> None:
        self.cmds[ev.id] = CmdRecord(ev, tenant)

    def cmd_ready(self, ev, now: float, server: str, device: str,
                  cost: float) -> None:
        r = self.cmds.get(ev.id)
        if r is None:                 # enqueued before tracing attached
            r = self.cmds[ev.id] = CmdRecord(ev, "?")
        r.t_ready = now
        r.server = server
        r.device = device
        r.cost = cost

    def requeue(self, ev, now: float, src: str, reason: str) -> None:
        r = self.cmds.get(ev.id)
        if r is None:
            r = self.cmds[ev.id] = CmdRecord(ev, "?")
        if r.requeues is None:
            r.requeues = []
        r.requeues.append((now, src, reason))

    def cmd_deps(self, ev, dep_ids) -> None:
        """Happens-before edges (DESIGN.md §11): the dependency event
        ids this command waited on, as classified by the client at send
        time — the explicit half of the causal DAG (resource edges come
        from exec slices, link/NIC spans, and run-queue samples)."""
        if not dep_ids:
            return
        r = self.cmds.get(ev.id)
        if r is None:
            r = self.cmds[ev.id] = CmdRecord(ev, "?")
        r.deps = list(dep_ids)

    def exec_slice(self, ev, t0: float, t1: float) -> None:
        """One device slice of a preemptively-scheduled command (llf,
        DESIGN.md §10): the device was occupied by ``ev`` over exactly
        ``[t0, t1)``. Non-preemptive commands occupy
        ``[t_start, t_start + cost)`` and never emit slices."""
        r = self.cmds.get(ev.id)
        if r is None:
            r = self.cmds[ev.id] = CmdRecord(ev, "?")
        if r.slices is None:
            r.slices = []
        r.slices.append((t0, t1))

    def admission(self, tenant: str, decision) -> None:
        """Admission verdict marker (DESIGN.md §10 control plane →
        §9 observability): admit/degrade/reject with the controller's
        predicted latency, so predicted-vs-actual is inspectable next
        to the tenant's own command tracks."""
        self.admissions.append((decision.t, tenant, decision.status,
                                decision.predicted_s,
                                decision.requested_slo_s,
                                decision.slo_s, decision.reason))

    def link_span(self, label: str, t0: float, busy: float) -> None:
        """Wire occupancy of one link: ``busy`` seconds of serialization
        starting at ``t0`` (queueing behind earlier messages excluded —
        that is the gap between the transfer span start and this)."""
        self.link_spans.append((label, t0, busy))

    def run_queue(self, label: str, t: float, depth: int) -> None:
        """Run-queue depth sample from a DeviceScheduler at a push/pop
        boundary (the in-service command is excluded, matching
        ``queued_seconds``). Renders as a Perfetto counter track."""
        self.runq.append((label, t, depth))

    def transfer(self, kind: str, link: str, tenant: str, t0: float,
                 t1: float, nbytes: float, ev_id: Optional[int] = None,
                 chunk_arrivals: Optional[list] = None,
                 link_obj=None) -> None:
        if link_obj is not None and link not in self.links:
            # substrate metadata for what-if re-timing: which part of a
            # recorded transfer duration is bandwidth-proportional
            self.links[link] = (link_obj.latency, link_obj.bandwidth)
        self.transfers.append((kind, link, tenant, t0, t1, nbytes,
                               ev_id, chunk_arrivals))

    def nic_span(self, label: str, t0: float, busy: float) -> None:
        # ``busy`` is the exact float the caller added to
        # ``NIC.busy_time`` — appended in the same order, so a sum over
        # these spans reproduces the counter bit-for-bit.
        self.nic_spans.append((label, t0, busy))

    def placement(self, t: float, tenant: str, name: str, chosen: str,
                  policy: str) -> None:
        self.placements.append((t, tenant, name, chosen, policy))

    def dedup(self, t: float, tenant: str, nbytes: float) -> None:
        self.dedups.append((t, tenant, nbytes))

    def fault(self, t: float, kind: str, target: str,
              detail: str = "") -> None:
        self.faults.append((t, kind, target, detail))

    def slo_violation(self, t: float, tenant: str, ev_id: int,
                      latency: float, slo: float) -> None:
        """Client-ack hook (gated: only violations of a declared SLO
        reach here): command ``ev_id`` finished ``latency`` seconds
        after enqueue against an SLO of ``slo`` seconds."""
        self.slo.append((t, tenant, ev_id, latency, slo))

    # ---- derived views ----
    @staticmethod
    def _cmd_end(ev) -> float:
        return ev.t_client_ack if ev.t_client_ack > 0.0 else ev.t_end

    @staticmethod
    def _stamps(rec) -> list:
        """Forward-filled stamp chain [queued, submitted, ready, start,
        done, end] — six boundaries, one per STAGES interval. A 0.0
        stamp means the command never reached that stage (e.g. a
        WriteBuffer completes inline without a run queue); it inherits
        the previous boundary so its stage contributes exactly zero and
        the telescoping sum stays exact."""
        ev = rec.ev
        raw = [ev.t_queued, ev.t_submitted,
               rec.t_ready if rec.t_ready is not None else 0.0,
               ev.t_start, ev.t_end, Tracer._cmd_end(ev)]
        out = [raw[0]]
        for s in raw[1:]:
            out.append(s if s > out[-1] else out[-1])
        return out

    def finished(self) -> list:
        """CmdRecords whose lifecycle closed (COMPLETE, end stamped)."""
        return [r for r in self.cmds.values()
                if r.ev.status == "complete" and self._cmd_end(r.ev) > 0.0]

    def breakdown(self, exact: bool = False) -> dict:
        """Per-stage decomposition over finished commands.

        Returns ``{stage: [durations...]}`` plus ``"total"`` (end-to-end
        per-command latency, same order). With ``exact=True`` durations
        are ``fractions.Fraction`` — the per-command stage sums then
        equal the end-to-end latency *exactly* (telescoping is exact in
        rational arithmetic), which ``benchmarks/latency_breakdown.py``
        gates on."""
        num = Fraction if exact else float
        out: dict = {s: [] for s in STAGES}
        out["total"] = []
        for rec in self.finished():
            st = self._stamps(rec)
            if exact:
                st = [Fraction(x) for x in st]
            for name, a, b in zip(STAGES, st, st[1:]):
                out[name].append(num(b - a) if not exact else b - a)
            out["total"].append(st[-1] - st[0])
        return out

    def format_breakdown(self, title: str = "") -> str:
        """Terminal table: per-stage count/mean/p50/p95/p99 (µs) and the
        share of total end-to-end latency attributed to each stage."""
        bd = self.breakdown()
        total = sum(bd["total"]) or 1.0
        lines = []
        if title:
            lines.append(f"# {title}")
        lines.append(f"{'stage':<14}{'count':>7}{'mean_us':>10}"
                     f"{'p50_us':>10}{'p95_us':>10}{'p99_us':>10}"
                     f"{'share%':>8}")

        def row(name, vals, share):
            h = Histogram()
            for v in vals:
                h.add(0.0, v * 1e6)
            s = h.summary()
            lines.append(f"{name:<14}{s['count']:>7}{s['mean']:>10.2f}"
                         f"{s['p50']:>10.2f}{s['p95']:>10.2f}"
                         f"{s['p99']:>10.2f}{share:>8.2f}")

        raw = [100.0 * sum(bd[stage]) / total for stage in STAGES]
        for stage, share in zip(STAGES, _round_shares(raw)):
            row(stage, bd[stage], share)
        row("total", bd["total"], 100.0)
        return "\n".join(lines)

    def metrics(self) -> MetricsRegistry:
        """Histograms derived from the spans: end-to-end latency per
        tenant, execute/queue-wait per server/device, wire time and
        bytes per link — then every attached cluster's ``stats()``
        counters flattened alongside."""
        reg = MetricsRegistry()
        for rec in self.finished():
            st = self._stamps(rec)
            reg.observe("cmd_latency", rec.tenant, st[0], st[-1] - st[0])
            if rec.server is not None:
                key = f"{rec.server}/{rec.device}"
                reg.observe("queue_wait", key, st[2], st[3] - st[2])
                reg.observe("execute", key, st[3], rec.cost)
                if rec.slices:
                    # llf preemption slices (DESIGN.md §10): per-slice
                    # device occupancy, plus the count per command
                    for a, b in rec.slices:
                        reg.observe("preempt_slice", key, a, b - a)
                    reg.observe("preempt_slices_per_cmd", key, st[3],
                                len(rec.slices))
        for kind, link, _tenant, t0, t1, nbytes, _e, _c in self.transfers:
            reg.observe("wire_time", link, t0, t1 - t0)
            reg.observe("wire_bytes", link, t0, nbytes)
        for label, t0, busy in self.link_spans:
            reg.observe("link_busy", label, t0, busy)
        for label, t, depth in self.runq:
            reg.observe("run_queue_depth", label, t, depth)
        for t, _tenant, status, predicted, _req, _slo, _why \
                in self.admissions:
            # verdict counts + the controller's predicted latency per
            # verdict class; actuals live in cmd_latency/slo_lateness
            reg.observe("admission_predicted", status, t, predicted)
            name = f"admission.{status}"
            reg.counters[name] = reg.counters.get(name, 0) + 1
        for t, tenant, _eid, latency, slo in self.slo:
            # lateness past the deadline, per tenant: the per-class
            # violation *rates* live on the admission controller; this
            # is the per-violation magnitude view
            reg.observe("slo_lateness", tenant, t, latency - slo)
        for i, cluster in enumerate(self._clusters):
            pfx = f"c{i}" if len(self._clusters) > 1 else ""
            reg.ingest_stats(pfx, cluster.stats())
        return reg

    # ---- Perfetto / Chrome trace_event export ----
    def perfetto_events(self) -> list:
        """Chrome ``trace_event`` list. Layout:

        * one process per tenant; each finished command is an async
          track (``ph: b/e``, ``cat: 'cmd'``, ``id``: event id) whose
          nested child slices are the lifecycle stages;
        * one process per server; device threads carry ``X`` execution
          slices, NIC threads carry ``X`` occupancy slices;
        * a ``net`` process with one thread per link: ``X`` transfer
          slices plus ``i`` chunk-landfall instants;
        * placement decisions as thread-scoped instants, fault markers
          as global instants (``cat: 'fault'``).

        ``ts`` is simulated microseconds. Deterministic: entities are
        sorted, ids are simulation-assigned."""
        ev_list: list = []
        pids: dict = {}
        tids: dict = {}

        def pid(kind, name):
            key = (kind, name)
            if key not in pids:
                pids[key] = len(pids) + 1
                ev_list.append({"ph": "M", "name": "process_name",
                                "pid": pids[key], "tid": 0,
                                "args": {"name": f"{kind}:{name}"}})
            return pids[key]

        def tid(p, name):
            key = (p, name)
            if key not in tids:
                tids[key] = len([1 for (q, _n) in tids if q == p]) + 1
                ev_list.append({"ph": "M", "name": "thread_name",
                                "pid": p, "tid": tids[key],
                                "args": {"name": name}})
            return tids[key]

        us = 1e6
        # command lifecycles, per tenant, deterministic order by id
        for eid in sorted(self.cmds):
            rec = self.cmds[eid]
            ev = rec.ev
            if ev.status != "complete" or self._cmd_end(ev) <= 0.0:
                continue
            p = pid("tenant", rec.tenant)
            st = self._stamps(rec)
            name = getattr(ev.command, "name", None) or \
                type(ev.command).__name__ if ev.command is not None \
                else f"cmd{eid}"
            args = {"server": rec.server or (ev.server or ""),
                    "device": rec.device or ""}
            if rec.requeues:
                args["requeues"] = [
                    {"t_us": t * us, "from": src, "reason": why}
                    for t, src, why in rec.requeues]
            ev_list.append({"ph": "b", "cat": "cmd", "id": str(eid),
                            "name": str(name), "pid": p, "tid": 0,
                            "ts": st[0] * us, "args": args})
            for stage, a, b in zip(STAGES, st, st[1:]):
                if b <= a:
                    continue
                ev_list.append({"ph": "b", "cat": "cmd", "id": str(eid),
                                "name": stage, "pid": p, "tid": 0,
                                "ts": a * us})
                ev_list.append({"ph": "e", "cat": "cmd", "id": str(eid),
                                "name": stage, "pid": p, "tid": 0,
                                "ts": b * us})
            ev_list.append({"ph": "e", "cat": "cmd", "id": str(eid),
                            "name": str(name), "pid": p, "tid": 0,
                            "ts": st[-1] * us})
            # device execution on the server's device thread: one X per
            # llf slice when the command ran preemptively (the wall
            # interval [t_start, t_end] then interleaves with other
            # commands), else a single full-cost X
            if rec.server is not None and ev.t_start > 0.0:
                sp = pid("server", rec.server)
                dt = tid(sp, f"dev:{rec.device}")
                if rec.slices:
                    n_sl = len(rec.slices)
                    for i, (a, b) in enumerate(rec.slices):
                        ev_list.append({"ph": "X", "cat": "exec",
                                        "name": str(name), "pid": sp,
                                        "tid": dt, "ts": a * us,
                                        "dur": (b - a) * us,
                                        "args": {"tenant": rec.tenant,
                                                 "slice": i,
                                                 "slices": n_sl}})
                else:
                    ev_list.append({"ph": "X", "cat": "exec",
                                    "name": str(name), "pid": sp,
                                    "tid": dt,
                                    "ts": ev.t_start * us,
                                    "dur": rec.cost * us,
                                    "args": {"tenant": rec.tenant}})
        # NIC occupancy
        for label, t0, busy in self.nic_spans:
            server = label.split(".", 1)[0]
            p = pid("server", server)
            ev_list.append({"ph": "X", "cat": "nic", "name": "busy",
                            "pid": p, "tid": tid(p, label),
                            "ts": t0 * us, "dur": busy * us})
        # run-queue depth samples as counter tracks on the owning server
        for label, t, depth in self.runq:
            server = label.split(".", 1)[0]
            p = pid("server", server)
            ev_list.append({"ph": "C", "cat": "sched", "name": label,
                            "pid": p, "tid": 0, "ts": t * us,
                            "args": {"queued": depth}})
        # transfers on the net process, one thread per link (wire
        # occupancy gets its own sibling thread so the X slices nest
        # cleanly next to the queue-inclusive transfer spans)
        np_ = pid("net", "links") if (self.transfers or
                                      self.link_spans) else None
        for label, t0, busy in self.link_spans:
            ev_list.append({"ph": "X", "cat": "net", "name": "wire",
                            "pid": np_, "tid": tid(np_, label + ".wire"),
                            "ts": t0 * us, "dur": busy * us})
        for kind, link, tenant, t0, t1, nbytes, eid, chunks \
                in self.transfers:
            t = tid(np_, link)
            ev_list.append({"ph": "X", "cat": "net", "name": kind,
                            "pid": np_, "tid": t, "ts": t0 * us,
                            "dur": max(0.0, (t1 - t0)) * us,
                            "args": {"bytes": nbytes, "tenant": tenant,
                                     "event": eid,
                                     "chunks": len(chunks) if chunks
                                     else 0}})
            for arrive in (chunks or ()):
                ev_list.append({"ph": "i", "cat": "net",
                                "name": "chunk_landfall", "pid": np_,
                                "tid": t, "ts": arrive * us,
                                "s": "t"})
        # placement decisions
        for t, tenant, name, chosen, policy in self.placements:
            p = pid("tenant", tenant)
            ev_list.append({"ph": "i", "cat": "placement",
                            "name": f"{name}->{chosen}", "pid": p,
                            "tid": tid(p, "placement"), "ts": t * us,
                            "s": "t", "args": {"policy": policy}})
        # dedup savings
        for t, tenant, nbytes in self.dedups:
            p = pid("tenant", tenant)
            ev_list.append({"ph": "i", "cat": "dedup",
                            "name": "dedup" if nbytes >= 0
                            else "dedup_undo",
                            "pid": p, "tid": tid(p, "store"),
                            "ts": t * us, "s": "t",
                            "args": {"bytes": nbytes}})
        # admission verdicts: instants on the tenant's process carrying
        # the controller's prediction, so predicted-vs-actual reads off
        # the same screen as the tenant's command latencies
        for t, tenant, status, predicted, req_slo, slo_s, reason \
                in self.admissions:
            p = pid("tenant", tenant)
            ev_list.append({"ph": "i", "cat": "admission",
                            "name": f"admission:{status}", "pid": p,
                            "tid": tid(p, "admission"), "ts": t * us,
                            "s": "t",
                            "args": {"predicted_ms": predicted * 1e3,
                                     "requested_slo_ms":
                                         (req_slo or 0.0) * 1e3,
                                     "granted_slo_ms":
                                         (slo_s or 0.0) * 1e3,
                                     "reason": reason}})
        # SLO violations: instants on the tenant's own process so the
        # breach lines up with the offending command track
        for t, tenant, eid, latency, slo in self.slo:
            p = pid("tenant", tenant)
            ev_list.append({"ph": "i", "cat": "slo",
                            "name": "slo_violation", "pid": p,
                            "tid": tid(p, "slo"), "ts": t * us,
                            "s": "t",
                            "args": {"event": eid,
                                     "latency_ms": latency * 1e3,
                                     "slo_ms": slo * 1e3}})
        # fault markers: global instants so they cut across every track
        for t, kind, target, detail in self.faults:
            p = pid("cluster", "faults")
            ev_list.append({"ph": "i", "cat": "fault",
                            "name": f"{kind}:{target}", "pid": p,
                            "tid": 0, "ts": t * us, "s": "g",
                            "args": {"detail": detail}})
        return ev_list

    def write_perfetto(self, path: str) -> None:
        # a ``.gz`` suffix gzips transparently (1000-UE fleet traces
        # are large; Perfetto's UI loads gzipped JSON directly)
        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump({"traceEvents": self.perfetto_events(),
                       "displayTimeUnit": "ms"}, f, indent=None,
                      separators=(",", ":"))
            f.write("\n")

    # ---- causal critical-path analysis (DESIGN.md §11) ----
    def critical_path(self, exact: bool = False, root=None):
        """Reconstruct the happens-before DAG from the recorded spans
        and walk the binding constraint backward from the last finished
        command (or ``root``): a ``critpath.CriticalPath`` whose
        segments tile the makespan exactly. Post-hoc only — reads the
        span store, never the live simulation."""
        from . import critpath
        return critpath.critical_path(self, exact=exact, root=root)

    def format_blame(self, top: int = 12, title: str = "") -> str:
        """Terminal table ranking the critical path's makespan
        attribution per (resource, stage)."""
        from . import critpath
        return critpath.format_blame(self.critical_path(), top=top,
                                     title=title)

    def whatif(self, **knobs) -> dict:
        """Re-time the recorded DAG under hypothetical substrate changes
        (``nic_bandwidth=2.0``, ``device_speed=2.0``, ``wire=0.0``,
        ``overlap_halo=True``); see ``critpath.whatif`` for the model
        and its assumptions."""
        from . import critpath
        return critpath.whatif(self, **knobs)
