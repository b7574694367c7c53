"""PoCL-R runtime: client driver + server daemons + decentralized
scheduling over a simulated MEC network (paper §4–§5).

Semantics implemented faithfully:

* Commands are pushed to the target server immediately with their event
  dependencies (§5.2); the server dispatches as soon as deps resolve —
  locally-produced events resolve locally, remote ones via peer
  completion notifications, with NO client round-trip (decentralized
  mode). ``scheduling='client'`` routes completions through the client
  instead (the SnuCL-like baseline the paper compares against).
* Buffer migrations go source-server → destination-server directly over
  peer links (§5.1); ``p2p_migration=False`` stages them through the
  client (the naive path: download + upload over the slowest link).
* ``cl_pocl_content_size`` (§5.3): migrations and reads move only the
  used prefix, and a kernel that also writes an output's content-size
  buffer leaves only that prefix in the output's buffer.
* TCP vs RDMA transports (§5.4) with shadow-buffer staging, registration
  and rkey-exchange costs.
* Connection loss (§4.3): session IDs, command replay on reconnect,
  server-side dedup of already-processed commands, device-unavailable
  status, optional local fallback execution (Fig. 4).

Kernels execute *functionally* (real arrays) in causal simulation order,
so the same runtime that produces latency numbers also produces bit-exact
results for the tests.

Dispatch is O(1) per command (DESIGN.md §1): each server keeps an
indexed waiter table (dep event id → waiting commands, with per-command
remaining-dep counters) and an explicit ready queue instead of rescanning
a pending list; completions are routed only to servers that registered a
dependent on the event (``completion_routing='subscription'``, matching
the paper's direct P2P signaling) instead of broadcast to every peer; and
finished events are retired from all runtime tables once nobody holds a
reference, so long runs stay memory-bounded.

The migration data plane is pipelined (DESIGN.md §3): bulk payloads move
as chunked cut-through transfers (sender copy / wire / receiver copy
overlap per chunk, ``Link.send_chunked``); duplicate in-flight requests
for the same ``(buffer, destination)`` coalesce onto the pending
transfer instead of re-sending the payload; and the migration source is
chosen per-replica by estimated delivery time (link queue + bandwidth +
RDMA registration amortization) instead of set order. ``stats()``
exposes the data-plane scoreboard: ``bytes_on_wire``,
``migrations_coalesced``, ``chunks_in_flight``/``peak_chunks_in_flight``.

The server runtime is multi-tenant (DESIGN.md §4, the paper's
server-side scalability claim): a ``Cluster`` owns the shared substrate
— clock, server hosts (devices + per-device run queues + shared egress
NIC) and the peer mesh — and any number of ``ClientRuntime`` instances
(UE sessions) attach to it. Server-side per-session state (replay
dedup, remote-resolution tracking, dependency waiters) lives in a
``ServerSim`` per (client, server), registered in the host's session
table by session id; device time is arbitrated across sessions by a
pluggable scheduler (FIFO baseline or weighted deficit-round-robin —
``src/repro/core/scheduler.py``). Constructing a ``ClientRuntime``
without an explicit cluster builds a private one, preserving the
original single-tenant API.

Kernel placement is a cluster-wide control plane (DESIGN.md §6,
``Cluster(placement=...)``): every ``enqueue_kernel`` passes its
requested server through the ``PlacementEngine``, which may redirect
the kernel (and its implicit migrations) using live telemetry — run-
queue depth in device-seconds, replica locality from the buffer/store
state, and NIC occupancy on both ends. Policies are pluggable
(``pinned`` — the bit-exact default honoring the caller's pick,
``locality``, ``hetmec``) and can be overridden per tenant
(``ClientRuntime(placement=...)``).

Cross-tenant payloads deduplicate through the cluster's opt-in
content-addressed buffer store (DESIGN.md §5, ``Cluster(store=True)``):
identical uploads resolve to one shared physical replica set per server
(command-only writes when resident, gating on in-flight copies when
racing), migrations are served from or deduplicated against any
tenant's valid replica, tenant writes copy-on-write fork shared content
to private buffers, and ``ClientRuntime.detach()`` releases a tenant's
sessions, run-queue entries, and store references so long-lived
clusters shed departed UEs (unreferenced replicas evict LRU under the
store's per-server capacity).
"""
from __future__ import annotations

import dataclasses
import logging
import secrets
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from repro.core import commands as C
from repro.core.buffers import Buffer
from repro.core.events import (COMPLETE, ERROR, RUNNING, SUBMITTED,
                               Event)
from repro.core.membership import (ACTIVE, DEAD, JOINING,
                                   MembershipManager)
from repro.core.netsim import NIC, DeviceSim, Link, SimClock
from repro.core.placement import (PinnedPolicy, PlacementEngine,
                                  make_placement_policy)
from repro.core.admission import (AdmissionController, AdmissionRejected,
                                  DEGRADE, REJECT)
from repro.core.scheduler import (DeviceScheduler, make_policy,
                                  validate_scheduler_opts)
from repro.core.store import BufferStore, DIGEST_BYTES, content_digest
from repro.core import trace as trace_mod
from repro.core.trace import span
from repro.core.transport import (make_transport, wire_scale, scale_chunks,
    CLIENT_SUBMIT, CLIENT_REAP, CMD_BYTES, DISPATCH, COMPLETE_WRITE)

log = logging.getLogger(__name__)

# residual-laxity base for deadline-less commands under a preemptive
# scheduler: never tighter than anything, so they always yield
_INF = float("inf")


@dataclasses.dataclass
class DeviceSpec:
    name: str
    flops: float = 10e12
    mem_bw: float = 500e9


@dataclasses.dataclass
class ServerSpec:
    name: str
    devices: Sequence[DeviceSpec] = (DeviceSpec("gpu0"),)


@dataclasses.dataclass
class LinkSpec:
    latency: float = 61e-6        # one-way; paper LAN ping 0.122 ms RTT
    bandwidth: float = 100e6 / 8  # 100 Mbit Ethernet


class _Waiter:
    """One submitted command waiting on unresolved dependencies.
    ``dev_idx`` is the host's interned device index (resolved once at
    arrival so dispatch never repeats the name lookup); ``dev_name``
    is kept for the drain/requeue API boundary."""
    __slots__ = ("ev", "dev_name", "dev_idx", "remaining")

    def __init__(self, ev: Event, dev_name: str, dev_idx: int = -1):
        self.ev = ev
        self.dev_name = dev_name
        self.dev_idx = dev_idx
        self.remaining = 0


class ServerHost:
    """Cluster-side half of a pocld server: the physical devices, one
    run-queue scheduler per device, the shared egress NIC, and the §4.3
    session table (session id → attached ``ServerSim``). Everything a
    tenant can contend on lives here; everything scoped to one client
    session lives in ``ServerSim``."""

    def __init__(self, cluster: "Cluster", spec: ServerSpec):
        self.cluster = cluster
        self.name = spec.name
        # interned host id (DESIGN.md §8): small int, unique across the
        # cluster's lifetime (rejoins of a reused *name* get a fresh id)
        cluster._sid_seq += 1
        self.sid = cluster._sid_seq
        self.devices = {d.name: DeviceSim(cluster.clock, d.name,
                                          d.flops, d.mem_bw)
                        for d in spec.devices}
        self.schedulers = {
            name: DeviceScheduler(make_policy(cluster.scheduler_policy,
                                              cluster.scheduler_quantum,
                                              cluster.scheduler_opts))
            for name in self.devices}
        # interned device tables: index-aligned lists + name -> index,
        # so the dispatch hot path replaces two string-dict lookups per
        # kernel with two list indexes ('' = default device = index 0)
        self.device_names = list(self.devices)
        self.device_list = list(self.devices.values())
        self.scheduler_list = [self.schedulers[n] for n in self.device_names]
        self.dev_index = {n: i for i, n in enumerate(self.device_names)}
        self.dev_index[""] = 0
        self.nic = (NIC(cluster.nic_bandwidth, f"{self.name}.nic")
                    if cluster.nic_bandwidth else None)
        self.nic_in = (NIC(cluster.nic_ingress_bandwidth,
                           f"{self.name}.nic_in")
                       if cluster.nic_ingress_bandwidth else None)
        # observability (DESIGN.md §9): point the shared ports at the
        # cluster tracer (covers seed hosts and mid-run joins alike);
        # an untraced cluster leaves NIC.trace None — the hooks inside
        # Link.send/send_chunked stay a slot load + branch
        tr = cluster.trace
        if tr is not None:
            for nic in (self.nic, self.nic_in):
                if nic is not None:
                    nic.trace = tr
                    nic.trace_label = cluster.trace_prefix + nic.name
            # run-queue depth samples (DESIGN.md §11): push/pop
            # boundaries become device-ordering resource edges
            for dname, sch in self.schedulers.items():
                sch.trace = tr
                sch.trace_label = (f"{cluster.trace_prefix}{self.name}"
                                   f".{dname}.runq")
                sch.trace_clock = cluster.clock
        self.sessions: dict = {}     # session id (bytes) -> ServerSim
        # membership lifecycle (DESIGN.md §7); the MembershipManager is
        # authoritative, this mirror makes hot-path checks a plain load
        self.state = ACTIVE


class Cluster:
    """A shared simulated MEC cluster: one logical clock, the server
    hosts, and the peer-link mesh. Any number of ``ClientRuntime``
    instances attach to it — each brings its own client links, event
    tables, and per-server sessions, while devices, run queues, peer
    links, and NICs are contended across all of them.

    ``scheduler`` picks the cross-session device policy (``'fifo'`` |
    ``'drr'`` | ``'edf'`` | ``'llf'``, DESIGN.md §4/§10) and
    ``scheduler_opts`` its validated per-policy knobs ({'quantum'} for
    drr, {'chunk'} for llf; ``scheduler_quantum`` is the legacy spelling
    of the drr knob); ``admission`` enables SLO admission control
    (True for defaults, a dict of ``AdmissionController`` knobs, or a
    prebuilt controller — None/False keeps every tenant unscreened);
    ``nic_bandwidth`` (B/s) enables the shared-NIC egress
    model for every host and ``nic_ingress_bandwidth`` its receive-side
    mirror (None keeps the pre-NIC independent-link behavior on that
    side); ``placement`` picks the cluster-wide kernel placement policy
    (``'pinned'`` | ``'locality'`` | ``'hetmec'``, DESIGN.md §6 — a
    tenant can override it per ``ClientRuntime``). A ``ClientRuntime``
    built without an explicit cluster creates a private one, so the
    single-tenant API is unchanged.
    """

    def __init__(self, servers: Sequence[ServerSpec],
                 peer_link: LinkSpec = LinkSpec(),
                 peer_transport: str = "tcp",
                 svm: bool = False,
                 scheduler: str = "fifo",
                 scheduler_quantum: Optional[float] = None,
                 scheduler_opts: Optional[dict] = None,
                 nic_bandwidth: Optional[float] = None,
                 nic_ingress_bandwidth: Optional[float] = None,
                 store: bool = False,
                 store_capacity: Optional[float] = None,
                 placement: str = "pinned",
                 admission=None,
                 trace=None):
        self.clock = SimClock()
        # observability plane (DESIGN.md §9): ``trace`` accepts a Tracer
        # instance, True (build a private one), False (force off even if
        # a module default is set), or None (fall back to the module
        # default, which ``benchmarks/run.py --trace`` sets so every
        # cluster a benchmark builds is traced without plumbing).
        # ``self.trace`` is None whenever tracing is off — every hook in
        # the runtime gates on that with a single load + branch, the
        # same zero-overhead pattern as PlacementEngine.telemetry_active.
        if trace is None:
            trace = trace_mod.get_default()
        elif trace is True:
            trace = trace_mod.Tracer()
        elif trace is False:
            trace = None
        self.trace = trace
        self.trace_prefix = ""
        if trace is not None:
            idx = trace.register_cluster(self)
            if idx:          # 2nd+ cluster on one tracer: namespace it
                self.trace_prefix = f"c{idx}:"
        self.peer_transport = make_transport(peer_transport, svm)
        self.scheduler_policy = scheduler
        self.scheduler_quantum = scheduler_quantum
        # satellite fix (ISSUE 9): per-policy knobs are constructor
        # arguments, validated eagerly — no more monkeypatching module
        # constants. The legacy scheduler_quantum spelling stays valid
        # but may not conflict with the explicit knob.
        opts = validate_scheduler_opts(scheduler, scheduler_opts)
        if scheduler_quantum is not None and "quantum" in opts:
            raise ValueError(
                "pass either scheduler_quantum or "
                "scheduler_opts['quantum'], not both")
        self.scheduler_opts = opts
        self.nic_bandwidth = nic_bandwidth
        self.nic_ingress_bandwidth = nic_ingress_bandwidth
        # content-addressed cross-tenant buffer store (DESIGN.md §5):
        # opt-in so a store-less cluster keeps private-copy semantics
        # bit-exact (it is also the dedup benchmark's baseline)
        self.store = (BufferStore(self.clock, store_capacity)
                      if store or store_capacity is not None else None)
        # interning counters (DESIGN.md §8): hosts and sessions get
        # small-int ids for the hot-path tables; names stay the API
        self._sid_seq = 0
        self._skey_seq = 0
        self.hosts = {s.name: ServerHost(self, s) for s in servers}
        # cluster-wide placement control plane (DESIGN.md §6); 'pinned'
        # keeps every caller's hard-picked server bit-exactly
        self.placement = PlacementEngine(self, placement)
        self.p_links: dict = {}
        self._tenant_seq = 0      # monotonic: default names never recycle
        # kept for membership joins: a host admitted mid-run gets peer
        # links of the same spec the seed mesh was built with
        self.peer_link_spec = peer_link
        names = list(self.hosts)
        for i, a in enumerate(names):
            for b in names[i + 1:]:
                lk = self.p_links[(a, b)] = Link(self.clock,
                                                 peer_link.latency,
                                                 peer_link.bandwidth,
                                                 f"{a}<->{b}")
                if trace is not None:
                    lk.trace = trace
                    lk.trace_label = self.trace_prefix + lk.name
        self.clients: list = []
        # elastic membership control plane (DESIGN.md §7): seed hosts
        # start ACTIVE; join/drain/crash move them through the lifecycle
        self.membership = MembershipManager(self)
        for name in self.hosts:
            self.membership.register(name)
        # SLO admission control (DESIGN.md §10): screens tenants that
        # declare slo_ms at attach time. Off (None) by default — an
        # admission-less cluster admits everything, bit-exactly as
        # before.
        if admission is None or admission is False:
            self.admission = None
        elif isinstance(admission, AdmissionController):
            self.admission = admission
        else:
            self.admission = AdmissionController(
                self, None if admission is True else admission)

    # ---- membership verbs (delegates to the MembershipManager) ----
    def join_server(self, spec: ServerSpec, at: Optional[float] = None,
                    on_active: Optional[Callable] = None) -> None:
        """Admit a new server into the live cluster (DESIGN.md §7)."""
        self.membership.join(spec, at, on_active)

    def drain_server(self, name: str, at: Optional[float] = None,
                     on_complete: Optional[Callable] = None) -> None:
        """Gracefully decommission ``name``: requeue its unstarted
        commands, re-home its sole replicas, then retire it."""
        self.membership.drain(name, at, on_complete)

    def crash_server(self, name: str, at: Optional[float] = None) -> None:
        """Abruptly kill ``name``: links die, live events fail fast."""
        self.membership.crash(name, at)

    def _admit_host(self, spec: ServerSpec) -> ServerHost:
        """Membership join mechanics: build the host and wire fresh peer
        links to every current member. A rejoin of a DEAD name replaces
        the corpse's closed links — nothing resurrects."""
        name = spec.name
        host = ServerHost(self, spec)
        self.hosts[name] = host
        lat = self.peer_link_spec.latency
        bw = self.peer_link_spec.bandwidth
        for other in self.hosts:
            if other == name:
                continue
            key = ((other, name) if (other, name) in self.p_links
                   else (name, other))
            lk = self.p_links[key] = Link(self.clock, lat, bw,
                                          f"{key[0]}<->{key[1]}")
            if self.trace is not None:
                lk.trace = self.trace
                lk.trace_label = self.trace_prefix + lk.name
        return host

    def peer_link(self, a: str, b: str) -> Link:
        return self.p_links.get((a, b)) or self.p_links[(b, a)]

    def run(self, until: Optional[float] = None) -> float:
        """Drain the shared simulation (all attached tenants)."""
        return self.clock.run(until)

    def stats(self) -> dict:
        return {
            "time": self.clock.now,
            "clients": [c.name for c in self.clients],
            "sessions": {h: len(host.sessions)
                         for h, host in self.hosts.items()},
            "device_busy": {f"{h}/{d}": dev.busy_time
                            for h, host in self.hosts.items()
                            for d, dev in host.devices.items()},
            "scheduler": {f"{h}/{d}": {"policy": sch.policy.name,
                                       "dispatched": sch.dispatched,
                                       "preempted": sch.preempted,
                                       "queue_peak": sch.queue_peak,
                                       "queued_seconds":
                                           sch.queued_seconds()}
                          for h, host in self.hosts.items()
                          for d, sch in host.schedulers.items()},
            "nic_bytes": {h: (host.nic.bytes_sent if host.nic else 0)
                          for h, host in self.hosts.items()},
            "nic_busy": {h: (host.nic.busy_time if host.nic else 0.0)
                         for h, host in self.hosts.items()},
            "nic_in_bytes": {h: (host.nic_in.bytes_sent
                                 if host.nic_in else 0)
                             for h, host in self.hosts.items()},
            "nic_in_busy": {h: (host.nic_in.busy_time
                                if host.nic_in else 0.0)
                            for h, host in self.hosts.items()},
            "peer_link_bytes": {f"{a}-{b}": lk.bytes_sent
                                for (a, b), lk in self.p_links.items()},
            "store": self.store.stats() if self.store is not None else None,
            "placement": self.placement.stats(),
            "membership": self.membership.stats(),
            "admission": (self.admission.stats()
                          if self.admission is not None else None),
        }


class ServerSim:
    """One client session's view of the pocld daemon (the per-session
    half of the server split): replay dedup, remote-resolution tracking,
    and the dependency waiter table are all scoped to this session,
    while devices, run queues, and the NIC are shared on ``host``."""

    def __init__(self, rt: "ClientRuntime", host: ServerHost):
        self.rt = rt
        self.host = host
        self.name = host.name
        # interned session key (DESIGN.md §8): the scheduler run queues
        # key their per-tenant tables by this small int instead of the
        # (tenant name, server name) strings
        host.cluster._skey_seq += 1
        self.skey = host.cluster._skey_seq
        # observability (DESIGN.md §9): prefixed server label, built
        # once so the ready-hook never concatenates on the hot path
        self._tlabel = rt._tp + host.name
        self.session_id: Optional[bytes] = None
        self.processed: set = set()           # command ids (replay dedup)
        self.resolved_remote: set = set()     # remote event ids seen complete
        # dep event id -> [_Waiter, ...] in command-arrival order
        self._waiters: dict = {}
        self._ready: deque = deque()          # waiters with remaining == 0

    @property
    def devices(self) -> dict:
        return self.host.devices

    # ---- command arrival ----
    def receive_command(self, ev: Event, dev_name: str, deps: list):
        """``deps`` is [(dep_event_id, is_local_to_this_server), ...] as
        classified by the client at enqueue time."""
        if self.host.state == DEAD:
            # delivered to a corpse (the host retired or crashed while
            # the command was on the wire): bounce it back through
            # placement instead of executing or silently dropping. The
            # command id is unchanged, so if a copy was already
            # requeued the client-side guard dedups this one.
            events = self.rt.events
            for dep_id, _local in deps:
                dep = events.get(dep_id)
                if dep is not None:
                    dep.release()             # retained at _send_command
            self.rt._requeue_after_drain(ev, self.name, dev_name,
                                         [d for d, _l in deps])
            return
        if ev.command.id in self.processed:   # replayed after reconnect
            return
        if ev.status == ERROR:
            # failed client-side while the command was on the wire
            # (e.g. the tenant detached): never execute a dead command
            return
        self.processed.add(ev.command.id)
        ev.status = SUBMITTED
        ev.t_submitted = self.rt.clock.now
        w = _Waiter(ev, dev_name, self.host.dev_index.get(dev_name, -1))
        events = self.rt.events
        waiters = self._waiters
        resolved = self.resolved_remote
        remaining = 0
        for dep_id, local in deps:
            dep = events.get(dep_id)
            # ERROR counts as finished (the runtime's loose error-
            # dependency semantics, like _join_events): a dep that
            # failed while this command was on the wire must not leave
            # the waiter registered on an event whose callbacks already
            # flushed — that command would hang forever
            if dep is None or dep.status == COMPLETE \
                    or dep.status == ERROR \
                    or (not local and dep_id in resolved):
                if dep is not None:
                    dep.release()             # retained at _send_command
                continue
            lst = waiters.get(dep_id)
            if lst is None:
                lst = waiters[dep_id] = []
                if local:
                    # one callback per dep regardless of waiter count;
                    # fires wherever the event eventually completes
                    dep.on_complete(self._local_dep_complete)
            lst.append(w)
            remaining += 1
        if remaining:
            w.remaining = remaining
        else:
            self._ready.append(w)
        self._dispatch_ready()

    def _local_dep_complete(self, dep: Event):
        self._resolve_dep(dep.id)
        self._dispatch_ready()

    def _resolve_dep(self, dep_id: int):
        lst = self._waiters.pop(dep_id, None)
        if not lst:
            return
        dep = self.rt.events.get(dep_id)
        ready = self._ready
        for w in lst:
            w.remaining -= 1
            if not w.remaining:
                ready.append(w)
            if dep is not None:
                dep.release()                 # retained at _send_command
        # caller runs _dispatch_ready (keeps resolve usable mid-dispatch)

    def drain_waiters(self) -> list:
        """Server drain (DESIGN.md §7): empty the dependency waiter
        table, returning ``(ev, dev_name, pending_dep_ids)`` per
        distinct waiting command so the client can requeue each one on
        a survivor with its unresolved deps intact. The retained dep
        references are released here (the requeue's ``_send_command``
        re-retains what is still live); the old ``processed`` entry is
        dropped so nothing on this host claims the command anymore."""
        events = self.rt.events
        by_waiter: dict = {}          # id(w) -> (w, [dep ids])
        order: list = []
        for dep_id, lst in self._waiters.items():
            for w in lst:
                rec = by_waiter.get(id(w))
                if rec is None:
                    by_waiter[id(w)] = rec = (w, [])
                    order.append(rec)
                rec[1].append(dep_id)
                dep = events.get(dep_id)
                if dep is not None:
                    dep.release()             # retained at _send_command
        self._waiters.clear()
        out = []
        for w, dep_ids in order:
            self.processed.discard(w.ev.command.id)
            out.append((w.ev, w.dev_name, dep_ids))
        return out

    def notify_remote_complete(self, dep_id: int):
        # record only while the event is live: once retired, any command
        # arriving later resolves via the events-table miss, and a stale
        # entry here would never be cleaned (retirement already ran)
        if dep_id in self.rt.events:
            self.resolved_remote.add(dep_id)
        self._resolve_dep(dep_id)
        self._dispatch_ready()

    def _dispatch_ready(self):
        # drain in waves: execution may complete synchronously and
        # re-enter this method; a nested call drains the entries IT made
        # ready before the outer wave continues (matching the recursive
        # semantics of the pre-indexed implementation)
        while self._ready:
            wave = self._ready
            self._ready = deque()
            for w in wave:
                self._execute(w.ev, w.dev_name, w.dev_idx)

    # ---- execution ----
    def _execute(self, ev: Event, dev_name: str, dev_idx: int = -1):
        cmd = ev.command
        if type(cmd) is C.NDRangeKernel:
            # hot path: plain kernels skip the command-union isinstance
            # chain entirely and read cost fields as direct slots
            host = self.host
            if dev_idx < 0:
                dev_idx = host.dev_index[dev_name]
            dev = host.device_list[dev_idx]
            duration = cmd.duration
            cost = duration if duration is not None else \
                dev.kernel_cost(cmd.flops, cmd.bytes_moved, None)
        else:
            if isinstance(cmd, C.MigrateBuffer):
                self.rt._start_p2p_push(self, ev)
                return
            if isinstance(cmd, C.ReadBuffer):
                self.rt._start_read_return(self, ev)
                return
            host = self.host
            if dev_idx < 0:
                dev_idx = host.dev_index[dev_name]
            dev = host.device_list[dev_idx]
            if isinstance(cmd, C.WriteBuffer):
                cmd.buffer.set_data(np.asarray(cmd.data), self.name)
                ev.status = RUNNING
                ev.t_start = self.rt.clock.now
                self._complete(ev)
                return
            # BuiltinKernel / Marker / foreign commands: device time is
            # arbitrated across sessions by the host's per-device
            # scheduler — a ready command queues until the policy
            # dispatches it
            cost = dev.kernel_cost(getattr(cmd, "flops", 0.0),
                                   getattr(cmd, "bytes_moved", 0.0),
                                   getattr(cmd, "duration", None))
        dname = host.device_names[dev_idx]
        tr = self.rt._trace
        if tr is not None:
            # deps resolved, entering the device run queue: the one
            # lifecycle stamp the Event itself does not carry
            tr.cmd_ready(ev, self.rt.clock.now, self._tlabel, dname, cost)
        sch = host.scheduler_list[dev_idx]
        if sch.preempt_chunk is not None:
            # preemptive policy (llf, DESIGN.md §10): dispatch in
            # chunk-sized slices with preemption checks at the seams
            self._execute_preemptible(ev, dev, dname, sch, cost)
            return

        def run(release):
            if ev.status == ERROR:
                # failed while queued (crash fail-fast, detach) but the
                # entry outlived the sweep: never run a dead command —
                # and never let RUNNING overwrite a terminal status
                release()
                return
            ev.status = RUNNING

            def done():
                if ev.status == ERROR:
                    # failed while on the device (the host crashed or
                    # the tenant detached): the outputs must not be
                    # written — completion is void
                    release()
                    return
                self._finish_exec(ev)
                release()       # device freed: policy picks the next cmd

            ev.t_start, _ = dev.execute(cost, done)

        # the (event, device) tag lets a drain requeue scheduled-but-
        # unstarted commands without ever firing their run closures
        sch.submit(self, self.rt.weight, cost, run, (ev, dname),
                   ev.deadline)

    def _execute_preemptible(self, ev: Event, dev, dname: str, sch,
                             cost: float):
        """Chunked dispatch for preemptive policies (DESIGN.md §10).

        The kernel runs in ``preempt_chunk``-sized device slices; after
        each slice the scheduler is asked whether a queued command's
        laxity beats the running command's residual laxity
        (``deadline − remaining``). On preemption the remainder is
        requeued at its residual cost *before* the device is released,
        so the dispatcher's next pop compares remainder and preemptor
        head-to-head. The ``run`` closure may therefore be dispatched
        several times — once per resumption — but the outputs are
        written and the event completed exactly once, on the final
        slice; a drain that sweeps a preempted remainder requeues the
        whole command elsewhere via its (event, device) tag, same as
        any queued entry."""
        deadline = ev.deadline
        # residual-laxity base: a deadline-less command preempts never
        # and yields always (key inf), matching its queue priority
        key_base = deadline if deadline is not None else _INF
        chunk = sch.preempt_chunk
        weight = self.rt.weight
        state = [cost]                # remaining device-seconds

        def run(release):
            if ev.status == ERROR:
                release()
                return
            ev.status = RUNNING
            slice_next(release)

        def slice_next(release):
            remaining = state[0]
            this = remaining if remaining <= chunk else chunk

            def slice_done():
                if ev.status == ERROR:
                    # crashed/detached mid-kernel: outputs unwritten,
                    # completion void, device freed
                    release()
                    return
                left = state[0] - this
                state[0] = left
                if left <= 0.0:
                    self._finish_exec(ev)
                    release()
                    return
                if sch.should_preempt(key_base - left):
                    sch.requeue_preempted(self, weight, left, run,
                                          (ev, dname), deadline)
                    release()
                    return
                slice_next(release)

            t0, _ = dev.execute(this, slice_done)
            tr = self.rt._trace
            if tr is not None:
                # actual device occupancy: under preemption the wall
                # interval [t_start, t_end] interleaves with other
                # commands; the slices are the ground truth the
                # critical-path analyzer tiles with (DESIGN.md §11)
                tr.exec_slice(ev, t0, t0 + this)
            if ev.t_start == 0.0:
                ev.t_start = t0   # first slice only; resumes keep it

        sch.submit(self, weight, cost, run, (ev, dname), deadline)

    def _finish_exec(self, ev: Event):
        """The end of a command's device time, on either path (the last
        slice of a preemptible one): call the kernel's ``fn`` and commit
        its outputs to their buffers here, or mark the outputs valid
        here, then complete the event. The call and the commit are the
        ``pocl.kernel`` and ``pocl.commit`` spans (DESIGN.md §9). The
        commit copies nothing: an output that comes back as a
        ``jax.Array`` stays on its device, and a host output stays as it
        was returned, until something needs host bytes (``Buffer.host()``):
        a read (the ``pocl.read`` span), a content size, a host consumer.
        A kernel that takes a device output as input gets the device
        array itself. An output whose content-size buffer is among the
        same kernel's outputs is cut to the rows of its used prefix when
        it is made host bytes; a device array still leaves its device in
        one whole copy, which on a TPU v5e host is quicker than copying
        only the prefix, in pieces or after a slicing program (PERF.md
        §5)."""
        cmd = ev.command
        fn = cmd.fn if isinstance(cmd, C.NDRangeKernel) else None
        if fn is not None:
            # a content-sized input waiting for its cut is cut first, so
            # a kernel sees the same rows whatever wrote its input
            ins = [b.host() if b.cut else b.data for b in cmd.inputs]
            with span("pocl.kernel", event=ev.id, server=self.name):
                outs = fn(*ins)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            with span("pocl.commit", event=ev.id):
                for b, arr in zip(cmd.outputs, outs):
                    if not isinstance(arr, jax.Array):
                        arr = np.asarray(arr)
                    csb = b.content_size_buffer
                    # its size comes from this kernel too: cut to its
                    # used prefix when made host bytes
                    b.set_data(arr, self.name, cut=csb is not None
                               and arr.ndim > 0
                               and any(csb is o for o in cmd.outputs))
        else:
            for b in getattr(cmd, "outputs", ()):
                b.invalidate_except(self.name)
                b.valid_on = {self.name}
        self._complete(ev)

    def _complete(self, ev: Event):
        if ev.status == ERROR:
            # failed while executing or queued (tenant detach fails all
            # live events; the non-preemptive in-service command still
            # runs to completion) — completion is void, but the caller's
            # device release must still run
            return
        ev.complete(self.rt.clock.now)
        # resolve locally first: dependents on THIS server may have
        # classified the event as remote (e.g. a migration that finishes
        # on the destination) — no wire cost for self-notification
        self.notify_remote_complete(ev.id)
        self.rt._broadcast_completion(self, ev)


class Session:
    """Client-side view of one server connection (paper §4.3).

    ``replay_window`` bounds the unacked-command replay buffer; it is a
    runtime knob (``ClientRuntime(replay_window=...)``) rather than a
    hard-coded 64, and ``stats()['replay_window']`` surfaces the
    configured size next to the overflow counter."""

    def __init__(self, name: str, replay_window: int = 64):
        self.name = name
        self.session_id = bytes(16)           # all-zeroes until handshake
        self.available = False
        self.replay: deque = deque(maxlen=replay_window)  # unacked cmds
        self.lost_unacked = 0                  # overflowed replay slots

    def record(self, item):
        """Append to the replay window, dropping already-finished entries
        first. Overflow means an UNACKED command falls out of the window
        and could not be replayed after a reconnect — that loss used to
        be silent; now it is counted and logged once per session."""
        buf = self.replay
        while buf:
            s = buf[0][0].status
            if s != COMPLETE and s != ERROR:
                break
            buf.popleft()
        if buf.maxlen is not None and len(buf) == buf.maxlen:
            if not self.lost_unacked:
                log.warning(
                    "session %s: replay window full (maxlen=%d); dropping "
                    "oldest unacked command — it cannot be replayed after "
                    "a reconnect", self.name, buf.maxlen)
            self.lost_unacked += 1
        buf.append(item)


class ClientRuntime:
    """The PoCL remote client driver (host side of the OpenCL API)."""

    def __init__(self, servers: Optional[Sequence[ServerSpec]] = None,
                 client_link: LinkSpec = LinkSpec(),
                 peer_link: Optional[LinkSpec] = None,
                 transport: str = "tcp",
                 peer_transport: Optional[str] = None,
                 svm: bool = False,
                 scheduling: str = "decentralized",   # | 'client'
                 p2p_migration: bool = True,
                 completion_routing: str = "subscription",  # | 'broadcast'
                 local_device: Optional[DeviceSpec] = None,
                 cluster: Optional[Cluster] = None,
                 name: Optional[str] = None,
                 weight: float = 1.0,
                 slo_ms: Optional[float] = None,
                 slo_probe: Optional[dict] = None,
                 replay_window: int = 64,
                 reconnect_retries: int = 4,
                 reconnect_backoff: float = 2e-3,
                 scheduler: Optional[str] = None,
                 scheduler_quantum: Optional[float] = None,
                 scheduler_opts: Optional[dict] = None,
                 nic_bandwidth: Optional[float] = None,
                 nic_ingress_bandwidth: Optional[float] = None,
                 store: Optional[bool] = None,
                 store_capacity: Optional[float] = None,
                 placement: Optional[str] = None,
                 admission=None,
                 trace=None):
        if completion_routing not in ("subscription", "broadcast"):
            raise ValueError(f"unknown completion_routing "
                             f"{completion_routing!r}")
        if not weight > 0.0:
            raise ValueError(f"weight must be positive, got {weight!r}")
        # per-tenant latency target (DESIGN.md §10): every command this
        # tenant enqueues carries the absolute deadline
        # ``t_queued + slo_ms``; deadline-aware schedulers order by it,
        # admission control screens against it, and the client-ack path
        # scores violations against it. None = no target (bit-exact
        # pre-SLO behavior).
        if slo_ms is not None and not slo_ms > 0.0:
            raise ValueError(f"slo_ms must be positive, got {slo_ms!r}")
        if slo_probe is not None:
            if slo_ms is None:
                raise ValueError("slo_probe requires slo_ms")
            unknown = sorted(set(slo_probe) - {"cost_s", "nbytes"})
            if unknown:
                raise ValueError(f"unknown slo_probe keys: {unknown} "
                                 f"(allowed: ['cost_s', 'nbytes'])")
            for k, v in slo_probe.items():
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or v < 0:
                    raise ValueError(
                        f"slo_probe[{k!r}] must be a non-negative "
                        f"number, got {v!r}")
        if cluster is None:
            if servers is None:
                raise ValueError("pass server specs or an existing cluster")
            cluster = Cluster(servers,
                              peer_link=peer_link if peer_link is not None
                              else LinkSpec(latency=61e-6,
                                            bandwidth=100e6 / 8),
                              peer_transport=peer_transport or transport,
                              svm=svm, scheduler=scheduler or "fifo",
                              scheduler_quantum=scheduler_quantum,
                              scheduler_opts=scheduler_opts,
                              nic_bandwidth=nic_bandwidth,
                              nic_ingress_bandwidth=nic_ingress_bandwidth,
                              store=bool(store),
                              store_capacity=store_capacity,
                              placement=placement or "pinned",
                              admission=admission,
                              trace=trace)
            self._placement_policy = None   # cluster default covers it
        else:
            if servers is not None:
                raise ValueError("pass either servers or cluster, not both")
            ignored = {"peer_link": peer_link,
                       "peer_transport": peer_transport,
                       "scheduler": scheduler,
                       "scheduler_quantum": scheduler_quantum,
                       "scheduler_opts": scheduler_opts,
                       "nic_bandwidth": nic_bandwidth,
                       "nic_ingress_bandwidth": nic_ingress_bandwidth,
                       "store": store,
                       "store_capacity": store_capacity,
                       "admission": admission,
                       "trace": trace}
            bad = [k for k, v in ignored.items() if v is not None]
            if bad:
                # these configure the shared substrate — accepting them
                # here would silently measure a different cluster than
                # the caller asked for
                raise ValueError(
                    f"{', '.join(sorted(bad))} are cluster-level settings; "
                    f"pass them to Cluster(), not to a ClientRuntime "
                    f"attaching to an existing one")
            # placement, by contrast, is legitimately per-tenant when
            # attaching: it decides where THIS tenant's kernels run,
            # reading the same shared telemetry (DESIGN.md §6)
            self._placement_policy = (make_placement_policy(placement)
                                      if placement is not None else None)
            if self._placement_policy is not None and \
                    type(self._placement_policy) is not PinnedPolicy:
                # someone will read the telemetry now: start keeping
                # the engine's outstanding tally (stays on for good)
                cluster.placement.telemetry_active = True
        self.cluster = cluster
        self.clock = cluster.clock
        # default names come from a monotonic counter, not the live
        # client list — detach() shrinks the list, and a recycled "ue2"
        # would alias a departed tenant in stats and error messages
        self.name = name if name is not None else f"ue{cluster._tenant_seq}"
        cluster._tenant_seq += 1
        # observability (DESIGN.md §9): the hot-path gate is one slot
        # load + None check; labels are precomputed (cluster-namespace
        # prefix + tenant name) so hooks never build strings
        self._trace = cluster.trace
        self._tp = cluster.trace_prefix
        self._tlabel = self._tp + self.name
        self.weight = weight                  # fair-scheduler share
        self.transport = make_transport(transport, svm)
        self.peer_transport = cluster.peer_transport
        self.scheduling = scheduling
        self.p2p_migration = p2p_migration
        self.completion_routing = completion_routing
        # dispatch hot-path constants (DESIGN.md §8): the zero-payload
        # command cost and the completion cost are per-transport
        # constants, and the scheduling mode is fixed at construction —
        # the per-send ternaries and cost calls fold to these reads.
        # Each derived float is computed with the exact operand pair the
        # per-send expression used, so timestamps are bit-identical.
        _c0 = self.transport.command_cost(0.0)
        self._cmd_cost0 = _c0
        self._submit_overhead0 = CLIENT_SUBMIT + _c0.sender_cpu
        self._recv_delay0 = _c0.receiver_cpu + DISPATCH
        self._comp_cost = (self.peer_transport
                           if scheduling == "decentralized"
                           else self.transport).completion_cost()
        self._complete_overhead = COMPLETE_WRITE + self._comp_cost.sender_cpu
        # every client link (seed and joined alike) is built from
        # `client_link`, so the client-side wire inflation factor is a
        # per-runtime constant too
        self._cscale0 = wire_scale(self.transport, client_link.bandwidth)
        self.servers = {h.name: ServerSim(self, h)
                        for h in cluster.hosts.values()}
        self.events: dict = {}
        # event id -> {server names holding dependents of it}; registered
        # at enqueue time so a completion is signaled "directly to the
        # target server" (§5.2) instead of broadcast to every peer
        self._subs: dict = {}
        self.client_completion_msgs = 0       # server → client completes
        self.peer_completion_msgs = 0         # server → peer notifications
        self.client_routed_completion_msgs = 0  # client → server forwards
        self.sessions = {s: Session(s, replay_window)
                         for s in self.servers}
        # kept for membership joins: a server admitted mid-run gets a
        # session and access link of the same spec the seed set did
        self._replay_window = replay_window
        self._client_link_spec = client_link
        # bounded reconnect (DESIGN.md §7): retries with exponential
        # backoff instead of hanging on a server that never comes back
        if reconnect_retries < 0:
            raise ValueError(f"reconnect_retries must be >= 0, "
                             f"got {reconnect_retries!r}")
        if not reconnect_backoff > 0.0:
            raise ValueError(f"reconnect_backoff must be positive, "
                             f"got {reconnect_backoff!r}")
        self.reconnect_retries = reconnect_retries
        self.reconnect_backoff = reconnect_backoff
        self.reconnect_attempts: dict = {s: 0 for s in self.servers}
        self.reconnect_failures: dict = {}    # server -> surfaced reason
        # drain requeue dedup (DESIGN.md §7): a command bounced off a
        # draining/dead host is re-placed at most once — a replayed or
        # in-flight duplicate arriving later finds the id here
        self._requeued: set = set()
        self.local_device = DeviceSim(
            self.clock, "local",
            *( (local_device.flops, local_device.mem_bw)
               if local_device else (1e12, 50e9) ))
        # links: client links are per tenant (each UE brings its own
        # radio/access link); the peer mesh is the cluster's, shared
        self.c_links = {s: Link(self.clock, client_link.latency,
                                client_link.bandwidth,
                                f"{self.name}<->{s}")
                        for s in self.servers}
        tr = self._trace
        if tr is not None:
            for lk in self.c_links.values():
                lk.trace = tr
                lk.trace_label = self._tp + lk.name
        self.p_links = cluster.p_links
        cluster.clients.append(self)
        self._buffers: list[Buffer] = []
        self._mr_registered: set = set()
        # (buf.id, dst server) -> (migration Event, buf.version snapshot);
        # lets back-to-back requests for the same payload coalesce onto
        # the transfer already in flight (entries drop on completion, and
        # a version mismatch — the buffer was written since — makes the
        # entry stale so a fresh transfer is started instead)
        self._inflight_migrations: dict = {}
        # data-plane scoreboard (stats())
        self.bytes_on_wire = 0.0              # migration payload wire bytes
        self.upload_bytes_on_wire = 0.0       # write payload wire bytes
        self.migrations_coalesced = 0         # requests served by in-flight
        self.chunks_in_flight = 0             # gauge: chunks on any link
        self.peak_chunks_in_flight = 0
        # content-addressed store scoreboard (this tenant's share of the
        # cluster counters in BufferStore.stats())
        self.dedup_hits = 0                   # transfers served by a replica
        self.dedup_bytes_saved = 0.0          # payload bytes never sent
        self.detached = False                 # tenant lifecycle (detach())
        # SLO plumbing (DESIGN.md §10). ``_slo_s`` is the effective
        # per-command budget in seconds (None = no target: the deadline
        # stamp, the reap-time scoring, and the admission feedback are
        # all skipped behind one load + branch). Admission screening
        # happens here — after the links/sessions exist (the probe math
        # reads them) but before the handshake spends simulated time —
        # and may degrade the budget or reject the tenant outright.
        self.slo_ms = slo_ms                  # requested target (ms)
        self._slo_s = slo_ms * 1e-3 if slo_ms is not None else None
        self._slo_probe = dict(slo_probe) if slo_probe else None
        self._slo_class = (f"{slo_ms:g}ms" if slo_ms is not None
                           else None)
        self.admission = None                 # AdmissionDecision or None
        self.slo_commands = 0                 # completions scored
        self.slo_violations = 0               # ... that missed deadline
        ctrl = cluster.admission
        if ctrl is not None and self._slo_s is not None:
            decision = ctrl.request(self)
            self.admission = decision
            tr = self._trace
            if tr is not None:
                # verdict marker (admit/degrade/reject + predicted
                # latency) lands in the trace even for rejects — the
                # tenant then leaves before spending simulated time
                tr.admission(self._tlabel, decision)
            if decision.status == REJECT:
                # leave no residue on the shared cluster: the sessions
                # and links built above were never handshaken and spend
                # no simulated time; only the client list saw us
                cluster.clients.remove(self)
                self.detached = True
                raise AdmissionRejected(self.name, decision)
            if decision.status == DEGRADE:
                self._slo_s = decision.slo_s
                self._slo_class = f"{decision.slo_s * 1e3:g}ms"
        # connect (handshake: rtt + session id assignment) — run the
        # clock just far enough that all of THIS client's sessions are
        # established, as clCreateContext would block. A full drain here
        # would fast-forward every other tenant's in-flight work on a
        # shared cluster, so a dynamically-arriving UE could never
        # contend with work already queued. Hosts that are not live
        # (DEAD/DRAINING members of an elastic cluster) return None —
        # their sessions simply stay unavailable.
        deadlines = [d for d in (self._handshake(s) for s in self.servers)
                     if d is not None]
        if deadlines:
            self.clock.run(until=max(deadlines))

    # ------------------------------------------------------------------
    def peer_link(self, a: str, b: str) -> Link:
        return self.cluster.peer_link(a, b)

    def _nic_in(self, server: str) -> Optional[NIC]:
        """The receiving host's shared ingress port (None when the
        cluster models no ingress NIC). Every send that terminates at a
        server passes through it; sends to the client do not — the UE
        side has no modeled port."""
        return self.cluster.hosts[server].nic_in

    def _handshake(self, server: str) -> Optional[float]:
        """Returns the sim time at which the session becomes available,
        or None when no session can be established (host not live, or
        the access link is down)."""
        if self.cluster.hosts[server].state not in (ACTIVE, JOINING):
            return None
        sess = self.sessions[server]

        def done():
            sess.session_id = secrets.token_bytes(16)
            srv = self.servers[server]
            srv.session_id = sess.session_id
            # §4.3: the daemon's session table is keyed by session id —
            # the id (not the transport address) is what a reconnect
            # from a new IP presents to resume this session's state
            srv.host.sessions[sess.session_id] = srv
            sess.available = True

        return self.c_links[server].send(64, done,
                                         ingress=self._nic_in(server))

    # ---- elastic membership hooks (DESIGN.md §7) ----
    def _attach_server(self, host: ServerHost) -> float:
        """A server joined the live cluster: build this tenant's session
        state and access link to it and handshake, exactly as the
        constructor does for the seed set. Returns the sim time the
        session becomes available (now, if the handshake cannot start).
        A rejoin of a previously-dead name replaces the corpse's
        session wholesale — nothing resurrects."""
        name = host.name
        self.servers[name] = ServerSim(self, host)
        self.sessions[name] = Session(name, self._replay_window)
        lk = self.c_links[name] = Link(self.clock,
                                       self._client_link_spec.latency,
                                       self._client_link_spec.bandwidth,
                                       f"{self.name}<->{name}")
        if self._trace is not None:
            lk.trace = self._trace
            lk.trace_label = self._tp + lk.name
        self.reconnect_attempts.setdefault(name, 0)
        self.reconnect_failures.pop(name, None)
        d = self._handshake(name)
        return d if d is not None else self.clock.now

    def _server_retired(self, name: str) -> None:
        """A drain finished: the host leaves cleanly — every command
        was executed or requeued and every sole replica re-homed, so
        this is bookkeeping: close the session and link, drop replica
        validity (the canonical bytes live on the ``Buffer``), and
        defensively fail anything that still targets the host."""
        sess = self.sessions.get(name)
        if sess is not None:
            sess.available = False
            sess.replay.clear()
            sess.session_id = bytes(16)
        srv = self.servers.get(name)
        if srv is not None:
            srv.processed.clear()
            srv.resolved_remote.clear()
            srv._waiters.clear()      # drained: empty unless raced
            srv._ready.clear()
            srv.session_id = None
        link = self.c_links.get(name)
        if link is not None:
            link.close()
        for b in self._buffers:
            b.valid_on.discard(name)
        self._fail_events_on(name, f"server {name} retired")

    def _server_crashed(self, name: str) -> None:
        """Abrupt server loss: every live event targeting the host
        fails fast — dependents on survivors observe ERROR through the
        normal completion routing instead of hanging — the session is
        destroyed (a rejoin is a FRESH server), and replica validity
        drops. Recovery (retry, re-place, reconnect with backoff) is
        the client application's move, §4.3-style."""
        sess = self.sessions.get(name)
        if sess is not None:
            sess.available = False
            sess.replay.clear()
            sess.session_id = bytes(16)
        srv = self.servers.get(name)
        if srv is not None:
            # commands waiting on deps die with the host; release the
            # dep references they retained or those events never retire
            for dep_id, lst in list(srv._waiters.items()):
                dep = self.events.get(dep_id)
                if dep is not None:
                    for _w in lst:
                        dep.release()
            srv._waiters.clear()
            srv._ready.clear()
            srv.processed.clear()
            srv.resolved_remote.clear()
            srv.session_id = None
        link = self.c_links.get(name)
        if link is not None:
            link.close()              # kills mid-flight chunked uploads
        for b in self._buffers:
            b.valid_on.discard(name)
        self._fail_events_on(name, f"server {name} crashed")

    def _fail_events_on(self, name: str, reason: str) -> None:
        """Fail-fast every live event executing on ``name`` or moving
        data into it. The in-flight migration table self-cleans: fail()
        fires the entry's drop callback."""
        now = self.clock.now
        for ev in list(self.events.values()):
            if ev.status in (COMPLETE, ERROR):
                continue
            if ev.server == name or \
                    getattr(ev.command, "dst_server", None) == name:
                ev.fail(now, reason)
                self._route_completion_via_client(ev)
                ev.release()          # no completion ack will ever come

    def _pick_failover_server(self, exclude: Optional[str] = None) \
            -> Optional[str]:
        """Least-loaded survivor this tenant can use (drain/crash
        failover): an available session on an ACTIVE host, by (queue
        depth, name) so the choice is deterministic."""
        engine = self.cluster.placement
        eligible = self.cluster.membership.is_eligible
        best = None
        best_key = None
        for s in sorted(self.sessions):
            if s == exclude or not eligible(s):
                continue
            if not self.sessions[s].available:
                continue
            key = (engine.queue_depth(s), s)
            if best_key is None or key < best_key:
                best, best_key = s, key
        return best

    def _requeue_after_drain(self, ev: Event, old_server: str,
                             dev_name: str, dep_ids: list) -> None:
        """A draining (or just-dead) server handed back a scheduled-
        but-unstarted command: re-place it on a survivor. The command
        id is unchanged, so the §4.3 dedup guarantees exactly-once —
        the old host's tables dropped the command before this runs, and
        ``_requeued`` stops a replayed or in-flight duplicate from
        bouncing a second time."""
        if self.detached or ev.status in (COMPLETE, ERROR):
            return
        if ev.id in self._requeued:
            return                    # already re-placed: this copy is
        self._requeued.add(ev.id)     # the §4.3 duplicate — drop it
        tr = self._trace
        if tr is not None:
            tr.requeue(ev, self.clock.now, self._tp + old_server, "drain")
        cmd = ev.command
        if isinstance(cmd, C.MigrateBuffer):
            self._requeue_migration(ev, cmd)
            return
        target = self._pick_failover_server(exclude=old_server)
        if target is None:
            ev.fail(self.clock.now,
                    f"server {old_server} left and no failover target")
            self._route_completion_via_client(ev)
            ev.release()              # no completion ack will ever come
            return
        dep_ids = list(dep_ids)
        payload = 0.0
        if isinstance(cmd, C.NDRangeKernel):
            # the kernel's implicit input migrations targeted the old
            # host; re-derive them for the new one
            for b in cmd.inputs:
                if target not in b.valid_on:
                    dep_ids.append(self.enqueue_migration(b, target).id)
        elif isinstance(cmd, C.WriteBuffer):
            payload = cmd.nbytes      # the bytes go to the new host now
            cmd.buffer.valid_on.discard(old_server)
            cmd.buffer.valid_on.add(target)
        if dev_name and \
                dev_name not in self.cluster.hosts[target].devices:
            dev_name = ""             # heterogeneous fleet: default dev
        ev.server = target
        self._send_command(ev, target, dev_name, dep_ids, payload=payload)

    def _requeue_migration(self, ev: Event, cmd) -> None:
        """Re-drive a migration whose source host left: a fresh
        enqueue picks a surviving replica (or falls back to a client
        upload) and the result is mirrored onto the original handle."""
        buf, dst = cmd.buffer, cmd.dst_server
        # the handle must leave the coalescing table first: the fresh
        # migration would otherwise coalesce onto the very event it is
        # meant to complete
        self._drop_inflight((buf.id, dst), ev)
        retry = self.enqueue_migration(buf, dst)

        def mirror(r):
            if ev.status in (COMPLETE, ERROR):
                return
            if r.status == ERROR:
                ev.fail(self.clock.now, r.error or "migration failed")
            else:
                ev.complete(self.clock.now)
            self._route_completion_via_client(ev)
            ev.release()              # client observed completion directly

        retry.on_complete(mirror)

    # ---- buffers ----
    def create_buffer(self, nbytes: int, content_size_buffer: Buffer = None,
                      name: str = "") -> Buffer:
        b = Buffer(nbytes=nbytes, content_size_buffer=content_size_buffer,
                   name=name)
        b.valid_on = {"client"}
        self._buffers.append(b)
        return b

    # ---- event lifecycle ----
    def _register_event(self, ev: Event) -> Event:
        ev.t_queued = self.clock.now
        slo = self._slo_s
        if slo is not None:         # deadline stamp (DESIGN.md §10)
            ev.deadline = ev.t_queued + slo
        ev.retain()                 # client hold until completion observed
        ev.on_retire = self._retire
        self.events[ev.id] = ev
        tr = self._trace
        if tr is not None:
            tr.cmd_queued(ev, self._tlabel)
        return ev

    def _new_event(self, cmd, server: str) -> Event:
        # _register_event, inlined (one enqueue-path call per command)
        ev = Event(command=cmd, server=server)
        ev.t_queued = self.clock.now
        slo = self._slo_s
        if slo is not None:         # deadline stamp (DESIGN.md §10)
            ev.deadline = ev.t_queued + slo
        ev._refs += 1               # client hold until completion observed
        ev.on_retire = self._retire
        self.events[ev.id] = ev
        tr = self._trace
        if tr is not None:
            tr.cmd_queued(ev, self._tlabel)
        return ev

    def _retire(self, ev: Event):
        """Last reference dropped on a finished event: remove it from
        every runtime table so long runs stay memory-bounded. The Event
        object itself stays valid for user-held handles."""
        self.events.pop(ev.id, None)
        self._subs.pop(ev.id, None)
        cmd_id = getattr(ev.command, "id", None)
        for srv in self.servers.values():
            srv.resolved_remote.discard(ev.id)
            if cmd_id is not None:
                srv.processed.discard(cmd_id)

    # ---- enqueue API ----
    def enqueue_kernel(self, server: str, device: str = "",
                       fn: Optional[Callable] = None,
                       inputs: Sequence[Buffer] = (),
                       outputs: Sequence[Buffer] = (),
                       flops: float = 0.0, bytes_moved: float = 0.0,
                       duration: Optional[float] = None,
                       wait_for: Sequence[Event] = (),
                       name: str = "kernel",
                       pin: bool = False) -> Event:
        """Enqueue a kernel; implicit migrations are added for any input
        not valid on the target server (standard OpenCL semantics).

        ``server`` is the *requested* placement: the cluster's placement
        engine (DESIGN.md §6) may redirect the kernel — and therefore
        its implicit migrations — to a better host. The default
        ``pinned`` policy always honors the request, preserving the
        hard-picked behavior bit-exactly; ``pin=True`` bypasses the
        engine for this one kernel regardless of policy (used by the
        redundant-dispatch race, whose whole point is landing each copy
        on a DIFFERENT explicitly-chosen server)."""
        with span("pocl.enqueue_kernel") as sp:
            self._check_live()
            engine = self.cluster.placement
            if not pin:
                server = engine.place(self, server, device, inputs, flops,
                                      bytes_moved, duration)
            if not self.sessions[server].available:
                raise DeviceUnavailable(server)
            deps = list(wait_for)
            for b in inputs:
                if server not in b.valid_on:
                    deps.append(self.enqueue_migration(b, server,
                                                       wait_for=wait_for))
            # copy-on-write (DESIGN.md §5): writing an output that holds
            # shared content forks it to a private buffer first — the shared
            # replicas stay intact for the other holders, and the fork's
            # device-side copy (read + write of the buffer) is charged to
            # this kernel's memory traffic (a ``duration`` override absorbs
            # it, like every other analytic cost term)
            store = self.cluster.store
            if store is not None:
                for b in outputs:
                    if store.cow_fork(b):
                        bytes_moved += 2.0 * b.nbytes
            cmd = C.NDRangeKernel(fn=fn, inputs=tuple(inputs),
                                  outputs=tuple(outputs), flops=flops,
                                  bytes_moved=bytes_moved, duration=duration,
                                  name=name)
            ev = self._new_event(cmd, server)
            sp.set_metadata(event=ev.id)
            if engine.telemetry_active:
                engine.record(server,
                              engine.kernel_cost(server, device, flops,
                                                 bytes_moved, duration), ev)
            self._send_command(ev, server, device, [d.id for d in deps])
            for b in outputs:
                # eager client-side clobber: later enqueues must neither read
                # stale replicas nor coalesce onto migrations of the old
                # contents, so the version bumps at enqueue time too
                b.invalidate_except(server)
            return ev

    def enqueue_many(self, server: str, kernels: Sequence[dict],
                     device: str = "", pin: bool = False) -> list:
        """Batched ``enqueue_kernel``: one call, many kernels, identical
        schedule (DESIGN.md §8).

        ``kernels`` is a sequence of dicts carrying ``enqueue_kernel``'s
        keyword arguments (``fn``, ``inputs``, ``outputs``, ``flops``,
        ``bytes_moved``, ``duration``, ``wait_for``, ``name``; optional
        per-kernel ``server``/``device``/``pin`` overriding the
        call-level defaults). ``wait_for`` entries may be Event objects
        or **integer indices** into this batch, referencing an earlier
        kernel's event — the natural way to express a dependency chain
        built in one call. Returns the Events in batch order.

        Produces the *exact* sequence of clock-schedule calls the
        equivalent ``enqueue_kernel`` loop would (same timestamps, same
        seq numbers — bit-exact), because no simulated time passes
        between batch entries: the liveness check, placement policy
        resolution, placement candidate lists (per named device), and
        table lookups are hoisted out of the loop, while everything
        observable — placement decisions and counters, implicit
        migrations, CoW forks, telemetry records, wire sends, eager
        invalidation — runs per kernel in the loop's order."""
        self._check_live()
        engine = self.cluster.placement
        policy = self._placement_policy or engine.default_policy
        pinned_policy = type(policy) is PinnedPolicy
        telemetry = engine.telemetry_active
        sessions = self.sessions
        store = self.cluster.store
        new_event = self._new_event
        send = self._send_command
        cand_cache: dict = {}          # device -> hoisted candidate list
        results: list = []
        for spec in kernels:
            get = spec.get
            srv = get("server", server)
            dev = get("device", device)
            inputs = get("inputs", ())
            outputs = get("outputs", ())
            flops = get("flops", 0.0)
            bytes_moved = get("bytes_moved", 0.0)
            duration = get("duration")
            wait_for = [results[w] if type(w) is int else w
                        for w in get("wait_for", ())]
            if not (pin or get("pin", False)):
                if pinned_policy:
                    # inlined PlacementEngine.place fast path: counters
                    # only, the requested server stands
                    engine.decisions += 1
                    engine.placed_local += 1
                else:
                    cands = cand_cache.get(dev)
                    if cands is None:
                        cands = cand_cache[dev] = \
                            engine.candidates_for(self, dev)
                    srv = engine.place(self, srv, dev, inputs, flops,
                                       bytes_moved, duration,
                                       candidates=cands)
            if not sessions[srv].available:
                raise DeviceUnavailable(srv)
            if inputs:
                deps = list(wait_for)
                for b in inputs:
                    if srv not in b.valid_on:
                        deps.append(self.enqueue_migration(
                            b, srv, wait_for=wait_for))
            else:
                deps = wait_for     # fresh private list: no copy needed
            if store is not None:
                for b in outputs:
                    if store.cow_fork(b):
                        bytes_moved += 2.0 * b.nbytes
            cmd = C.NDRangeKernel(get("fn"), tuple(inputs),
                                  tuple(outputs), flops, bytes_moved,
                                  duration, get("name", "kernel"))
            ev = new_event(cmd, srv)
            if telemetry:
                engine.record(srv,
                              engine.kernel_cost(srv, dev, flops,
                                                 bytes_moved, duration),
                              ev)
            send(ev, srv, dev, [d.id for d in deps])
            for b in outputs:
                b.invalidate_except(srv)
            results.append(ev)
        return results

    def enqueue_write(self, server: str, buf: Buffer, data,
                      wait_for: Sequence[Event] = ()) -> Event:
        with span("pocl.enqueue_write") as sp:
            self._check_live()
            cmd = C.WriteBuffer(buffer=buf, data=data,
                                nbytes=np.asarray(data).nbytes)
            ev = self._new_event(cmd, server)
            sp.set_metadata(event=ev.id)
            dep_ids = [d.id for d in wait_for]
            store = self.cluster.store
            if store is not None and cmd.nbytes > 0:
                self._send_write_via_store(ev, server, buf, cmd, dep_ids,
                                           store)
            else:
                self._send_command(ev, server, "", dep_ids,
                                   payload=cmd.nbytes)
            buf.valid_on = {server, "client"}
            buf.version += 1        # eager: new contents are on their way
            return ev

    def _record_dedup(self, store: BufferStore, entry, nbytes: float):
        store.record_dedup(entry, nbytes)
        self.dedup_hits += 1
        self.dedup_bytes_saved += nbytes
        tr = self._trace
        if tr is not None:
            tr.dedup(self.clock.now, self._tlabel, nbytes)

    def _unrecord_dedup(self, store: BufferStore, nbytes: float):
        store.unrecord_dedup(nbytes)
        self.dedup_hits -= 1
        self.dedup_bytes_saved -= nbytes
        tr = self._trace
        if tr is not None:
            tr.dedup(self.clock.now, self._tlabel, -nbytes)

    def _send_write_via_store(self, ev: Event, server: str, buf: Buffer,
                              cmd, dep_ids: list,
                              store: BufferStore) -> None:
        """Content-addressed upload (DESIGN.md §5). The payload digest is
        computed at enqueue, like the command struct: if an identical
        replica — any tenant's — is already resident on the target
        server, only the command struct + digest cross the wire; if one
        is in flight there, the command gates on its arrival instead of
        re-sending the bytes; otherwise the payload is paid once and the
        landed replica registers with the store for everyone after."""
        key = content_digest(cmd.data)
        entry = store.attach(buf, key, cmd.nbytes)
        # +1 because enqueue_write bumps AFTER this resolution: the
        # snapshot must equal the version this write itself installs,
        # so only a LATER write of the buffer invalidates a gate
        self._resolve_store_write(ev, server, buf, cmd, dep_ids, store,
                                  entry, buf.version + 1)

    def _resolve_store_write(self, ev: Event, server: str, buf: Buffer,
                             cmd, dep_ids: list, store: BufferStore,
                             entry, snap: int) -> None:
        """Resolve a store-attached write against the entry's CURRENT
        replica state (re-entered when a ride dies, so a fresh check —
        a surviving rider may have restarted the upload we can gate
        on instead of each rider paying its own copy). ``snap`` is the
        buffer version this write installs: a later write bumping past
        it supersedes this one while it gates."""
        if server in entry.valid_on:
            self._record_dedup(store, entry, cmd.nbytes)
            self._send_command(ev, server, "", dep_ids,
                               extra_wire=DIGEST_BYTES)
            return
        pend = entry.pending.get(server)
        if pend is not None and pend.status not in (COMPLETE, ERROR):
            self._record_dedup(store, entry, cmd.nbytes)

            def after(_p):
                if self.detached or ev.status in (COMPLETE, ERROR):
                    # we left (detach failed our events) before ever
                    # sending the dedup'd command: no write happened,
                    # so no bytes were saved — take the claim back
                    self._unrecord_dedup(store, cmd.nbytes)
                    return
                if buf.version != snap:
                    # a newer write of this buffer was sent while we
                    # gated: shipping the stale command now would invert
                    # write-after-write order on the server (store-less
                    # clusters send writes FIFO). The content this write
                    # carried is superseded — complete as a no-op
                    ev.complete(self.clock.now)
                    self._route_completion_via_client(ev)
                    ev.release()    # client observed completion directly
                    return
                if server in entry.valid_on:
                    self._send_command(ev, server, "", dep_ids,
                                       extra_wire=DIGEST_BYTES)
                else:
                    # the transfer we gated on never landed (dropped
                    # link or stale payload): the claimed saving did not
                    # materialize — take it back and resolve again
                    self._unrecord_dedup(store, cmd.nbytes)
                    self._resolve_store_write(ev, server, buf, cmd,
                                              dep_ids, store, entry,
                                              snap)

            pend.on_complete(after)
            return
        self._send_upload(ev, server, cmd, dep_ids, store, entry)

    def _send_upload(self, ev: Event, server: str, cmd, dep_ids: list,
                     store: BufferStore, entry) -> None:
        def landed(_e):
            if _e.status == COMPLETE:
                store.replica_landed(entry, server)

        # landed BEFORE add_pending: its clear-callback garbage-collects
        # entries with no refs/replicas/pendings, and if the buffer was
        # rewritten mid-upload (refs empty) the replica must register
        # first — otherwise replica_landed resurrects a popped entry and
        # its resident bytes leak forever
        ev.on_complete(landed)
        store.add_pending(entry, server, ev)
        self._send_command(ev, server, "", dep_ids, payload=cmd.nbytes)

    def enqueue_read(self, server: str, buf: Buffer,
                     wait_for: Sequence[Event] = ()) -> Event:
        with span("pocl.enqueue_read") as sp:
            self._check_live()
            cmd = C.ReadBuffer(buffer=buf)
            ev = self._new_event(cmd, server)
            sp.set_metadata(event=ev.id)
            self._send_command(ev, server, "", [d.id for d in wait_for])
            return ev

    def enqueue_migration(self, buf: Buffer, dst: str,
                          wait_for: Sequence[Event] = ()) -> Event:
        """Migrate to ``dst``. P2P: command goes to the SOURCE server,
        which pushes directly to the destination (paper §5.1).

        Duplicate requests coalesce: if a migration of the same buffer
        contents to the same destination is already in flight, its event
        is returned instead of pushing the payload a second time. The
        coalesced transfer's contents are identical by construction (a
        write or output clobber bumps ``buf.version``, which makes the
        in-flight entry stale), so a dependent waiting on the returned
        event sees exactly the bytes it asked for. When several replicas
        exist, the source is the server with the cheapest estimated
        delivery (``_pick_migration_source``), not set order."""
        self._check_live()
        if dst in buf.valid_on:
            ev = self._new_event(C.Marker(), dst)
            ev.complete(self.clock.now)
            ev.release()            # completed on the client: no ack cycle
            return ev
        store = self.cluster.store
        sentry = store.entry_for(buf) if store is not None else None
        key = (buf.id, dst)
        entry = self._inflight_migrations.get(key)
        if entry is not None:
            # our OWN transfer of these bytes is already on the wire:
            # coalesce (store-less semantics) BEFORE the store's
            # resident-dedup check — claiming a saving here would
            # double-book bytes this tenant is simultaneously paying
            pending, version = entry
            if version == buf.version and \
                    pending.status not in (COMPLETE, ERROR):
                self.migrations_coalesced += 1
                live = [d for d in wait_for
                        if d.status not in (COMPLETE, ERROR)]
                if not live:
                    return pending
                # the payload still crosses the wire once, but the
                # returned handle must honor the caller's wait list like
                # a non-coalesced migration would
                return self._join_events([pending, *live])
        if sentry is not None and dst in sentry.valid_on:
            # identical content is already resident on dst — uploaded or
            # migrated there by ANY tenant — so nothing needs to move;
            # the §5 content-addressed analogue of `dst in buf.valid_on`
            self._record_dedup(store, sentry, buf.transfer_bytes())
            buf.valid_on.add(dst)
            ev = self._new_event(C.Marker(), dst)
            ev.complete(self.clock.now)
            ev.release()            # completed on the client: no ack cycle
            return ev
        if sentry is not None:
            pend = sentry.pending.get(dst)
            if pend is not None and pend.status not in (COMPLETE, ERROR):
                # identical content is already on the wire to dst —
                # another tenant's upload or migration (our own transfers
                # were caught by the per-tenant table above): ride it
                # instead of pushing the payload again
                self._record_dedup(store, sentry, buf.transfer_bytes())
                ride = self._ride_pending_replica(sentry, pend, buf, dst)
                # the ride joins the per-tenant in-flight table like a
                # real migration: a back-to-back request for the same
                # (buf, dst) coalesces onto it (counted under
                # migrations_coalesced) instead of opening a second
                # ride and double-claiming the dedup saving
                self._track_inflight(key, ride, buf.version)
                live = [d for d in wait_for
                        if d.status not in (COMPLETE, ERROR)]
                if not live:
                    return ride
                return self._join_events([ride, *live])
        # membership (DESIGN.md §7): a DEAD host's replicas are gone —
        # never source from one (DRAINING hosts still serve: the drain's
        # own re-homing pushes FROM the draining host)
        alive = self.cluster.membership.is_alive
        srcs = [s for s in buf.valid_on if s != "client" and alive(s)]
        if sentry is not None and sentry.valid_on:
            # §5 replica-aware sourcing across tenants: any server
            # holding a valid replica of this content can serve the
            # push, not just the ones this tenant put it on
            srcs = sorted({*srcs, *(s for s in sentry.valid_on
                                    if alive(s))})
        if not srcs:  # client-held data: plain upload
            return self.enqueue_write(dst, buf, buf.host()
                                      if buf.data is not None
                                      else np.zeros(buf.nbytes, np.uint8))
        src = self._pick_migration_source(buf, srcs, dst)
        cmd = C.MigrateBuffer(buffer=buf, dst_server=dst)
        if self.p2p_migration:
            ev = self._new_event(cmd, src)
            self._track_inflight(key, ev, buf.version)
            if sentry is not None:
                store.add_pending(sentry, dst, ev)
            self._send_command(ev, src, "", [d.id for d in wait_for])
            return ev
        # naive: read back to client, then write to dst
        rd = self.enqueue_read(src, buf, wait_for=wait_for)
        wr_ev = self._new_event(cmd, dst)
        trc = self._trace
        if trc is not None:             # write leg waits on the read leg
            trc.cmd_deps(wr_ev, [rd.id])
        self._track_inflight(key, wr_ev, buf.version)
        if sentry is not None:
            store.add_pending(sentry, dst, wr_ev)

        def after_read(rd_ev):
            if rd_ev.status == ERROR:
                # the read leg was lost on a dead link: release the
                # in-flight entry so a retry starts a fresh transfer,
                # and propagate the failure to the migration handle
                self._drop_inflight(key, wr_ev)
                wr_ev.fail(self.clock.now, rd_ev.error)
                self._route_completion_via_client(wr_ev)
                wr_ev.release()     # no completion ack will ever come
                return
            cur = self._inflight_migrations.get(key)
            if cur is not None and cur[0] is wr_ev:
                # refresh the coalescing snapshot to the generation the
                # read actually captured: a producer that executed after
                # enqueue (bumping the version) no longer blocks requests
                # from riding the long client→dst upload leg (mirrors the
                # push-time refresh on the P2P path; requests arriving
                # during the read leg itself still conservatively miss)
                self._inflight_migrations[key] = (wr_ev, rd_ev.data_version)
            self.clock.schedule(CLIENT_SUBMIT, self._deliver_naive_write,
                                wr_ev, dst, buf.transfer_bytes(),
                                rd_ev.data_version)

        rd.on_complete(after_read)
        return wr_ev

    def _pick_migration_source(self, buf: Buffer, srcs: Sequence[str],
                               dst: str) -> str:
        """Cheapest replica by estimated delivery time at enqueue: data
        link queue (``_busy_until``) + serialization at the link's
        effective bandwidth + propagation, plus — on the P2P path — the
        one-time MR registration/rkey-exchange cost when the RDMA
        transport has not yet registered this (buffer, src, dst), so an
        already-registered replica is preferred even over a slightly
        busier link. P2P scores the src↔dst peer link; naive mode scores
        the read leg over the source's client link (the client→dst leg
        is common to every candidate). The payload-free client→source
        command leg is deliberately ignored: it is near-uniform across
        sources. Under the shared-NIC egress model the source host's NIC
        queue counts toward the estimate too — a server mid-push to one
        peer is a poor source for another even over an idle link. Sorted
        iteration makes the choice deterministic (set order is not)."""
        if len(srcs) == 1:
            return srcs[0]
        nbytes = buf.transfer_bytes()
        p2p = self.p2p_migration
        tr = self.peer_transport if p2p else self.transport
        now = self.clock.now
        best = None
        best_t = None
        for s in sorted(srcs):
            if p2p:
                link = self.p_links.get((s, dst)) \
                    or self.p_links.get((dst, s))
            else:
                link = self.c_links.get(s)
            if link is None or not link.up:
                continue
            busy = link._busy_until
            nic = self.cluster.hosts[s].nic    # both legs leave server s
            if nic is not None and nic._busy_until > busy:
                busy = nic._busy_until         # shared egress is the queue
            queue = busy - now
            if queue < 0.0:
                queue = 0.0
            bw = link.bandwidth
            t = queue + link.latency + (
                (CMD_BYTES + nbytes) * wire_scale(tr, bw) / bw if bw else 0.0)
            if p2p and (buf.id, s, dst) not in self._mr_registered:
                t += tr.register_buffer(nbytes, peers=len(self.servers) - 1)
            if best_t is None or t < best_t:
                best, best_t = s, t
        return best if best is not None else sorted(srcs)[0]

    def _join_events(self, events: Sequence[Event]) -> Event:
        """Client-side user event completing once every input has
        finished (error counts as finished, matching the runtime's loose
        error-dependency semantics); subscribers are notified over the
        client links like any other client-completing event."""
        join = self._register_event(Event(user=True, server="client"))
        trc = self._trace
        if trc is not None:             # the join's causal inputs
            trc.cmd_deps(join, [e.id for e in events])
        state = {"remaining": len(events)}

        def one_done(_e):
            state["remaining"] -= 1
            if not state["remaining"]:
                join.complete(self.clock.now)
                self._route_completion_via_client(join)
                join.release()  # client observed completion directly

        for e in events:
            e.on_complete(one_done)     # fires now if already finished
        return join

    def _check_live(self):
        if self.detached:
            raise DeviceUnavailable(
                f"{self.name} (tenant detached from cluster)")

    def _ride_pending_replica(self, sentry, pending: Event, buf: Buffer,
                              dst: str) -> Event:
        """Identical content is already in flight to ``dst`` on another
        tenant's transfer: return a tenant-local event that completes
        when it lands (cross-tenant coalescing, DESIGN.md §5). The
        foreign event cannot be returned directly — dependency
        classification and completion routing resolve through THIS
        tenant's event table. If the ride dies under us (dropped link,
        payload gone stale) a real migration runs as fallback."""
        ev = self._register_event(Event(user=True, server="client"))
        trc = self._trace
        if trc is not None:             # the ride's causal input
            trc.cmd_deps(ev, [pending.id])
        snap = buf.version
        saved = buf.transfer_bytes()    # what the caller counted as saved

        def settle(_p):
            if self.detached or ev.status in (COMPLETE, ERROR):
                # we left (detach failed our events) before the ride
                # resolved: the claimed saving never materialized —
                # no migration of ours completed
                self._unrecord_dedup(self.cluster.store, saved)
                return
            now = self.clock.now
            landed = dst in sentry.valid_on
            if landed and buf.version == snap:
                buf.valid_on.add(dst)
            if landed or buf.version != snap:
                # delivered — or our buffer was rewritten while riding,
                # which voids the ordering contract exactly like the
                # eager clobber does on a private migration
                if not landed:
                    # ride died after our buffer moved on: nothing was
                    # transferred or avoided — take the credit back
                    self._unrecord_dedup(self.cluster.store, saved)
                ev.complete(now)
                self._route_completion_via_client(ev)
                ev.release()        # client observed completion directly
                return
            # the ride died: the claimed saving did not materialize —
            # take it back before the real migration (which re-counts
            # only if it genuinely dedups). The ride must leave the
            # per-tenant in-flight table first: the retry would
            # otherwise coalesce onto the ride itself (same key, same
            # version) and wait on an event only IT can complete
            self._unrecord_dedup(self.cluster.store, saved)
            self._drop_inflight((buf.id, dst), ev)
            retry = self.enqueue_migration(buf, dst)

            def mirror(r):
                if ev.status in (COMPLETE, ERROR):
                    return
                if r.status == ERROR:
                    ev.fail(self.clock.now, r.error or "migration failed")
                else:
                    ev.complete(self.clock.now)
                self._route_completion_via_client(ev)
                ev.release()        # client observed completion directly

            retry.on_complete(mirror)

        pending.on_complete(settle)
        return ev

    def _fail_dropped_migration(self, ev: Event, dst: str):
        """A migration payload dropped on a dead link can never be
        re-sent (the daemon already marked the command processed, so a
        replay is deduped): fail fast like the read-return leg does —
        the in-flight entry releases via the failure callbacks, so a
        retry after reconnect starts a fresh transfer. Idempotent: a
        crash's fail-fast sweep and the link's mid-flight drop callback
        can both reach the same event — only the first acts."""
        if ev.status in (COMPLETE, ERROR):
            return
        ev.fail(self.clock.now, f"link to {dst} down during migration")
        self._route_completion_via_client(ev)
        ev.release()                # no completion ack will ever come

    def _track_inflight(self, key, ev: Event, version: int):
        self._inflight_migrations[key] = (ev, version)
        ev.on_complete(lambda _e: self._drop_inflight(key, ev))

    def _drop_inflight(self, key, ev: Event):
        cur = self._inflight_migrations.get(key)
        if cur is not None and cur[0] is ev:
            del self._inflight_migrations[key]

    def _send_migration_chunks(self, link: Link, tr, nbytes: float,
                               extra_overhead: float,
                               arrived: Callable,
                               egress: Optional[NIC] = None,
                               ingress: Optional[NIC] = None,
                               on_dropped: Optional[Callable] = None,
                               ev_id: Optional[int] = None) \
            -> bool:
        """Shared bulk-payload leg for both migration paths: build the
        transport's cut-through plan, apply wire inflation, keep the
        scoreboard, and send (``egress`` is the sending host's shared
        NIC when the transfer leaves a server, ``ingress`` the
        receiving host's when it lands on one). ``arrived`` fires after
        the last chunk's receiver-side work. Returns False if the link
        is down at send time (the transfer was dropped); ``on_dropped``
        fires instead of ``arrived`` if the link dies mid-flight — the
        remaining chunks are lost deterministically at fault time."""
        if nbytes > 0:
            fixed, chunks = tr.chunk_plan(nbytes)
        else:   # content-size says empty: command struct only
            cost = tr.command_cost(0.0)
            fixed, chunks = cost.sender_cpu, [(0.0, cost.wire_bytes,
                                               cost.receiver_cpu)]
        scale = wire_scale(tr, link.bandwidth)
        if scale != 1.0:
            chunks = scale_chunks(chunks, scale)
        n_chunks = len(chunks)

        def delivered():
            self.chunks_in_flight -= n_chunks
            arrived()

        def dropped():
            self.chunks_in_flight -= n_chunks
            if on_dropped is not None:
                on_dropped()

        trc = self._trace
        arrivals = [] if trc is not None else None
        t0 = self.clock.now
        rcv = link.send_chunked(chunks, delivered,
                                serialize_overhead=extra_overhead + fixed,
                                egress=egress, ingress=ingress,
                                on_dropped=dropped,
                                chunk_arrivals=arrivals)
        if rcv is None:
            return False
        self.chunks_in_flight += n_chunks
        if self.chunks_in_flight > self.peak_chunks_in_flight:
            self.peak_chunks_in_flight = self.chunks_in_flight
        # computed once, shared by the scoreboard and the trace span, so
        # a span-derived sum reproduces the counter bit-exactly
        wire_total = sum(c[1] for c in chunks)
        self.bytes_on_wire += wire_total
        if trc is not None:
            trc.transfer("migration", self._tp + link.name, self._tlabel,
                         t0, rcv, wire_total, ev_id=ev_id,
                         chunk_arrivals=arrivals, link_obj=link)
        return True

    def _deliver_naive_write(self, ev, dst, nbytes, version):
        """``version`` is the buffer's content generation when the bytes
        left the source (captured by the read leg), NOT now: a write
        landing during the read makes the payload stale even though it
        has not crossed the client→dst link yet."""
        buf = ev.command.buffer

        def arrived():
            if buf.version == version:   # not clobbered while in flight
                buf.valid_on.add(dst)
                self._store_replica_landed(buf, dst)
            # completes on the destination daemon like any other server-
            # side command, sharing the completion-routing logic
            # (subscription vs broadcast) with every other path
            self.servers[dst]._complete(ev)

        if not self._send_migration_chunks(
                self.c_links[dst], self.transport, nbytes, 0.0, arrived,
                ingress=self._nic_in(dst),
                on_dropped=lambda: self._fail_dropped_migration(ev, dst),
                ev_id=ev.id):
            self._fail_dropped_migration(ev, dst)

    def marker(self) -> Event:
        ev = self._new_event(C.Marker(), "client")
        ev.complete(self.clock.now)
        ev.release()                # completed on the client: no ack cycle
        return ev

    # ---- wire ----
    def _send_command(self, ev: Event, server: str, device: str,
                      dep_ids: list, payload: float = 0.0,
                      extra_wire: float = 0.0):
        trc = self._trace
        if trc is not None and dep_ids:
            # happens-before edges for the critical-path DAG
            # (DESIGN.md §11): raw ids, before the wire-message
            # classification below drops already-finished deps
            trc.cmd_deps(ev, dep_ids)
        # classify deps at enqueue time: already-finished ones are
        # dropped from the wire message; live ones are retained (they
        # must stay resolvable until this command dispatches) and, when
        # remote, the target server subscribes to their completion
        deps = []
        if dep_ids:
            events = self.events
            by_sub = self.completion_routing == "subscription"
            if len(dep_ids) == 1:     # common case: skip the dedup set
                dep_id = dep_ids[0]
                dep = events.get(dep_id)
                if dep is not None and dep.status != COMPLETE \
                        and dep.status != ERROR:
                    dep.retain()
                    local = dep.server == server
                    if not local and by_sub:
                        self._subs.setdefault(dep_id, set()).add(server)
                    deps.append((dep_id, local))
            else:
                seen = set()
                for dep_id in dep_ids:
                    if dep_id in seen:
                        continue
                    seen.add(dep_id)
                    dep = events.get(dep_id)
                    if dep is None or dep.status == COMPLETE \
                            or dep.status == ERROR:
                        continue      # finished (error counts): no wire dep
                    dep.retain()
                    local = dep.server == server
                    if not local and by_sub:
                        self._subs.setdefault(dep_id, set()).add(server)
                    deps.append((dep_id, local))
        sess = self.sessions[server]
        sess.record((ev, server, device, deps, payload))
        link = self.c_links[server]
        if payload > 0:
            # bulk upload: cut-through chunks (per-chunk copy totals
            # equal cost.sender_cpu/receiver_cpu, so single-chunk timing
            # on an idle link is unchanged)
            fixed, chunks = self.transport.chunk_plan(payload)
            scale = self._cscale0
            if scale != 1.0:
                chunks = scale_chunks(chunks, scale)

            def deliver_chunked():
                self.clock.schedule(
                    DISPATCH,
                    self.servers[server].receive_command, ev, device, deps)

            arrivals = [] if trc is not None else None
            t0 = self.clock.now
            rcv = link.send_chunked(chunks, deliver_chunked,
                                    serialize_overhead=CLIENT_SUBMIT + fixed,
                                    ingress=self._nic_in(server),
                                    chunk_arrivals=arrivals)
            if rcv is not None:
                # count only bytes that actually went out (a down link
                # drops the send) — mirrors bytes_on_wire's accounting
                self.upload_bytes_on_wire += payload * scale
                if trc is not None:
                    trc.transfer("upload", self._tp + link.name,
                                 self._tlabel, t0, rcv, payload * scale,
                                 ev_id=ev.id, chunk_arrivals=arrivals,
                                 link_obj=link)
            return
        # zero-payload: the cost triple is the transport's cached
        # constant (`_cmd_cost0`) and the derived overhead/delay floats
        # were folded at construction; the delivery callback is a bound
        # method + args instead of a per-send closure
        cost = self._cmd_cost0
        link.send((cost.wire_bytes + extra_wire) * self._cscale0,
                  self._deliver_command,
                  serialize_overhead=self._submit_overhead0,
                  ingress=self.cluster.hosts[server].nic_in,
                  args=(server, ev, device, deps))

    def _deliver_command(self, server: str, ev: Event, device: str,
                         deps: list):
        self.clock.schedule(self._recv_delay0,
                            self.servers[server].receive_command,
                            ev, device, deps)

    # ---- migration execution (on source server) ----
    def _start_p2p_push(self, src_srv: ServerSim, ev: Event):
        cmd = ev.command
        buf, dst = cmd.buffer, cmd.dst_server
        nbytes = buf.transfer_bytes()
        tr = self.peer_transport
        reg = 0.0
        key = (buf.id, src_srv.name, dst)
        if key not in self._mr_registered:
            reg = tr.register_buffer(nbytes, peers=len(self.servers) - 1)
            self._mr_registered.add(key)
        link = self.peer_link(src_srv.name, dst)
        ev.status = RUNNING
        ev.t_start = self.clock.now
        # contents being pushed are the canonical bytes as of now; a
        # write landing while the transfer is in flight makes the copy
        # at dst stale, so validity is only granted on version match
        version = buf.version
        inflight_key = (buf.id, dst)
        entry = self._inflight_migrations.get(inflight_key)
        if entry is not None and entry[0] is ev:
            # refresh the coalescing snapshot: the producer this
            # migration waited on has executed by now, so requests
            # enqueued mid-flight still coalesce
            self._inflight_migrations[inflight_key] = (ev, version)

        def arrived():
            if buf.version == version:   # not clobbered while in flight
                buf.valid_on.add(dst)
                self._store_replica_landed(buf, dst)
            ev.server = dst
            self.servers[dst]._complete(ev)

        if not self._send_migration_chunks(
                link, tr, nbytes, reg, arrived,
                egress=src_srv.host.nic, ingress=self._nic_in(dst),
                on_dropped=lambda: self._fail_dropped_migration(ev, dst),
                ev_id=ev.id):
            self._fail_dropped_migration(ev, dst)

    def _store_replica_landed(self, buf: Buffer, dst: str):
        """A migration payload landed on ``dst`` with its version intact:
        if the buffer shares content through the cluster store, the
        arrival is a new physical replica of that content — register it
        so any tenant's later request resolves there. (The version match
        the callers establish guarantees the buffer is still attached to
        the entry the bytes belong to.)"""
        store = self.cluster.store
        if store is None:
            return
        sentry = store.entry_for(buf)
        if sentry is not None:
            store.replica_landed(sentry, dst)

    def _start_read_return(self, srv: ServerSim, ev: Event):
        """The return leg of a read. The buffer is made host bytes first,
        its content-size buffer before it: the ``pocl.read`` span, whose
        ``bytes`` are those it copied off a device."""
        buf = ev.command.buffer
        with span("pocl.read", event=ev.id) as sp:
            copied = buf.to_host()
            if copied:
                sp.set_metadata(bytes=copied)
        nbytes = buf.transfer_bytes()
        ev.data_version = buf.version   # generation of the returned bytes
        cost = self.transport.command_cost(nbytes)
        link = self.c_links[srv.name]
        ev.status = RUNNING
        ev.t_start = self.clock.now

        def arrived():
            if ev.status in (COMPLETE, ERROR):
                # failed fast while the return leg was in flight (the
                # serving host crashed): the client already observed
                # ERROR — completing now would double-fire callbacks
                return
            if buf.version == ev.data_version:
                # downloaded bytes still match the canonical contents;
                # a write that landed mid-read makes this copy stale
                buf.valid_on.add("client")
            ev.complete(self.clock.now)
            self._route_completion_via_client(ev)
            ev.release()            # client observed completion directly

        trc = self._trace
        t0 = self.clock.now
        ret = link.send(cost.wire_bytes * wire_scale(self.transport,
                                                     link.bandwidth),
                        arrived,
                        serialize_overhead=COMPLETE_WRITE + cost.sender_cpu,
                        egress=srv.host.nic)
        if ret is not None:
            if trc is not None:
                trc.transfer("read_return", self._tp + link.name,
                             self._tlabel, t0, ret,
                             cost.wire_bytes * wire_scale(self.transport,
                                                          link.bandwidth),
                             ev_id=ev.id, link_obj=link)
        else:
            # link died after the command was delivered: the daemon has
            # already marked it processed, so a replay will be deduped
            # and the data can never be re-sent — surface the error
            # instead of hanging the handle (and its consumers) forever
            ev.fail(self.clock.now,
                    f"link to {srv.name} down during read return")
            self._route_completion_via_client(ev)
            ev.release()            # nothing further will arrive

    # ---- completion propagation ----
    def _broadcast_completion(self, srv: ServerSim, ev: Event):
        comp = self._comp_cost          # per-transport constant
        nic = srv.host.nic              # every leg leaves this server
        # to client (always)
        self.c_links[srv.name].send(
            comp.wire_bytes, self._client_reap,
            serialize_overhead=self._complete_overhead,
            egress=nic, args=(ev,))
        self.client_completion_msgs += 1
        if self.scheduling != "decentralized":
            return
        if self.completion_routing == "subscription":
            targets = sorted(self._subs.pop(ev.id, ()))
        else:
            targets = [p for p in self.servers if p != srv.name]
        for name in targets:
            if name == srv.name:
                continue
            link = self.peer_link(srv.name, name)
            link.send(comp.wire_bytes,
                      self.servers[name].notify_remote_complete,
                      serialize_overhead=comp.sender_cpu, egress=nic,
                      ingress=self._nic_in(name), args=(ev.id,))
            self.peer_completion_msgs += 1

    def _route_completion_via_client(self, ev: Event):
        """Events that complete on the client itself (reads, user/race
        events, local fallback) have no server to signal from; notify any
        subscribed servers over their client links."""
        subs = self._subs.pop(ev.id, None)
        if not subs:
            return
        comp = self.transport.completion_cost()
        for name in sorted(subs):
            self.c_links[name].send(
                comp.wire_bytes,
                self.servers[name].notify_remote_complete,
                serialize_overhead=comp.sender_cpu,
                ingress=self._nic_in(name), args=(ev.id,))
            self.client_routed_completion_msgs += 1

    def _client_reap(self, ev: Event):
        self.clock.schedule(CLIENT_REAP, self._client_reap2, ev)

    def _client_reap2(self, ev: Event):
        ev.t_client_ack = self.clock.now
        slo = self._slo_s
        if slo is not None:
            # SLO scoring (DESIGN.md §10): client-observed end-to-end
            # latency vs the tenant's effective budget. Feeds the
            # admission controller's windowed per-class histograms and,
            # when traced, the violation instants.
            latency = ev.t_client_ack - ev.t_queued
            violated = latency > slo
            self.slo_commands += 1
            if violated:
                self.slo_violations += 1
            ctrl = self.cluster.admission
            if ctrl is not None:
                ctrl.observe(self._slo_class, ev.t_client_ack, latency,
                             violated)
            if violated:
                tr = self._trace
                if tr is not None:
                    tr.slo_violation(ev.t_client_ack, self._tlabel,
                                     ev.id, latency, slo)
        if self.scheduling == "client":
            # SnuCL-like: client forwards resolution to the other servers
            if self.completion_routing == "subscription":
                targets = sorted(self._subs.pop(ev.id, ()))
            else:
                targets = [p for p in self.servers if p != ev.server]
            comp = self.transport.completion_cost()
            for name in targets:
                if name == ev.server:
                    continue
                self.c_links[name].send(
                    comp.wire_bytes,
                    self.servers[name].notify_remote_complete,
                    serialize_overhead=comp.sender_cpu,
                    ingress=self._nic_in(name), args=(ev.id,))
                self.client_routed_completion_msgs += 1
        ev.release()                # client hold: completion observed

    # ---- fault injection / sessions (paper §4.3) ----
    def inject_disconnect(self, server: str, at: Optional[float] = None):
        def go():
            self.c_links[server].up = False
            self.sessions[server].available = False
        if at is None:
            go()
        else:
            self.clock.schedule_at(at, go)

    def detach(self) -> None:
        """Tenant lifecycle (DESIGN.md §5): release everything this
        client holds on the shared cluster and leave it.

        * Buffer references drop from the content-addressed store, so
          replicas this tenant pinned become evictable (and dedup'able
          by the tenants that remain).
        * Server-side: the session ids leave every host's §4.3 session
          table, this tenant's queued commands leave the device run
          queues, and the per-session daemon state (replay dedup,
          remote-resolution, waiter tables) is destroyed — a later
          reattach presenting the same session id starts a FRESH
          session; it must not resurrect the dedup'd replay state.
        * Client-side: every live event fails with ``tenant detached``
          (dependents and user callbacks observe ERROR, and other
          tenants gated on this tenant's in-flight transfers fall back
          to their own), the access links close, and the runtime
          refuses further enqueues.

        The in-service command on a device, if any, runs to completion
        (the scheduler is non-preemptive) but completes into a failed
        event, which is a no-op. Bystander tenants only ever shared the
        clock, devices, NICs, and peer mesh — none of which detach
        rewinds — so their timing is unperturbed beyond the freed
        capacity."""
        if self.detached:
            return
        self.detached = True
        now = self.clock.now
        cluster = self.cluster
        if cluster.store is not None:
            for b in self._buffers:
                cluster.store.release(b)
        for srv in self.servers.values():
            host = srv.host
            if srv.session_id is not None:
                host.sessions.pop(srv.session_id, None)
            for sch in host.schedulers.values():
                sch.discard(srv)
            srv.processed.clear()
            srv.resolved_remote.clear()
            srv._waiters.clear()
            srv._ready.clear()
            srv.session_id = None
        for sess in self.sessions.values():
            sess.available = False
            sess.replay.clear()
            sess.session_id = bytes(16)
        for link in self.c_links.values():
            link.close()
        for ev in list(self.events.values()):
            if ev.status not in (COMPLETE, ERROR):
                ev.fail(now, f"tenant {self.name} detached")
        self.events.clear()
        self._subs.clear()
        self._inflight_migrations.clear()
        if self in cluster.clients:
            cluster.clients.remove(self)

    def reconnect(self, server: str, at: Optional[float] = None):
        """Restore the link; replay unacknowledged commands (server dedupes
        by command id). The session ID survives even if the client's
        address changed.

        Bounded (DESIGN.md §7): if the server is gone — crashed,
        retired, or the link stays dead — the handshake is retried with
        exponential backoff (``reconnect_backoff`` doubling, up to
        ``reconnect_retries`` retries beyond the first attempt), then
        the failure is surfaced: the unacked commands still targeting
        the server fail so their dependents observe ERROR instead of
        waiting forever on a session that will never come back. A
        server that rejoins mid-backoff is picked up by the next
        attempt (the fresh link is re-read each try)."""
        self._check_live()

        def attempt(tries_left: int, delay: float):
            self.reconnect_attempts[server] = \
                self.reconnect_attempts.get(server, 0) + 1
            link = self.c_links.get(server)
            if self.cluster.membership.is_alive(server) and \
                    link is not None:
                link.up = True        # a closed (dead-host) link stays down
                if link.up and link.send(
                        64 + 16,      # handshake incl. session id
                        lambda: handshook(link),
                        ingress=self._nic_in(server)) is not None:
                    return
            if tries_left > 0:
                self.clock.schedule(delay, attempt, tries_left - 1,
                                    delay * 2.0)
                return
            self._reconnect_exhausted(server)

        def handshook(link):
            sess = self.sessions[server]
            srv = self.servers[server]
            # present the session id to the daemon's session table
            # (§4.3): the id, not the transport address, resolves
            # the server-side session — its replay-dedup state is
            # what makes the replayed commands below idempotent
            daemon = srv.host.sessions.get(sess.session_id)
            if daemon is None:          # expired/unknown: re-admit
                daemon = srv.host.sessions[sess.session_id] = srv
            sess.available = True
            for (ev, _srv_name, device, deps, payload) in \
                    list(sess.replay):
                if ev.status in (COMPLETE, ERROR):
                    continue
                cost = self.transport.command_cost(payload)
                link.send(cost.wire_bytes,
                          lambda e=ev, d=device, dd=deps:
                          daemon.receive_command(e, d, dd),
                          serialize_overhead=cost.sender_cpu,
                          ingress=self._nic_in(server))

        def go():
            attempt(self.reconnect_retries, self.reconnect_backoff)

        if at is None:
            go()
        else:
            self.clock.schedule_at(at, go)

    def _reconnect_exhausted(self, server: str) -> None:
        """Every reconnect attempt failed: surface it. The commands
        still unacked in the replay buffer can never be replayed —
        fail them (unless a drain already re-placed them elsewhere) so
        nothing upstream hangs on this session."""
        reason = (f"reconnect to {server} failed after "
                  f"{self.reconnect_attempts.get(server, 0)} attempts")
        log.warning("%s: %s", self.name, reason)
        self.reconnect_failures[server] = reason
        now = self.clock.now
        sess = self.sessions.get(server)
        if sess is None:
            return
        for (ev, *_rest) in list(sess.replay):
            # a drain may have requeued the command to a survivor —
            # its event now targets that host and must stay live
            if ev.status in (COMPLETE, ERROR) or ev.server != server:
                continue
            ev.fail(now, reason)
            self._route_completion_via_client(ev)
            ev.release()            # no completion ack will ever come
        sess.replay.clear()

    def enqueue_kernel_redundant(self, servers: Sequence[str], **kw) -> Event:
        """Straggler mitigation: dispatch the same kernel to several
        servers; the first completion wins and late copies are ignored
        (the client simply reaps the winner — the OpenCL semantics make
        duplicate side-effect-free kernels safe to race).

        Returns a user event that completes with the winner."""
        race = self._register_event(Event(user=True, server="client"))
        outputs = kw.get("outputs", ())
        fn = kw.pop("fn", None)

        def on_done(ev):
            if race.status != COMPLETE:
                # winner executes the functional payload; losers are void
                if fn is not None:
                    ins = [b.host() for b in kw.get("inputs", ())]
                    outs = fn(*ins)
                    if not isinstance(outs, (tuple, list)):
                        outs = (outs,)
                    for b, arr in zip(outputs, outs):
                        b.set_data(np.asarray(arr), ev.server)
                race.server = ev.server
                race.complete(self.clock.now)
                self._route_completion_via_client(race)
                race.release()      # client observed completion directly

        for s in servers:
            if not self.sessions[s].available:
                continue
            # pin=True: the race's value IS the explicit server spread —
            # a placement policy would happily collapse every copy onto
            # the one telemetry-best host, defeating the mitigation
            ev = self.enqueue_kernel(s, fn=None, pin=True, **kw)
            ev.on_complete(on_done)
        return race

    def run_local_fallback(self, fn, inputs, outputs, flops=0.0,
                           duration=None) -> Event:
        """Fig. 4: compute locally (reduced model) while remotes are gone."""
        self._check_live()
        fork_bytes = 0.0
        if self.cluster.store is not None:
            for b in outputs:       # local writes fork shared content too
                if self.cluster.store.cow_fork(b):
                    # same 2×nbytes device-copy charge as the server-side
                    # kernel path (DESIGN.md §5)
                    fork_bytes += 2.0 * b.nbytes
        ev = self._new_event(C.NDRangeKernel(fn=fn, inputs=tuple(inputs),
                                             outputs=tuple(outputs),
                                             flops=flops, duration=duration),
                             "client")

        def done():
            cmd = ev.command
            if cmd.fn is not None:
                ins = [b.host() for b in cmd.inputs]
                outs = cmd.fn(*ins)
                if not isinstance(outs, (tuple, list)):
                    outs = (outs,)
                for b, arr in zip(cmd.outputs, outs):
                    b.set_data(np.asarray(arr), "client")
            ev.complete(self.clock.now)
            self._route_completion_via_client(ev)
            ev.release()            # client observed completion directly

        cost = self.local_device.kernel_cost(flops, fork_bytes, duration)
        ev.t_start, _ = self.local_device.execute(cost, done)
        return ev

    # ---- control ----
    def finish(self) -> float:
        """Drain the simulation; returns the final clock time. The clock
        is the cluster's, so on a shared cluster this drains every
        attached tenant, not just this one."""
        with span("pocl.finish"):
            return self.clock.run()

    def stats(self) -> dict:
        # NOTE: peer_link_bytes and device_busy read the cluster-shared
        # substrate — on a shared cluster they are totals across every
        # tenant, not this client's share (Cluster.stats() carries the
        # same numbers); the remaining keys are per-client
        return {
            "time": self.clock.now,
            "client_link_bytes": {s: lk.bytes_sent
                                  for s, lk in self.c_links.items()},
            "peer_link_bytes": {f"{a}-{b}": lk.bytes_sent
                                for (a, b), lk in self.p_links.items()},
            "device_busy": {f"{s}/{d}": dev.busy_time
                            for s, srv in self.servers.items()
                            for d, dev in srv.devices.items()},
            "client_completion_msgs": self.client_completion_msgs,
            "peer_completion_msgs": self.peer_completion_msgs,
            "client_routed_completion_msgs":
                self.client_routed_completion_msgs,
            "events_live": len(self.events),
            "replay_window": {s: sess.replay.maxlen
                              for s, sess in self.sessions.items()},
            "replay_overflows": {s: sess.lost_unacked
                                 for s, sess in self.sessions.items()},
            # bounded reconnect (DESIGN.md §7)
            "reconnect_attempts": dict(self.reconnect_attempts),
            "reconnect_failures": dict(self.reconnect_failures),
            # data-plane scoreboard (DESIGN.md §3)
            "bytes_on_wire": self.bytes_on_wire,
            "upload_bytes_on_wire": self.upload_bytes_on_wire,
            "migrations_coalesced": self.migrations_coalesced,
            "chunks_in_flight": self.chunks_in_flight,
            "peak_chunks_in_flight": self.peak_chunks_in_flight,
            "migrations_inflight": len(self._inflight_migrations),
            # content-addressed store scoreboard (DESIGN.md §5)
            "dedup_hits": self.dedup_hits,
            "dedup_bytes_saved": self.dedup_bytes_saved,
            "detached": self.detached,
            # SLO scoreboard (DESIGN.md §10)
            "slo_ms": self.slo_ms,
            "slo_effective_ms": (self._slo_s * 1e3
                                 if self._slo_s is not None else None),
            "slo_commands": self.slo_commands,
            "slo_violations": self.slo_violations,
            "slo_violation_rate": (self.slo_violations
                                   / self.slo_commands
                                   if self.slo_commands else 0.0),
            "admission": (self.admission.status
                          if self.admission is not None else None),
            # placement scoreboard (DESIGN.md §6) — cluster-wide, like
            # peer_link_bytes: decisions across every attached tenant
            "placement": self.cluster.placement.stats(),
        }


class DeviceUnavailable(RuntimeError):
    """CL_DEVICE_NOT_AVAILABLE analogue."""
    def __init__(self, server):
        super().__init__(f"server {server} unavailable")
        self.server = server
