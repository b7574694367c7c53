"""The readers of the program's own spans (``progspans.py`` and the
``cfd.*_per_job`` and ``passthrough.*_per_chain`` metrics that read it):
on traces built by hand, whose self times, busy time and bytes are known
exactly; on traces of the program without spans, where every reader
gives nothing; and on small traces recorded on a TPU v5e by
``record_trace.py`` (``data/*_spans.xplane.pb``)."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP]

import harness  # noqa: E402
import progspans  # noqa: E402
import tracereduce  # noqa: E402

DATA = os.path.join(HERE, "data")
CFD_READERS = ["cfd.split_s_per_job", "cfd.halo_s_per_job",
               "cfd.transfer_s_per_job", "cfd.assemble_s_per_job",
               "cfd.host_device_gb_per_job"]
CHAIN_READERS = ["passthrough.runtime_us_per_chain",
                 "passthrough.kernel_call_us_per_chain",
                 "passthrough.commit_us_per_chain"]
SLAB = 9 * 8192 * 2050 * 4      # bytes of a half slab of the CFD cell


def xspace_text(planes: dict) -> str:
    """plane -> line -> events ``(name, start ns, end ns[, stats])`` as
    XSpace text (offsets in ps from the line's start)."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        evs = [ev for line in lines.values() for ev in line]
        ids = {n: i for i, n in enumerate(sorted({ev[0] for ev in evs}), 1)}
        keys = sorted({k for ev in evs if len(ev) > 3 for k in ev[3]})
        sids = {k: i for i, k in enumerate(keys, 1)}

        def stats(ev):
            return " ".join(
                f"stats {{ metadata_id: {sids[k]} "
                + (f'str_value: "{v}"' if isinstance(v, str)
                   else f"int64_value: {v}") + " }"
                for k, v in (ev[3].items() if len(ev) > 3 else ()))

        body = [f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                + " ".join(f"events {{ metadata_id: {ids[ev[0]]} "
                           f"offset_ps: {ev[1] * 1000} "
                           f"duration_ps: {(ev[2] - ev[1]) * 1000} "
                           f"{stats(ev)} }}" for ev in evs_)
                + " }"
                for lid, (line, evs_) in enumerate(lines.items(), 1)]
        body += [f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{n}" }} }}' for n, i in ids.items()]
        body += [f'stat_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{k}" }} }}' for k, i in sids.items()]
        out.append(f'planes {{ id: {pid} name: "{plane}" '
                   + " ".join(body) + " }")
    return "\n".join(out)


def write_trace(planes: dict, path) -> str:
    from jax.profiler import ProfileData
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            xspace_text(planes)))
    return str(path)


# Two chains of the pass-through path, each with a kernel call and a
# commit nested in the drain, a JAX event nested in a commit (not the
# program's), and a drain that outlasts the window (clipped to it).
CHAINS = {
    "/device:TPU:0": {"XLA Modules": [
        ("jit_copy(1)", 12000, 18000), ("jit_copy(1)", 25000, 30000),
        ("jit_copy(1)", 60000, 62000)]},
    "/host:CPU": {"python3": [
        ("bench.window", 0, 100000),
        ("bench.chain", 1000, 41000),
        ("pocl.enqueue_write", 1000, 3000, {"event": 1}),
        ("pocl.enqueue_kernel", 3000, 6000, {"event": 2}),
        ("pocl.enqueue_read", 6000, 7000, {"event": 3}),
        ("pocl.finish", 8000, 40000),
        ("pocl.kernel", 10000, 20000, {"event": 2, "server": "s0"}),
        ("pocl.commit", 22000, 35000, {"event": 2, "bytes": 4}),
        ("np.asarray(jax.Array)", 23000, 34000),
        ("bench.chain", 50000, 90000),
        ("pocl.enqueue_write", 50000, 51000, {"event": 4}),
        ("pocl.enqueue_kernel", 51000, 53000, {"event": 5}),
        ("pocl.enqueue_read", 53000, 54000, {"event": 6}),
        ("pocl.finish", 54000, 88000),
        ("pocl.kernel", 55000, 65000, {"event": 5, "server": "s0"}),
        ("pocl.commit", 66000, 86000, {"event": 5, "bytes": 4}),
        ("pocl.finish", 95000, 120000)]},
}

# Two CFD jobs: the split, a drain whose kernel calls hold the copies
# (the device busy inside the copy back), the halo exchange and the
# concatenation.
JOBS = {
    "/device:TPU:0": {"XLA Ops": [("fusion.1", 13000, 17000),
                                  ("fusion.1", 63000, 65000)]},
    "/host:CPU": {"python3": [
        ("bench.window", 0, 100000),
        ("bench.job", 0, 40000),
        ("lbm.split", 1000, 9000),
        ("pocl.finish", 10000, 30000),
        ("pocl.kernel", 10000, 20000, {"event": 9, "server": "s0"}),
        ("lbm.h2d", 10000, 12000, {"server": "s0", "bytes": SLAB}),
        ("lbm.d2h", 12000, 19000, {"server": "s0", "bytes": SLAB}),
        ("pocl.commit", 20000, 21000, {"event": 9}),
        ("lbm.exchange_halos", 30000, 33000),
        ("lbm.concatenate", 34000, 39000),
        ("bench.job", 50000, 90000),
        ("lbm.split", 50000, 60000),
        ("pocl.kernel", 60000, 70000, {"event": 19, "server": "s1"}),
        ("lbm.h2d", 60000, 61000, {"server": "s1", "bytes": SLAB}),
        ("lbm.d2h", 62000, 66000, {"server": "s1", "bytes": SLAB}),
        ("lbm.exchange_halos", 71000, 72000),
        ("lbm.concatenate", 80000, 83000)]},
}


@pytest.fixture(scope="module")
def chains(tmp_path_factory):
    path = write_trace(CHAINS, tmp_path_factory.mktemp("t") / "c.pb")
    return SimpleNamespace(trace=tracereduce.load(path, [0]),
                           window=SimpleNamespace(trace_file=path))


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    path = write_trace(JOBS, tmp_path_factory.mktemp("t") / "j.pb")
    return SimpleNamespace(trace=tracereduce.load(path, [0]),
                           window=SimpleNamespace(trace_file=path))


def read(name: str, ctx):
    return harness.load_module(harness.metric_file(name)).read(ctx)


def test_leaves_give_each_instant_to_the_innermost_span():
    spans = [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 50, 60),
             ("e", 100, 110), ("z", 60, 60)]
    assert progspans.leaves(spans) == [
        ("a", 0, 10), ("b", 10, 20), ("c", 20, 30), ("b", 30, 40),
        ("a", 40, 50), ("d", 50, 60), ("a", 60, 100), ("e", 100, 110)]


def test_program_spans_are_selected_by_prefix_and_clipped(chains):
    spans = progspans.program_spans(chains.trace)
    assert len(spans) == 13
    assert not any(n.startswith(("bench.", "np.")) for n, _, _ in spans)
    assert spans[-1] == ("pocl.finish", 95000, 100000)


def test_chain_readers_on_a_hand_built_trace(chains):
    # self time of the enqueues and the drains less what they hold:
    # (2000+3000+1000 + 32000-23000) + (1000+2000+1000 + 34000-30000)
    # + 5000 of the drain clipped at the window's end
    assert read("passthrough.runtime_us_per_chain", chains) == \
        pytest.approx(28000 / 2 / 1e3)
    assert read("passthrough.kernel_call_us_per_chain", chains) == \
        pytest.approx((10000 + 10000) / 2 / 1e3)
    assert read("passthrough.commit_us_per_chain", chains) == \
        pytest.approx((13000 + 20000) / 2 / 1e3)


def test_cfd_readers_on_a_hand_built_trace(jobs):
    assert read("cfd.split_s_per_job", jobs) == pytest.approx(
        (8000 + 10000) / 2 / 1e9)
    assert read("cfd.halo_s_per_job", jobs) == pytest.approx(
        (3000 + 1000) / 2 / 1e9)
    # the copies less the device's busy time in them (4000 and 2000 ns)
    assert read("cfd.transfer_s_per_job", jobs) == pytest.approx(
        (2000 + 7000 - 4000 + 1000 + 4000 - 2000) / 2 / 1e9)
    assert read("cfd.assemble_s_per_job", jobs) == pytest.approx(
        (5000 + 3000) / 2 / 1e9)
    assert read("cfd.host_device_gb_per_job", jobs) == 4 * SLAB / 2 / 1e9


def test_stats_sum_only_the_program_spans_in_the_window(tmp_path):
    planes = {"/host:CPU": {"python3": [
        ("bench.window", 100, 1000), ("bench.job", 100, 1000),
        ("lbm.h2d", 50, 150, {"bytes": 7}),         # starts before it
        ("lbm.d2h", 200, 300, {"bytes": 11}),
        ("TransferToDevice", 300, 400, {"bytes": 13}),
        ("pocl.commit", 400, 500, {"event": 3}),
        ("pocl.commit", 500, 600, {"bytes": 17})]}}
    path = write_trace(planes, tmp_path / "s.pb")
    red = tracereduce.load(path, [])
    assert progspans.stat_sum(path, red, "bytes") == 11 + 17
    assert progspans.stat_sum(path, red, "server") is None


def test_idle_by_leaf_on_a_hand_built_trace(chains):
    # idle ns by leaf: every instant of the window outside the three
    # modules, given to its innermost program span, its request, or none
    expect = {"pocl.commit": 8000 + 20000,
              "pocl.finish": 9000 + 4000 + 5000,
              OUT: 1000 + 9000 + 5000,
              "pocl.kernel": 4000 + 8000,
              "pocl.enqueue_kernel": 3000 + 2000,
              "bench.chain": 1000 + 1000 + 2000,
              "pocl.enqueue_write": 2000 + 1000,
              "pocl.enqueue_read": 1000 + 1000}
    got = progspans.idle_by_leaf(chains.trace)
    assert got == [[n, ns / 1e9] for n, ns in expect.items()]
    assert sum(s for _, s in got) == pytest.approx(
        tracereduce.idle_share(chains.trace)
        * tracereduce.window_s(chains.trace))


OUT = progspans.OUTSIDE


@pytest.mark.parametrize("name,readers", [("cfd", CFD_READERS),
                                          ("passthrough", CHAIN_READERS)])
def test_a_program_without_spans_gives_nothing(name, readers):
    """The traces of ``data/`` were recorded before the program had
    spans, as the parent of this change is: no reader raises, and each
    leaves its metric out."""
    path = os.path.join(DATA, f"{name}.xplane.pb")
    ctx = SimpleNamespace(trace=tracereduce.load(path, [0]),
                          window=SimpleNamespace(trace_file=path))
    assert progspans.program_spans(ctx.trace) == []
    for r in readers:
        assert read(r, ctx) is None, r
        assert read(r, SimpleNamespace(trace=None)) is None, r


# ---- traces recorded on a TPU v5e (record_trace.py) ----

def recorded(name: str):
    path = os.path.join(DATA, f"{name}_spans.xplane.pb")
    return SimpleNamespace(trace=tracereduce.load(path, [0]),
                           window=SimpleNamespace(trace_file=path))


def test_recorded_cfd_spans():
    ctx = recorded("cfd")
    red = ctx.trace
    jobs = tracereduce.span_durations(red, "bench.job")
    spans = progspans.program_spans(red)
    count = {n: sum(1 for m, _, _ in spans if m == n)
             for n in {m for m, _, _ in spans}}
    n = len(jobs)
    # jobs of two steps on two servers
    assert n >= 2
    assert count["lbm.split"] == count["lbm.concatenate"] == n
    assert count["lbm.exchange_halos"] == n
    assert count["lbm.h2d"] == count["lbm.d2h"] == count["pocl.kernel"] \
        == 4 * n
    assert count["pocl.finish"] == 2 * n
    # a half slab of the 256x512 lattice, both ways, two steps, two slabs
    half = 9 * 256 * 258 * 4
    assert read("cfd.host_device_gb_per_job", ctx) == 8 * half / 1e9
    for name, names in [("cfd.split_s_per_job", {"lbm.split"}),
                        ("cfd.halo_s_per_job", {"lbm.exchange_halos"}),
                        ("cfd.assemble_s_per_job", {"lbm.concatenate"})]:
        assert read(name, ctx) == pytest.approx(
            sum(e - s for m, s, e in spans if m in names) / n / 1e9)
    copies = [(s, e) for m, s, e in spans if m in ("lbm.h2d", "lbm.d2h")]
    busy = sum(tracereduce.busy_within(red, copies))
    assert 0 < busy
    assert read("cfd.transfer_s_per_job", ctx) == pytest.approx(
        (sum(e - s for s, e in copies) - busy) / n / 1e9)
    # one lbm_step call a kernel call; at this size a call lasts ~34 us,
    # less than the device clock's offset from the host's is known to,
    # so where each falls among the host spans is not checked
    assert len(tracereduce.module_events(red, "jit_lbm_step")) == 4 * n


def test_recorded_chain_spans_add_up_to_the_host_time():
    ctx = recorded("passthrough")
    red = ctx.trace
    chains = tracereduce.span_durations(red, "bench.chain")
    n = len(chains)
    assert n >= 2
    parts = [read(r, ctx) for r in CHAIN_READERS]
    assert all(p > 0 for p in parts)
    host = read("passthrough.host_us_per_chain", ctx)
    leaf = dict(progspans.idle_by_leaf(red))
    # outside every program span a chain only draws its value: its share
    # of the chains' time is small
    chain_s = sum(e - s for s, e in chains) / 1e9
    assert leaf.get("bench.chain", 0) < 0.1 * chain_s
    assert sum(parts) == pytest.approx(host, rel=0.1)


@pytest.mark.parametrize("name", ["cfd", "passthrough"])
def test_recorded_idle_by_leaf_sums_to_the_idle_time(name):
    red = recorded(name).trace
    got = progspans.idle_by_leaf(red)
    secs = [s for _, s in got]
    assert secs == sorted(secs, reverse=True) and all(s > 0 for s in secs)
    assert sum(secs) == pytest.approx(
        tracereduce.idle_share(red) * tracereduce.window_s(red))
    names = {n for n, _ in got}
    assert names - {OUT} <= {n for n, _, _ in progspans.program_spans(red)} \
        | {n for n, _, _ in red.spans}
    assert "pocl.kernel" in names or "lbm.d2h" in names
