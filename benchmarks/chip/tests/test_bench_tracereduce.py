"""The reduction from a profiler trace to the per-layer metrics: on a trace
built by hand, whose busy union, kernel time and idle gaps are known
exactly, and on small traces recorded on a TPU v5e by ``record_trace.py``
(``data/``)."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP]

import harness  # noqa: E402
import tracereduce  # noqa: E402

DATA = os.path.join(HERE, "data")

# plane -> line -> events (name, start ns, end ns)
SYNTHETIC = {
    "/device:TPU:0": {
        "XLA Ops": [("fusion.1", 1000, 6000), ("copy.2", 4000, 8000),
                    ("fusion.1", 12000, 13000)],
        "XLA Modules": [("jit_lbm_step(42)", 1000, 8000),
                        ("jit_other(7)", 12000, 13000),
                        ("jit_copy(3)", 15000, 15500)]},
    "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 10000)]},
    "/host:CPU": {"python3": [
        ("bench.window", 0, 20000), ("bench.chain", 500, 9000),
        ("bench.chain", 10000, 19000), ("PjitFunction(copy)", 9000, 11500),
        ("TransferToHost", 13000, 19000)]},
}


def synthetic():
    """``SYNTHETIC`` as a ``ProfileData``, through the XSpace text format
    (event offsets in ps from the line's start)."""
    from jax.profiler import ProfileData
    planes = []
    for pid, (plane, lines) in enumerate(SYNTHETIC.items(), 1):
        ids = {n: i for i, n in enumerate(
            sorted({n for evs in lines.values() for n, _, _ in evs}), 1)}
        body = [f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                + " ".join(f"events {{ metadata_id: {ids[n]} "
                           f"offset_ps: {s * 1000} "
                           f"duration_ps: {(e - s) * 1000} }}"
                           for n, s, e in evs) + " }"
                for lid, (line, evs) in enumerate(lines.items(), 1)]
        body += [f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{n}" }} }}' for n, i in ids.items()]
        planes.append(f'planes {{ id: {pid} name: "{plane}" '
                      + " ".join(body) + " }")
    return ProfileData.from_text_proto("\n".join(planes))


@pytest.fixture(scope="module")
def one():
    return tracereduce.reduce(synthetic(), [0])


def test_busy_union_and_idle_share(one):
    # a module that shows no op (jit_copy) is busy all the same
    assert one.busy == {0: [(1000, 8000), (12000, 13000), (15000, 15500)]}
    assert tracereduce.window_s(one) == 20000 / 1e9
    assert tracereduce.busy_s(one) == 8500 / 1e9
    assert tracereduce.idle_share(one) == pytest.approx(0.575)
    both = tracereduce.reduce(synthetic(), [0, 1])
    assert tracereduce.busy_s(both) == (8500 + 10000) / 2 / 1e9


def test_kernel_time_by_module_name(one):
    evs = tracereduce.module_events(one, "jit_lbm_step")
    assert [(s, e) for _, _, s, e in evs] == [(1000, 8000)]
    assert tracereduce.module_events(one, "jit_lbm") == []


def test_top_ops_sum_over_events_by_module(one):
    assert tracereduce.top_ops(one) == [
        ["jit_lbm_step/fusion.1", 5000 / 1e9],
        ["jit_lbm_step/copy.2", 4000 / 1e9],
        ["jit_other/fusion.1", 1000 / 1e9], ["jit_copy", 500 / 1e9]]


def test_idle_gaps_named_by_span_and_host_event(one):
    assert tracereduce.idle_gaps(one) == [
        ["bench.chain: TransferToHost", 4500 / 1e9],
        ["bench.chain: PjitFunction(copy)", 4000 / 1e9],
        ["bench.chain: TransferToHost", 2000 / 1e9],
        ["bench.chain", 1000 / 1e9]]


def test_busy_within_spans(one):
    spans = tracereduce.span_durations(one, "bench.chain")
    assert spans == [(500, 9000), (10000, 19000)]
    assert tracereduce.busy_within(one, spans) == [7000, 1500]


def test_host_us_per_chain_reader(one):
    mod = harness.load_module(
        harness.metric_file("passthrough.host_us_per_chain"))
    ctx = SimpleNamespace(trace=one)
    assert mod.read(ctx) == pytest.approx(
        ((8500 - 7000) + (9000 - 1500)) / 2 / 1e3)
    assert mod.read(SimpleNamespace(trace=None)) is None


def busy_only(busy: dict) -> tracereduce.Reduced:
    return tracereduce.Reduced(t0=0, t1=1000, busy=busy, ops=[],
                               modules=[], spans=[], host=[])


@pytest.mark.parametrize("busy,expect", [
    ({0: [(0, 100)], 1: [(0, 100)]}, 2.0),           # the same interval
    ({0: [(0, 100)], 1: [(50, 100)]}, 1.5),          # over half of chip 0
    ({0: [(0, 100), (300, 400)]}, 1.0),              # one chip
    ({d: [(100 * d, 100 * d + 100)] for d in range(4)}, 1.0),  # in turn
    ({d: [(0, 100)] for d in range(4)}, 4.0),        # all four at once
])
def test_chips_busy_at_once_reader(busy, expect):
    mod = harness.load_module(harness.metric_file("cfd.chips_busy_at_once"))
    assert mod.read(SimpleNamespace(trace=busy_only(busy))) == expect


def test_chips_busy_at_once_reads_nothing_without_busy_time():
    mod = harness.load_module(harness.metric_file("cfd.chips_busy_at_once"))
    assert mod.read(SimpleNamespace(trace=None)) is None
    assert mod.read(SimpleNamespace(trace=busy_only({0: [], 1: []}))) \
        is None


def test_a_trace_without_the_window_is_refused():
    from jax.profiler import ProfileData
    with pytest.raises(ValueError, match="bench.window"):
        tracereduce.reduce(ProfileData.from_text_proto(""), [0])


# ---- traces recorded on a TPU v5e (record_trace.py) ----

def recorded(name: str):
    return tracereduce.load(os.path.join(DATA, f"{name}.xplane.pb"), [0])


def brute_busy_ns(red) -> int:
    """Busy ns of device 0 by a sweep over event edges, apart from
    ``merge``."""
    edges = sorted([(s, 1) for _, _, s, _ in red.ops + red.modules]
                   + [(e, -1) for _, _, _, e in red.ops + red.modules])
    busy, depth, since = 0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


@pytest.mark.parametrize("name", ["cfd", "passthrough"])
def test_recorded_busy_union(name):
    red = recorded(name)
    ivs = red.busy[0]
    assert all(a < b for a, b in ivs)
    assert all(b1 < a2 for (_, b1), (a2, _) in zip(ivs, ivs[1:]))
    assert red.t0 <= ivs[0][0] and ivs[-1][1] <= red.t1
    assert tracereduce.busy_s(red) * 1e9 == pytest.approx(brute_busy_ns(red))
    assert 0 < tracereduce.busy_s(red) < tracereduce.window_s(red)
    assert tracereduce.idle_share(red) == pytest.approx(
        1 - tracereduce.busy_s(red) / tracereduce.window_s(red))


def test_recorded_cfd_kernel_time():
    red = recorded("cfd")
    jobs = tracereduce.span_durations(red, "bench.job")
    evs = tracereduce.module_events(red, "jit_lbm_step")
    # three jobs of two steps on two servers: one call per slab and step
    assert len(jobs) == 3 and len(evs) == 3 * 2 * 2
    assert all(any(a <= s and e <= b for a, b in jobs)
               for _, _, s, e in evs)
    ctx = SimpleNamespace(trace=red, device_kind="TPU v5 lite",
                          window=SimpleNamespace(work=[256 * 512 * 2] * 3))
    ms = harness.load_module(harness.metric_file("cfd.lbm_step_ms")).read(
        ctx)
    assert ms == pytest.approx(
        sum(e - s for _, _, s, e in evs) / len(evs) / 1e6)
    share = harness.load_module(
        harness.metric_file("lbm_step_roofline")).read(ctx)
    least_s = 72 * 256 * 512 * 2 * 3 / 819e9
    assert share == pytest.approx(100 * least_s / (ms * len(evs) / 1e3))
    assert 0 < share < 100


@pytest.mark.parametrize("name,span", [("cfd", "bench.job"),
                                       ("passthrough", "bench.chain")])
def test_recorded_idle_gaps_are_named_by_the_benchmark_spans(name, span):
    red = recorded(name)
    top = tracereduce.idle_gaps(red)
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= tracereduce.window_s(red)
    assert all(label.startswith(span) for label, _ in top)
    # the longest gaps wait on a kernel's output copied to the host
    assert top[0][0] == f"{span}: np.asarray(jax.Array)"


def test_recorded_passthrough_host_time():
    red = recorded("passthrough")
    chains = tracereduce.span_durations(red, "bench.chain")
    assert len(chains) == 6
    # the copy shows only as a module, one per chain
    assert len(red.ops) == 0 and len(red.modules) == 6
    busy = tracereduce.busy_within(red, chains)
    assert all(b > 0 for b in busy)
    mod = harness.load_module(
        harness.metric_file("passthrough.host_us_per_chain"))
    assert mod.read(SimpleNamespace(trace=red)) == pytest.approx(
        (sum(e - s for s, e in chains) - sum(busy)) / 6 / 1e3)
