"""The reader of the program's ``pocl.read`` spans
(``passthrough.read_us_per_chain``) and what the read's ``bytes`` do to
the readers beside it, on a hand-built trace of two AR frames whose
commits keep the kernels' outputs on the device and whose reads copy the
indices off it; and on recorded traces of a program that copies at the
commit and opens no ``pocl.read`` span, where the reader gives
nothing."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [HERE, CHIP]

import harness  # noqa: E402
import tracereduce  # noqa: E402
from test_bench_progspans import write_trace  # noqa: E402

READER = "passthrough.read_us_per_chain"
INDICES = 3440000               # whole uint32[860000] indices

# Two frames: two kernel calls and their commits (no bytes: the outputs
# stay on the device), then a read that copies the size and the indices
# off the device, its last part the wait for the sort.
FRAMES = {
    "/device:TPU:0": {"XLA Modules": [
        ("jit_ar_reconstruct(1)", 12000, 14000),
        ("jit_ar_sort(2)", 16000, 30000),
        ("jit_ar_reconstruct(1)", 62000, 64000),
        ("jit_ar_sort(2)", 66000, 78000)]},
    "/host:CPU": {"python3": [
        ("bench.window", 0, 100000),
        ("bench.chain", 1000, 45000),
        ("pocl.enqueue_read", 3000, 4000, {"event": 6}),
        ("pocl.finish", 8000, 44000),
        ("pocl.kernel", 10000, 13000, {"event": 4, "server": "s0"}),
        ("pocl.commit", 13000, 13500, {"event": 4}),
        ("pocl.kernel", 14000, 15000, {"event": 5, "server": "s0"}),
        ("pocl.commit", 15000, 15500, {"event": 5}),
        ("pocl.read", 16000, 40000, {"event": 6, "bytes": INDICES + 4}),
        ("bench.chain", 50000, 95000),
        ("pocl.enqueue_read", 51000, 52000, {"event": 12}),
        ("pocl.finish", 55000, 94000),
        ("pocl.kernel", 60000, 63000, {"event": 10, "server": "s0"}),
        ("pocl.commit", 63000, 63500, {"event": 10}),
        ("pocl.kernel", 64000, 65000, {"event": 11, "server": "s0"}),
        ("pocl.commit", 65000, 65500, {"event": 11}),
        ("pocl.read", 66000, 88000, {"event": 12, "bytes": INDICES + 4}),
        ("pocl.read", 98000, 120000, {"event": 18})]},
}


def read(name: str, ctx):
    return harness.load_module(harness.metric_file(name)).read(ctx)


def ctx_of(path):
    return SimpleNamespace(trace=tracereduce.load(path, [0]),
                           window=SimpleNamespace(trace_file=path))


@pytest.fixture(scope="module")
def frames(tmp_path_factory):
    return ctx_of(write_trace(FRAMES, tmp_path_factory.mktemp("t") /
                              "r.pb"))


def test_read_reader_on_a_hand_built_trace(frames):
    # the two reads, and the one clipped at the window's end
    assert read(READER, frames) == pytest.approx(
        (24000 + 22000 + 2000) / 2 / 1e3)


def test_readers_beside_it_leave_the_read_out_or_count_its_bytes(frames):
    """The read is a program span of its own: the commit's and the
    command path's self times leave it out, and the bytes copied off the
    device per frame are the reads'."""
    assert read("passthrough.commit_us_per_chain", frames) == \
        pytest.approx(4 * 500 / 2 / 1e3)
    # the enqueues, and the drains less their kernel calls, commits and
    # reads
    assert read("passthrough.runtime_us_per_chain", frames) == \
        pytest.approx((1000 + 1000 + (36000 - 4000 - 1000 - 24000)
                       + (39000 - 4000 - 1000 - 22000)) / 2 / 1e3)
    assert read("ar.commit_mb_per_frame", frames) == pytest.approx(
        2 * (INDICES + 4) / 2 / 1e6)


@pytest.mark.parametrize("name", ["passthrough", "passthrough_spans",
                                  "cfd_spans"])
def test_a_program_without_read_spans_gives_nothing(name):
    """The traces of ``data/`` were recorded from programs that open no
    ``pocl.read`` span (the later ones copy at the commit): the reader
    does not raise and leaves its metric out; untraced, it gives
    nothing."""
    ctx = ctx_of(os.path.join(HERE, "data", f"{name}.xplane.pb"))
    assert read(READER, ctx) is None
    assert read(READER, SimpleNamespace(trace=None, window=None)) is None
