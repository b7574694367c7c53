"""The AR deployment (``entries/ar_offload.py``) driven through a whole run
of the harness on the CPU at a tiny capacity: it comes out correct, and
not correct with its control, with culling or the order broken
underneath, or with a wrong camera in the program's pose buffer (the
reference builds its own). Its metric readers, and the pass-through
readers it shares, on a hand-built trace whose ``ar.h2d`` spans sit
inside ``pocl.kernel``; and the AR readers on a recorded trace of a
program with no AR spans, where they give nothing."""
import copy
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [HERE, CHIP, os.path.join(ROOT, "src")]

import harness  # noqa: E402
import progspans  # noqa: E402
import tracereduce  # noqa: E402
from repro.apps import ar  # noqa: E402
from test_bench_progspans import write_trace  # noqa: E402

CELL = "ar_pointcloud_860k.frame"
SEED = 2**31 + 17               # larger than 32 signed bits hold
SECONDS = 0.3
CAP = 2048
READERS = ["ar.reconstruct_ms", "ar_reconstruct_roofline", "ar.sort_ms",
           "ar_sort_roofline", "ar.commit_mb_per_frame",
           "ar.h2d_us_per_frame"]


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def tiny() -> harness.Cell:
    cell = harness.load_cell(harness.load_json(harness.spec_path()), CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["capacity"] = CAP
    return cell


def run(cell, capsys) -> dict:
    rc = harness.run(cell, SEED, SECONDS, False, time.perf_counter(),
                     require_tpu=False)
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    tail = out.err.strip().splitlines()[-len(result["checks"]):]
    for line, name in zip(tail, result["checks"]):
        assert line.startswith(f"check {name}: ") and "limit" in line
    assert "visible share" in out.err
    return result


def test_cell_runs_correct(capsys):
    cell = tiny()
    result = run(cell, capsys)
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"chain_p50_us", "chain_p95_us",
                                      "setup_s"}
    assert set(result["checks"]) == {"points_misplaced",
                                     "order_violations"}


def _no_culling():
    """Every point of the frame is kept, whatever the frustum: the
    kernel sees no near or far plane and a field of view of almost
    180 degrees."""
    import jax

    body = ar.ar_reconstruct.__wrapped__

    @jax.jit
    def reconstruct(params, size, geom):
        params = params.at[ar.NEAR].set(-1e30).at[ar.FAR].set(1e30)
        return body(params.at[ar.TAN_HALF_FOV].set(1e30), size, geom)
    return "ar_reconstruct", reconstruct


def _wide_view():
    """The pose buffer holds tan of the whole field of view, not of its
    half."""
    view_params = ar.view_params

    def wide(eye, target, fov_y, **camera):
        return view_params(eye, target, 2 * fov_y, **camera)
    return "view_params", wide


def _backwards_view():
    """The pose buffer's camera looks away from its target."""
    view_params = ar.view_params

    def backwards(*args, **camera):
        out = view_params(*args, **camera)
        out[..., ar.VIEW.start + 8:ar.VIEW.start + 12] *= -1
        return out
    return "view_params", backwards


def _reversed():
    """The visible points front to back."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sort(keys):
        front = jnp.where(keys > -jnp.inf, keys, jnp.inf)
        visible = jnp.sum(keys > -jnp.inf, dtype=jnp.uint32)
        return (jnp.argsort(front, stable=True).astype(jnp.uint32),
                (visible * ar.INDEX_BYTES)[None])
    return "ar_sort", sort


FAULTS = {"no_culling": (_no_culling, "points_misplaced"),
          "reversed": (_reversed, "order_violations"),
          "wide_view": (_wide_view, "points_misplaced"),
          "backwards_view": (_backwards_view, "points_misplaced")}


@pytest.mark.parametrize("fault", ["control", *FAULTS])
def test_broken_program_is_not_correct(fault, capsys, monkeypatch):
    cell = tiny()
    if fault == "control":
        with cell.entry.control():
            result = run(cell, capsys)
        assert result["checks"]["order_violations"]["value"] > 0
    else:
        patch, check = FAULTS[fault]
        monkeypatch.setattr(ar, *patch())
        result = run(cell, capsys)
        assert result["checks"][check]["value"] > 0
    assert result["correct"] is False, result
    assert result["failed"] > 0


def test_same_seed_same_frames_and_poses():
    cell = tiny()
    e = cell.entry

    def draw(seed):
        rng = np.random.default_rng(seed)
        geom, counts = e.make_clip(cell.config, rng)
        walk = e.PoseWalk(cell.config, rng)
        poses = [walk() for _ in range(e.POSES + 3)]
        return (geom, counts, np.stack([p[0] for p in poses]),
                np.stack([p[1] for p in poses]))

    a, b, c = draw(SEED), draw(SEED), draw(SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[2], c[2])
    geom, counts, eye_target, buffers = a
    assert geom.shape == (cell.config["clip_frames"], 3, CAP // 2)
    slots = np.concatenate([geom & 0xFFFF, geom >> 16], axis=2)
    assert geom.dtype == np.uint32 and slots.max() < 1024
    assert ((counts >= 0.9 * CAP) & (counts <= CAP)).all()
    assert all((q[:, n:] == 0).all() for q, n in zip(slots, counts))
    assert eye_target.shape == (e.POSES + 3, 2, 3)
    assert buffers.dtype == np.float32 and buffers.shape[1] == ar.PARAMS
    np.testing.assert_array_equal(buffers, ar.view_params(
        eye_target[:, 0], eye_target[:, 1], **e.camera(cell.config)))


# Two frames: each a reconstruct and a sort, whose uploads (``ar.h2d``)
# sit inside their kernel calls, and a commit after each; the device runs
# the two kernels.
FRAMES = {
    "/device:TPU:0": {"XLA Modules": [
        ("jit_ar_reconstruct(1)", 12000, 14000),
        ("jit_ar_sort(2)", 20000, 26000),
        ("jit_ar_reconstruct(1)", 62000, 64000),
        ("jit_ar_sort(2)", 70000, 75000)]},
    "/host:CPU": {"python3": [
        ("bench.window", 0, 100000),
        ("bench.chain", 1000, 45000),
        ("pocl.enqueue_write", 1000, 3000, {"event": 1}),
        ("pocl.finish", 8000, 44000),
        ("pocl.kernel", 10000, 15000, {"event": 4, "server": "s0"}),
        ("ar.h2d", 10000, 11500, {"server": "s0", "bytes": 5160100}),
        ("pocl.commit", 15000, 19000, {"event": 4, "bytes": 3440000}),
        ("pocl.kernel", 19000, 27000, {"event": 5, "server": "s0"}),
        ("ar.h2d", 19000, 19800, {"server": "s0", "bytes": 3440000}),
        ("pocl.commit", 27000, 40000, {"event": 5, "bytes": 2000004}),
        ("bench.chain", 50000, 95000),
        ("pocl.finish", 55000, 94000),
        ("pocl.kernel", 60000, 65000, {"event": 9, "server": "s0"}),
        ("ar.h2d", 60000, 61000, {"server": "s0", "bytes": 5160100}),
        ("pocl.commit", 65000, 68000, {"event": 9, "bytes": 3440000}),
        ("pocl.kernel", 68000, 76000, {"event": 10, "server": "s0"}),
        ("ar.h2d", 68000, 68500, {"server": "s0", "bytes": 3440000}),
        ("pocl.commit", 76000, 90000, {"event": 10, "bytes": 1500004}),
        ("ar.h2d", 99000, 120000, {"server": "s0", "bytes": 1})]},
}
CAPACITY = 860000
PEAK = 819e9


def ctx_of(path):
    return SimpleNamespace(
        trace=tracereduce.load(path, [0]), device_kind="TPU v5 lite",
        window=SimpleNamespace(trace_file=path),
        cell=SimpleNamespace(config={"capacity": CAPACITY}))


def read(name: str, ctx):
    return harness.load_module(harness.metric_file(name)).read(ctx)


@pytest.fixture(scope="module")
def frames(tmp_path_factory):
    return ctx_of(write_trace(FRAMES, tmp_path_factory.mktemp("t") /
                              "f.pb"))


def test_readers_on_a_hand_built_trace(frames):
    assert read("ar.reconstruct_ms", frames) == pytest.approx(
        (2000 + 2000) / 2 / 1e6)
    assert read("ar.sort_ms", frames) == pytest.approx(
        (6000 + 5000) / 2 / 1e6)
    assert read("ar_reconstruct_roofline", frames) == pytest.approx(
        100 * 10 * CAPACITY * 2 / PEAK / 4000e-9)
    assert read("ar_sort_roofline", frames) == pytest.approx(
        100 * 8 * CAPACITY * 2 / PEAK / 11000e-9)
    assert read("ar.commit_mb_per_frame", frames) == pytest.approx(
        (3440000 + 2000004 + 3440000 + 1500004) / 2 / 1e6)
    # the uploads inside the kernel calls, and the one clipped at the
    # window's end
    assert read("ar.h2d_us_per_frame", frames) == pytest.approx(
        (1500 + 800 + 1000 + 500 + 1000) / 2 / 1e3)


def test_pass_through_readers_on_the_ar_trace(frames):
    """A frame is one chain: the pass-through cell's readers of the
    command path read it as they read a 4-byte chain."""
    assert read("passthrough.device_idle_share", frames) == pytest.approx(
        100 * (1 - 15000 / 100000))
    assert read("passthrough.commit_us_per_chain", frames) == \
        pytest.approx((4000 + 13000 + 3000 + 14000) / 2 / 1e3)
    # a kernel call's self time holds its upload (``ar.h2d``)
    assert read("passthrough.kernel_call_us_per_chain", frames) == \
        pytest.approx((5000 + 8000 + 5000 + 8000) / 2 / 1e3)
    # the write, and what the two drains spend outside kernel and commit
    assert read("passthrough.runtime_us_per_chain", frames) == \
        pytest.approx((2000 + (36000 - 30000) + (39000 - 30000)) / 2 / 1e3)
    # each chain less the device's busy time inside it
    assert read("passthrough.host_us_per_chain", frames) == pytest.approx(
        ((44000 - 8000) + (45000 - 7000)) / 2 / 1e3)


def test_ar_spans_are_no_program_spans_of_progspans(frames):
    """``progspans`` does not see ``ar.*``: a kernel call's self time
    holds its upload."""
    assert progspans.self_ns(frames.trace, ("pocl.kernel",)) == \
        5000 + 8000 + 5000 + 8000
    assert not any(n.startswith("ar.")
                   for n, _, _ in progspans.program_spans(frames.trace))


def test_readers_give_nothing_without_ar_spans_or_kernels():
    """On a recorded pass-through trace (no AR kernel or span) every AR
    reader gives nothing but the commit's bytes, which are the chain's
    4-byte output; untraced, all give nothing."""
    ctx = ctx_of(os.path.join(HERE, "data", "passthrough_spans.xplane.pb"))
    got = {name: read(name, ctx) for name in READERS}
    assert got.pop("ar.commit_mb_per_frame") == pytest.approx(4e-6)
    assert got == dict.fromkeys(got)
    untraced = SimpleNamespace(trace=None, window=None)
    assert all(read(name, untraced) is None for name in READERS)
