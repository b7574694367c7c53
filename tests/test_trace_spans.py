"""The wall-clock spans of ``core.trace.span`` (DESIGN.md §9) on the CPU:
off, they are one shared no-op; under a ``jax.profiler`` trace, the
runtime's command path and the CFD offload loop record every span,
nested as documented and with their stats."""
import numpy as np
import pytest
from jax.profiler import ProfileData, trace as profiler_trace

from repro.apps import lbm
from repro.core import ClientRuntime, DeviceSpec, LinkSpec, ServerSpec
from repro.core import trace as trace_mod
from repro.core.runtime import Cluster

PREFIXES = ("pocl.", "lbm.")
H, W, SERVERS, STEPS = 64, 128, 2, 2


def recorded(tmp_path, work) -> list:
    """``work()`` under a profiler trace; the program spans it recorded,
    ``(name, start ns, end ns, stats)`` sorted by start."""
    with profiler_trace(str(tmp_path)):
        work()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def inside(inner, outers) -> bool:
    return any(s <= inner[1] and inner[2] <= e for _, s, e, _ in outers)


def named(spans, name) -> list:
    return [sp for sp in spans if sp[0] == name]


def chain(rt):
    """One pass-through chain: write, jitted copy, read, finish."""
    import jax
    import jax.numpy as jnp
    a, b = rt.create_buffer(4), rt.create_buffer(4)
    copy = jax.jit(jnp.copy)
    w = rt.enqueue_write("s0", a, np.array([7], np.int32))
    k = rt.enqueue_kernel("s0", fn=lambda x: copy(jax.device_put(x)),
                          inputs=[a], outputs=[b], wait_for=[w])
    rt.enqueue_read("s0", b, wait_for=[k])
    rt.finish()
    return b


def one_server():
    return ClientRuntime(servers=[ServerSpec("s0", [DeviceSpec("cpu")])],
                         client_link=LinkSpec(latency=1e-4,
                                              bandwidth=1e8))


@pytest.fixture(scope="module")
def f0():
    return np.asarray(lbm.init_shear(H, W))


@pytest.fixture(scope="module")
def cfd_spans(tmp_path_factory, f0):
    lbm.run_offloaded(f0, SERVERS, STEPS)        # compiled outside
    return recorded(tmp_path_factory.mktemp("cfd"),
                    lambda: lbm.run_offloaded(f0, SERVERS, STEPS))


@pytest.fixture(scope="module")
def chain_spans(tmp_path_factory):
    chain(one_server())
    return recorded(tmp_path_factory.mktemp("chain"),
                    lambda: chain(one_server()))


def test_off_span_is_one_shared_noop_and_makes_no_annotation(monkeypatch):
    made = []

    class Counting(trace_mod.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    assert not Counting.is_enabled()
    off = trace_mod.span("pocl.x", event=1)
    assert off is trace_mod.span("lbm.y") and off is trace_mod._OFF
    with off as sp:
        sp.set_metadata(bytes=4)
    b = chain(one_server())
    lbm.run_offloaded(np.asarray(lbm.init_shear(16, 32)), 2, 2)
    assert made == []
    np.testing.assert_array_equal(b.data, [7])


def test_cfd_records_every_span_nested_as_documented(cfd_spans):
    count = {n: len(named(cfd_spans, n)) for n in
             {sp[0] for sp in cfd_spans}}
    assert count == {
        "lbm.split": 1, "lbm.exchange_halos": STEPS - 1,
        "lbm.concatenate": 1, "pocl.finish": STEPS,
        "pocl.enqueue_write": SERVERS * STEPS,
        "pocl.enqueue_kernel": SERVERS * STEPS,
        "pocl.enqueue_read": SERVERS * STEPS,
        "pocl.kernel": SERVERS * STEPS, "pocl.commit": SERVERS * STEPS,
        "pocl.read": SERVERS * STEPS,
        "lbm.h2d": SERVERS * STEPS, "lbm.d2h": SERVERS * STEPS}
    finish = named(cfd_spans, "pocl.finish")
    kernels = named(cfd_spans, "pocl.kernel")
    assert all(inside(k, finish) for k in kernels)
    for name in ("pocl.commit", "pocl.read"):
        assert all(inside(c, finish) for c in named(cfd_spans, name))
    for name in ("lbm.h2d", "lbm.d2h"):
        assert all(inside(c, kernels) for c in named(cfd_spans, name))
    for name in ("lbm.split", "lbm.exchange_halos", "lbm.concatenate"):
        assert not any(inside(sp, finish) for sp in named(cfd_spans, name))
    assert {sp[3]["server"] for sp in kernels} == {"s0", "s1"}
    assert all(isinstance(sp[3]["event"], int) for sp in kernels)


def test_chain_records_every_command_span(chain_spans):
    assert [sp[0] for sp in chain_spans] == [
        "pocl.enqueue_write", "pocl.enqueue_kernel", "pocl.enqueue_read",
        "pocl.finish", "pocl.kernel", "pocl.commit", "pocl.read"]
    w, k, r, fin, call, commit, read = chain_spans
    assert w[3]["event"] < k[3]["event"] < r[3]["event"]
    assert call[3] == {"event": k[3]["event"], "server": "s0"}
    assert inside(call, [fin]) and inside(commit, [fin])
    assert inside(read, [fin])
    assert call[2] <= commit[1] and commit[2] <= read[1]
    # the copy's output was a device array: the commit kept it there,
    # and its four bytes came back at the read
    assert commit[3] == {"event": k[3]["event"]}
    assert read[3] == {"event": r[3]["event"], "bytes": 4}


def test_byte_stats_sum_to_the_slab_bytes(cfd_spans, f0):
    slab = 9 * H * (W // SERVERS + 2) * f0.itemsize
    copies = named(cfd_spans, "lbm.h2d") + named(cfd_spans, "lbm.d2h")
    assert all(sp[3]["bytes"] == slab for sp in copies)
    total = sum(sp[3].get("bytes", 0) for sp in cfd_spans)
    assert total == 2 * SERVERS * STEPS * slab
    # the loop's kernels hand back host arrays: the commit copies none
    assert not any("bytes" in sp[3] for sp in named(cfd_spans,
                                                    "pocl.commit")
                   + named(cfd_spans, "pocl.read"))


def test_preemptive_policy_calls_each_kernel_once(tmp_path):
    """Under ``llf`` a kernel runs in slices; its function is called and
    its outputs committed once, on the last slice."""
    n = 3

    def work():
        cluster = Cluster([ServerSpec("s0", [DeviceSpec("gpu0")])],
                          scheduler="llf", scheduler_opts={"chunk": 1e-4})
        rt = ClientRuntime(cluster=cluster,
                           client_link=LinkSpec(latency=1e-4,
                                                bandwidth=1e8))
        buf = rt.create_buffer(64)
        evs = [rt.enqueue_write("s0", buf, np.zeros(16, np.float32))]
        for _ in range(n):
            evs.append(rt.enqueue_kernel("s0", fn=lambda x: x + 1.0,
                                         inputs=[buf], outputs=[buf],
                                         duration=1e-3,
                                         wait_for=[evs[-1]]))
        rt.finish()
        np.testing.assert_array_equal(buf.data, np.full(16, n, np.float32))

    spans = recorded(tmp_path, work)
    calls = named(spans, "pocl.kernel")
    assert len(calls) == n == len(named(spans, "pocl.commit"))
    assert len({sp[3]["event"] for sp in calls}) == n


ROWS = 1000     # rows of a content-sized uint32 output


def sized_chain(rt, used: int, size_from: str):
    """A kernel writes ``ROWS`` uint32 to ``out`` and ``used`` to a size
    buffer; ``size_from`` says whose content size that is: ``kernel``
    (``out``'s, written by the same kernel), ``client`` (``out``'s,
    written by the client before the kernel) or ``none`` (no buffer
    names it). ``out`` is read back."""
    import jax
    import jax.numpy as jnp
    size = rt.create_buffer(4)
    out = rt.create_buffer(ROWS * 4, content_size_buffer=None
                           if size_from == "none" else size)
    src = rt.create_buffer(4)
    fill = jax.jit(lambda u: (jnp.arange(ROWS, dtype=jnp.uint32), u))
    deps = [rt.enqueue_write("s0", src, np.array([used], np.int32))]
    outs = [out, size]
    if size_from == "client":
        deps.append(rt.enqueue_write("s0", size, np.array([used], np.int32)))
        outs = [out]
    k = rt.enqueue_kernel(
        "s0", fn=lambda u: fill(jax.device_put(u))[:len(outs)],
        inputs=[src], outputs=outs, wait_for=deps)
    rt.enqueue_read("s0", out, wait_for=[k])
    rt.finish()
    return out


@pytest.mark.parametrize("used,size_from,rows,copied", [
    (400, "kernel", 100, ROWS * 4 + 4),         # keeps 100 of 1000 rows
    (401, "kernel", 101, ROWS * 4 + 4),         # a part row is a row
    (4000, "kernel", ROWS, ROWS * 4 + 4),
    (0, "kernel", 0, ROWS * 4 + 4),
    (-5, "kernel", 0, ROWS * 4 + 4),            # corrupt: clamped to 0
    (10**6, "kernel", ROWS, ROWS * 4 + 4),      # corrupt: to nbytes
    (400, "client", ROWS, ROWS * 4),            # not this kernel's size
    (400, "none", ROWS, ROWS * 4),              # no content size
])
def test_commit_copies_the_used_prefix(tmp_path, used, size_from, rows,
                                       copied):
    """The buffer keeps the rows of its used prefix where the kernel
    wrote its size too. The commit leaves both outputs on the device;
    the read copies the size, where it is the buffer's, and then the
    whole output off the device."""
    sized_chain(one_server(), used, size_from)      # compiled outside
    got = []
    spans = recorded(tmp_path, lambda: got.append(
        sized_chain(one_server(), used, size_from)))
    out = got[0]
    assert isinstance(out.data, np.ndarray)
    np.testing.assert_array_equal(out.data, np.arange(rows,
                                                      dtype=np.uint32))
    (commit,) = named(spans, "pocl.commit")
    assert "bytes" not in commit[3]
    (read,) = named(spans, "pocl.read")
    assert read[3]["bytes"] == copied
    if size_from != "none":
        assert out.transfer_bytes() == min(max(used, 0), ROWS * 4)


def test_host_kernel_commits_the_used_prefix_and_copies_nothing(tmp_path):
    """A kernel whose outputs are host arrays keeps only the used rows
    of a content-sized output, and neither its commit nor the read
    copies anything off a device."""
    def work():
        rt = one_server()
        size = rt.create_buffer(4)
        out = rt.create_buffer(ROWS * 4, content_size_buffer=size)
        k = rt.enqueue_kernel("s0", fn=lambda: (
            np.arange(ROWS, dtype=np.uint32), np.array([402], np.int32)),
            outputs=[out, size])
        rt.enqueue_read("s0", out, wait_for=[k])
        rt.finish()
        np.testing.assert_array_equal(out.data, np.arange(101))

    spans = recorded(tmp_path, work)
    (commit,) = named(spans, "pocl.commit")
    assert "bytes" not in commit[3]
    (read,) = named(spans, "pocl.read")
    assert "bytes" not in read[3]


def test_device_output_stays_on_its_device_until_read(tmp_path):
    """A jitted kernel's output is committed as the device array it is,
    with no copy; a second kernel that takes the buffer gets that very
    array; nothing leaves the device until the read."""
    import jax
    import jax.numpy as jnp
    double = jax.jit(lambda x: 2 * x)
    seen = {}

    def work():
        rt = one_server()
        a, b, c = (rt.create_buffer(16) for _ in range(3))
        w = rt.enqueue_write("s0", a, np.arange(4, dtype=np.int32))
        k1 = rt.enqueue_kernel("s0", fn=lambda x: double(jnp.asarray(x)),
                               inputs=[a], outputs=[b], wait_for=[w])
        k1.on_complete(lambda _e: seen.setdefault("committed", b.data))

        def consume(x):
            seen["input"] = x
            return double(x)
        k2 = rt.enqueue_kernel("s0", fn=consume, inputs=[b], outputs=[c],
                               wait_for=[k1])
        rt.finish()
        seen["before_read"] = (b.data, c.data)
        rt.enqueue_read("s0", c, wait_for=[k2])
        rt.finish()
        seen["after_read"] = (b.data, c.data)

    spans = recorded(tmp_path, work)
    assert isinstance(seen["committed"], jax.Array)
    assert seen["input"] is seen["committed"]
    assert all(isinstance(d, jax.Array) for d in seen["before_read"])
    b_data, c_data = seen["after_read"]
    assert b_data is seen["committed"]
    assert isinstance(c_data, np.ndarray)
    np.testing.assert_array_equal(c_data, [0, 4, 8, 12])
    assert len(named(spans, "pocl.commit")) == 2
    assert not any("bytes" in sp[3] for sp in named(spans, "pocl.commit"))
    (read,) = named(spans, "pocl.read")
    assert read[3]["bytes"] == 16


def test_numpy_output_is_committed_as_returned(tmp_path):
    """A numpy kernel's output is the buffer's contents from its commit
    on, the very array it returned, and its read copies nothing."""
    made = []

    def kernel():
        made.append(np.arange(4, dtype=np.int32))
        return made[-1]

    def work():
        rt = one_server()
        out = rt.create_buffer(16)
        k = rt.enqueue_kernel("s0", fn=kernel, outputs=[out])
        k.on_complete(lambda _e: made.append(out.data))
        rt.enqueue_read("s0", out, wait_for=[k])
        rt.finish()
        made.append(out.data)

    spans = recorded(tmp_path, work)
    returned, committed, read_back = made
    assert committed is returned and read_back is returned
    (commit,), (read,) = named(spans, "pocl.commit"), named(spans,
                                                           "pocl.read")
    assert "bytes" not in commit[3] and "bytes" not in read[3]


@pytest.mark.parametrize("sized", [False, True])
def test_host_bytes_keep_version_and_validity(sized):
    """``Buffer.to_host()`` changes the representation, not the content:
    a content-sized output is cut to its used rows there, and neither it
    nor its size buffer changes ``version`` or ``valid_on``."""
    import jax
    import jax.numpy as jnp
    from repro.core.buffers import Buffer
    size = Buffer(4)
    size.set_data(jnp.array([12], jnp.int32), "s0")
    buf = Buffer(40, content_size_buffer=size if sized else None)
    buf.set_data(jnp.arange(10, dtype=jnp.uint32), "s0", cut=sized)
    before = [(b.version, set(b.valid_on)) for b in (buf, size)]
    # the whole output off its device, and its size first where it is
    # the buffer's
    assert buf.to_host() == 40 + (4 if sized else 0)
    host = buf.data
    assert isinstance(host, np.ndarray)
    np.testing.assert_array_equal(host, np.arange(3 if sized else 10))
    assert isinstance(size.data, np.ndarray if sized else jax.Array)
    assert [(b.version, b.valid_on) for b in (buf, size)] == before
    assert buf.host() is host and not buf.cut and buf.to_host() == 0


def test_kernel_input_waiting_for_its_cut_gets_the_used_rows():
    """A content-sized device output that a second kernel takes as input
    is cut first: the kernel sees the rows it would see had the first
    kernel handed back host arrays."""
    import jax
    import jax.numpy as jnp
    fill = jax.jit(lambda: (jnp.arange(ROWS, dtype=jnp.uint32),
                            jnp.array([400], jnp.int32)))
    seen = []
    rt = one_server()
    size = rt.create_buffer(4)
    out = rt.create_buffer(ROWS * 4, content_size_buffer=size)
    total = rt.create_buffer(8)
    k = rt.enqueue_kernel("s0", fn=fill, outputs=[out, size])
    rt.enqueue_kernel("s0", fn=lambda x: seen.append(x) or np.sum(x),
                      inputs=[out], outputs=[total], wait_for=[k])
    rt.finish()
    (x,) = seen
    assert isinstance(x, np.ndarray)
    np.testing.assert_array_equal(x, np.arange(100, dtype=np.uint32))
    assert int(total.data) == sum(range(100))
