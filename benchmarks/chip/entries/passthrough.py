"""The pass-through command deployment (paper Fig. 9, "copy one int"): a
client drives one server through ``ClientRuntime`` with chains of
commands on two ``int32[1]`` buffers.

One request is one chain: ``enqueue_write`` of one int32 drawn from the
seed over the whole int32 range into ``a``, ``enqueue_kernel`` of a
jitted copy ``a -> b`` on the server's chip, ``enqueue_read`` of ``b``,
then ``finish()``.

``correct`` compares every chain of the window: the value read back must
be the value written.
"""
from __future__ import annotations

import functools
import sys
from unittest import mock

import numpy as np

BLOCK = 1 << 16                 # values drawn from the seed at a time


def copy_kernel(device, through=None):
    """The chain's kernel: a jitted copy of its input on ``device``;
    ``through`` a dtype that the copy passes through on the way."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def copy(x):
        if through is None:
            return jnp.copy(x)
        return x.astype(through).astype(x.dtype)

    def run(a):
        return copy(jax.device_put(a, device))
    return run


def control():
    """The control of ``correct``: the copy made through float32, the
    precision below a 32-bit integer's, which breaks the guarantee that
    the value read back is the value written. It has to come out not
    correct."""
    import jax.numpy as jnp
    return mock.patch.object(
        sys.modules[__name__], "copy_kernel",
        functools.partial(copy_kernel, through=jnp.float32))


class Deployment:
    span = "bench.chain"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.core import ClientRuntime, DeviceSpec, LinkSpec, ServerSpec

        links = config["links"]
        device = devices[0]
        self.server = "s0"
        self.rt = ClientRuntime(
            servers=[ServerSpec(self.server, [DeviceSpec(device.device_kind)])],
            client_link=LinkSpec(**links["client"]),
            transport=config["transport"])
        dtype = np.dtype(config["buffer"]["dtype"])
        n = config["buffer"]["elements"]
        self.dtype, self.n = dtype, n
        self.a = self.rt.create_buffer(dtype.itemsize * n)
        self.b = self.rt.create_buffer(dtype.itemsize * n)
        self.kernel = copy_kernel(device)
        self.value_rng = np.random.default_rng(seed)
        self.values = np.empty((0, n), dtype)
        self.next = 0
        self.sent: list = []
        self.got: list = []
        # warm up: compile the copy and run the runtime's whole path
        for _ in range(traffic["warmup_chains"]):
            self.serve()
        self.sent.clear()
        self.got.clear()

    def draw(self) -> np.ndarray:
        if self.next == len(self.values):
            info = np.iinfo(self.dtype)
            self.values = self.value_rng.integers(
                info.min, info.max, (BLOCK, self.n), self.dtype,
                endpoint=True)
            self.next = 0
        v = self.values[self.next]
        self.next += 1
        return v

    def serve(self) -> int:
        rt, a, b = self.rt, self.a, self.b
        v = self.draw()
        w = rt.enqueue_write(self.server, a, v)
        k = rt.enqueue_kernel(self.server, fn=self.kernel, inputs=[a],
                              outputs=[b], wait_for=[w], name="copy")
        rt.enqueue_read(self.server, b, wait_for=[k])
        rt.finish()
        self.sent.append(v)
        self.got.append(b.data)
        return 1

    def describe(self) -> list:
        return [f"passthrough: {len(self.sent)} chains; simulated clock "
                f"{self.rt.stats()['time']:.6f} s (modeled links, not a "
                f"metric)"]

    def check(self):
        """Every chain: the value read back against the value written."""
        wrong = sum(not np.array_equal(np.asarray(g), s)
                    for s, g in zip(self.sent, self.got))
        self.rt = None
        return {"chains_wrong": {"value": wrong, "limit": 0}}, wrong
