"""Quickstart: the PoCL-R offloading runtime in five minutes.

Builds a 2-server edge cluster, offloads a JAX kernel chain with P2P
buffer migration, demonstrates the content-size extension, survives a
connection loss, and prints the latency/byte accounting.

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp          # noqa: E402
import numpy as np               # noqa: E402

from repro.core import (ClientRuntime, DeviceSpec, LinkSpec,  # noqa: E402
                        ServerSpec)
from repro.utils import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    # -- a phone on WiFi driving two edge servers on a fast LAN ----------
    rt = ClientRuntime(
        servers=[ServerSpec("edge0", [DeviceSpec("gpu", flops=13e12)]),
                 ServerSpec("edge1", [DeviceSpec("gpu", flops=13e12)])],
        client_link=LinkSpec(latency=1.5e-3, bandwidth=300e6 / 8),  # WiFi6
        peer_link=LinkSpec(latency=20e-6, bandwidth=40e9 / 8),      # 40G
        transport="tcp")

    # -- offload a kernel chain: edge0 → (P2P migration) → edge1 --------
    x = rt.create_buffer(1 << 16, name="x")
    y = rt.create_buffer(1 << 16, name="y")
    z = rt.create_buffer(1 << 16, name="z")
    e1 = rt.enqueue_write("edge0", x, np.arange(16384, dtype=np.float32))
    e2 = rt.enqueue_kernel("edge0", fn=lambda a: np.asarray(jnp.sqrt(a)),
                           inputs=[x], outputs=[y], flops=16384,
                           wait_for=[e1])
    # consuming y on edge1 auto-migrates it server→server, not via us
    e3 = rt.enqueue_kernel("edge1", fn=lambda a: np.asarray(a * 2),
                           inputs=[y], outputs=[z], flops=16384,
                           wait_for=[e2])
    e4 = rt.enqueue_read("edge1", z, wait_for=[e3])
    rt.finish()
    ok = np.allclose(z.data, np.sqrt(np.arange(16384)) * 2)
    print(f"chain result correct: {ok}")
    print(f"client-observed latency: {e4.latency*1e3:.2f} ms")
    st = rt.stats()
    print(f"bytes via client link: {sum(st['client_link_bytes'].values()):,.0f}")
    print(f"bytes via peer link:   {sum(st['peer_link_bytes'].values()):,.0f}")

    # -- content-size extension: ship only the used prefix --------------
    size = rt.create_buffer(4)
    big = rt.create_buffer(1 << 20, content_size_buffer=size)
    rt.enqueue_write("edge0", size, np.array([2048], np.uint32))
    rt.enqueue_write("edge0", big, np.zeros(1 << 18, np.float32))
    rt.finish()
    before = rt.peer_link("edge0", "edge1").bytes_sent
    rt.enqueue_migration(big, "edge1")
    rt.finish()
    print(f"content-size migration moved "
          f"{rt.peer_link('edge0','edge1').bytes_sent-before:,.0f} bytes "
          f"of a {1<<20:,} byte buffer")

    # -- connection loss and session resume -----------------------------
    rt.inject_disconnect("edge0")
    print(f"edge0 available after disconnect: {rt.sessions['edge0'].available}")
    rt.reconnect("edge0")
    rt.finish()
    ev = rt.enqueue_kernel("edge0", fn=None, duration=1e-6)
    rt.finish()
    print(f"after reconnect, command status: {ev.status}")


if __name__ == "__main__":
    main()
