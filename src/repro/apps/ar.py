"""AR point-cloud offload (paper §7.1, Fig. 15): a phone streams a
volumetric video and, for every frame, has an edge server sort the
frame's points back to front for the phone's current pose.

A frame is ``capacity`` slots of 10-bit voxel coordinates, 6 bytes a
slot, padded past the frame's point count. It is kept as three planes
(x, y, z) of uint16 coordinates, each plane as ``capacity / 2`` uint32
words: word ``j`` holds slot ``j`` in its low half and slot
``j + capacity / 2`` in its high half (``pack_frame``). The chip holds
these words as they lie on the host, so their upload is a plain copy,
where a ``uint16[n, 3]`` frame is transposed on the host into the chip's
column-major tiles first (on a TPU v5e, 3.6 ms a frame, and 140 ms
under the profiler); and the kernel unpacks the halves without moving a
point.
``FrameOffload.frame`` is the per-frame command chain through
``ClientRuntime``, one server on the caller's device:

1. ``enqueue_write`` of the pose (``PARAMS`` float32: the view matrix,
   the frustum and the frame's bounding box);
2. ``enqueue_write`` of the geometry's content-size buffer (the count of
   points × 6 bytes) and of the padded, packed geometry;
3. ``enqueue_kernel`` ``ar_reconstruct`` → ``float32[capacity]`` depth
   keys;
4. ``enqueue_kernel`` ``ar_sort`` → ``uint32[capacity]`` indices of the
   visible points back to front, and their content-size buffer (visible
   × 4 bytes), so the client gets only the used prefix;
5. ``enqueue_read`` of the indices, then ``finish()``: the read copies
   the size and then the indices off the device.

Each kernel's upload to the device is an ``ar.h2d`` span with its
``bytes`` (of the arrays that were on the host) and ``server``. The two
kernels stay two commands, as in the paper's pipeline, and the keys
between them stay on the chip: the commit keeps the reconstruct's device
array, and the sort's upload of it is a no-op.

``reference_frame`` is the plain float64 numpy reference. Departures
from the paper: the stream's HEVC decode is left out (the frame arrives
as voxel coordinates), and indices are sent as uint32, not packed.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ClientRuntime, DeviceSpec, LinkSpec, ServerSpec
from repro.core.trace import span

# the pose buffer: view matrix (world to view, row-major, the camera
# looking down -z), tan(fov_y / 2), aspect, near, far, the frame's
# bounding-box minimum (x, y, z) and voxel size, all in metres
VIEW, TAN_HALF_FOV, ASPECT, NEAR, FAR, BOX_MIN, VOXEL = (
    slice(0, 16), 16, 17, 18, 19, slice(20, 23), 23)
PARAMS = 24
POINT_BYTES = 6                 # uint16 x, y, z
INDEX_BYTES = 4                 # uint32

# the paper's phone link (WiFi-6, effective rate); it moves only the
# simulated clock
_WIFI6 = LinkSpec(latency=1.5e-3, bandwidth=300e6 / 8)


def view_params(eye, target, fov_y: float, aspect: float, near: float,
                far: float, box_min, voxel: float) -> np.ndarray:
    """The pose buffer of a camera at ``eye`` looking at ``target`` (y
    up), for a frame quantised from ``box_min`` in steps of ``voxel``.
    ``eye`` and ``target`` may carry leading batch dimensions."""
    eye = np.asarray(eye, np.float64)
    target = np.asarray(target, np.float64)
    f = target - eye
    f /= np.linalg.norm(f, axis=-1, keepdims=True)
    s = np.cross(f, [0.0, 1.0, 0.0])
    s /= np.linalg.norm(s, axis=-1, keepdims=True)
    u = np.cross(s, f)
    out = np.zeros(eye.shape[:-1] + (PARAMS,), np.float64)
    view = out[..., VIEW].reshape(eye.shape[:-1] + (4, 4))
    for row, axis, sign in ((0, s, 1.0), (1, u, 1.0), (2, f, -1.0)):
        view[..., row, :3] = sign * axis
        view[..., row, 3] = -sign * np.sum(axis * eye, axis=-1)
    view[..., 3, 3] = 1.0
    out[..., VIEW] = view.reshape(eye.shape[:-1] + (16,))
    out[..., TAN_HALF_FOV] = np.tan(fov_y / 2)
    out[..., ASPECT] = aspect
    out[..., NEAR] = near
    out[..., FAR] = far
    out[..., BOX_MIN] = box_min
    out[..., VOXEL] = voxel
    return out.astype(np.float32)


def view_coords(m, x, y, z):
    """World to view coordinates by the rows of ``m`` (the view matrix,
    flat), as float32 multiply-adds: a default-precision TPU matmul
    would round the operands to bfloat16."""
    return tuple(m[4 * r] * x + m[4 * r + 1] * y + m[4 * r + 2] * z
                 + m[4 * r + 3] for r in range(3))


def pack_frame(q) -> np.ndarray:
    """The frame of slots ``q`` (``uint16[3, capacity]``, one plane per
    axis, ``capacity`` even) as the kernel takes it: ``uint32[3,
    capacity / 2]``, slot ``j`` in the low and slot ``j + capacity / 2``
    in the high half of word ``j`` of its plane."""
    q = np.asarray(q, np.uint32)
    half = q.shape[1] // 2
    return q[:, :half] | q[:, half:] << 16


@jax.jit
def ar_reconstruct(params, size, geom):
    """Depth keys of a frame: the voxels of ``geom`` (``pack_frame``'s
    words, plane after plane, flat) dequantised to their centres in the
    bounding box, moved into view space by the pose, culled against the
    six planes of the frustum; a visible point's key is its depth in
    front of the camera, a culled point's or a padding slot's (index >=
    ``size`` / 6) is -inf, which sorts last."""
    half = geom.shape[0] // 3
    words = [geom[a * half:(a + 1) * half] for a in range(3)]
    box, voxel = params[BOX_MIN], params[VOXEL]
    ty = params[TAN_HALF_FOV]
    tx = ty * params[ASPECT]
    n = (size[0] // POINT_BYTES).astype(jnp.int32)

    def keys(q, first):
        x, y, z = ((c.astype(jnp.float32) + 0.5) * voxel + box[a]
                   for a, c in enumerate(q))
        xv, yv, zv = view_coords(params[VIEW], x, y, z)
        d = -zv
        visible = ((d >= params[NEAR]) & (d <= params[FAR])
                   & (jnp.abs(xv) <= d * tx) & (jnp.abs(yv) <= d * ty)
                   & (first + jnp.arange(half, dtype=jnp.int32) < n))
        return jnp.where(visible, d, -jnp.inf)

    return jnp.concatenate([keys([w & 0xFFFF for w in words], 0),
                            keys([w >> 16 for w in words], half)])


@jax.jit
def ar_sort(keys):
    """The indices of ``keys`` by descending key, ties by index (a stable
    sort), so the visible points come first, back to front; and their
    content size, the visible count × 4 bytes."""
    order = jnp.argsort(-keys, stable=True).astype(jnp.uint32)
    visible = jnp.sum(keys > -jnp.inf, dtype=jnp.uint32)
    return order, (visible * INDEX_BYTES)[None]


class FrameOffload:
    """One phone's frames through ``ClientRuntime``: one server, named
    ``server``, whose kernels run on ``device``; frames of up to
    ``capacity`` points."""

    def __init__(self, device, capacity: int, server: str = "s0"):
        if capacity % 2:
            raise ValueError(f"a frame packs slots in pairs: capacity "
                             f"{capacity} is odd")
        self.device, self.capacity, self.server = device, capacity, server
        self.rt = rt = ClientRuntime(
            servers=[ServerSpec(server, [DeviceSpec(device.device_kind)])],
            client_link=_WIFI6, transport="tcp")
        self.pose = rt.create_buffer(PARAMS * 4, name="pose")
        self.geom_size = rt.create_buffer(4, name="geometry_size")
        self.geom = rt.create_buffer(capacity * POINT_BYTES,
                                     content_size_buffer=self.geom_size,
                                     name="geometry")
        self.keys = rt.create_buffer(capacity * 4, name="depth_keys")
        self.index_size = rt.create_buffer(4, name="index_size")
        self.index = rt.create_buffer(capacity * INDEX_BYTES,
                                      content_size_buffer=self.index_size,
                                      name="indices")

    def _upload(self, *arrays):
        with span("ar.h2d", server=self.server,
                  bytes=sum(a.nbytes for a in arrays
                            if isinstance(a, np.ndarray))):
            return jax.device_put(arrays, self.device)

    def _reconstruct(self, pose, size, geom):
        return ar_reconstruct(*self._upload(pose, size, geom.reshape(-1)))

    def _sort(self, keys):
        return ar_sort(*self._upload(keys))

    def frame(self, pose: np.ndarray, geom: np.ndarray,
              count: int) -> np.ndarray:
        """The indices of the visible points of the frame's first
        ``count`` points of ``geom`` (``pack_frame``'s ``uint32[3,
        capacity / 2]``), back to front for ``pose`` (``view_params``)."""
        shape = (3, self.capacity // 2)
        if geom.shape != shape or geom.dtype != np.uint32 or not \
                0 <= count <= self.capacity:
            raise ValueError(f"a frame is uint32{list(shape)} with "
                             f"0..{self.capacity} points, got "
                             f"{geom.dtype}{list(geom.shape)} with {count}")
        rt, s, cap = self.rt, self.server, self.capacity
        writes = [rt.enqueue_write(s, self.pose, pose),
                  rt.enqueue_write(s, self.geom_size, np.array(
                      [count * POINT_BYTES], np.uint32)),
                  rt.enqueue_write(s, self.geom, geom)]
        k = rt.enqueue_kernel(
            s, fn=self._reconstruct,
            inputs=[self.pose, self.geom_size, self.geom],
            outputs=[self.keys], bytes_moved=10 * cap, wait_for=writes,
            name="ar_reconstruct")
        k = rt.enqueue_kernel(
            s, fn=self._sort, inputs=[self.keys],
            outputs=[self.index, self.index_size], bytes_moved=8 * cap,
            wait_for=[k], name="ar_sort")
        rt.enqueue_read(s, self.index, wait_for=[k])
        rt.finish()
        return self.index.data


@dataclasses.dataclass
class ReferenceFrame:
    order: np.ndarray       # visible indices back to front, ties by index
    depth: np.ndarray       # per point: depth in front of the camera
    margin: np.ndarray      # per point: distance inside the frustum (m)


def reference_frame(pose, geom, count: int) -> ReferenceFrame:
    """The plain reference of a frame (``uint16[3, capacity]``, one plane
    per axis, as ``pack_frame`` takes it) in float64: dequantise,
    transform, cull, sort. ``margin`` is each point's least signed
    distance to the six planes of the frustum, positive inside."""
    p = np.asarray(pose, np.float64)
    q = np.asarray(geom, np.float64)[:, :count].T
    world = (q + 0.5) * p[VOXEL] + p[BOX_MIN]
    m = p[VIEW].reshape(4, 4)
    view = world @ m[:3, :3].T + m[:3, 3]
    x, y, d = view[:, 0], view[:, 1], -view[:, 2]
    ty = p[TAN_HALF_FOV]
    tx = ty * p[ASPECT]
    margin = np.minimum.reduce([
        d - p[NEAR], p[FAR] - d,
        (tx * d - np.abs(x)) / np.hypot(1.0, tx),
        (ty * d - np.abs(y)) / np.hypot(1.0, ty)])
    idx = np.flatnonzero(margin >= 0)
    order = idx[np.lexsort((idx, -d[idx]))]
    return ReferenceFrame(order=order, depth=d, margin=margin)
