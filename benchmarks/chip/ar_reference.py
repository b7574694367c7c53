"""The plain reference of the AR deployment and the comparison that
decides its ``correct``. It imports nothing of the program and reads
none of the program's buffers: the benchmark keeps its own copy, which
builds each pose's camera from the pose's own numbers, so that no change
to the program can move the yardstick.

A frame is 10-bit voxel coordinates of ``capacity`` slots, as the
program is sent it: three planes (x, y, z) of ``capacity / 2`` uint32
words, word ``j`` of a plane holding slot ``j`` in its low and slot
``j + capacity / 2`` in its high 16 bits. A pose is a camera at ``eye``
looking at ``target`` with y up, a vertical field of view ``fov_y``, an
``aspect``, ``near`` and ``far`` planes, and the frame's quantisation:
the bounding-box minimum ``box_min`` and the ``voxel`` size, all in
metres. In float64: each voxel's centre; its
coordinates along the camera's right, up and forward axes (``x``, ``y``
and the depth ``d``); its margin, the least signed distance to the six
planes of the frustum (positive inside). The visible points are those
with a margin of 0 or more, back to front: descending depth, ties by
index.

The guarantee is every visible point exactly once, back to front. Two
numbers hold a returned index list to it:

- ``points_misplaced``: indices out of range or repeated, and points in
  the symmetric difference of the returned and the reference's visible
  sets, leaving out points within ``EPS`` of a plane of the frustum;
- ``order_violations``: adjacent pairs of the returned list whose
  reference depth rises by more than ``DELTA``.

``EPS`` and ``DELTA`` are 1e-5 m. A float32 depth or plane distance at
3 m is good to a few units of 2.4e-7 m (an ulp there), so two points
the program's float32 orders wrongly lie within some 1e-6 m: the limits
are some 40 ulps above float32's rounding at 3 m. A bfloat16 key or
pose transform keeps 8 significant bits: it rounds a depth of 2 m by up
to 7.8 mm (half its ulp of 1/64 m), some 800 times the limits.
"""
from __future__ import annotations

import numpy as np

EPS = 1e-5                      # m, from a plane of the frustum
DELTA = 1e-5                    # m, of depth between adjacent points


def frame(eye, target, fov_y: float, aspect: float, near: float,
          far: float, box_min, voxel: float, geom, count: int):
    """``(order, depth, margin)`` of the frame's first ``count`` points
    seen from ``eye`` looking at ``target``: the visible indices back to
    front, and per point its depth and its margin inside the frustum."""
    eye = np.asarray(eye, np.float64)
    forward = np.asarray(target, np.float64) - eye
    forward /= np.linalg.norm(forward)
    right = np.cross(forward, (0.0, 1.0, 0.0))
    right /= np.linalg.norm(right)
    up = np.cross(right, forward)
    words = np.asarray(geom, np.uint32)
    q = np.concatenate([words & 0xFFFF, words >> 16], axis=1)
    q = q[:, :count].T.astype(np.float64)
    rel = (q + 0.5) * voxel + np.asarray(box_min, np.float64) - eye
    x, y, d = rel @ right, rel @ up, rel @ forward
    ty = np.tan(fov_y / 2)
    tx = ty * aspect
    margin = np.minimum.reduce([
        d - near, far - d,
        (tx * d - np.abs(x)) / np.sqrt(1.0 + tx * tx),
        (ty * d - np.abs(y)) / np.sqrt(1.0 + ty * ty)])
    idx = np.flatnonzero(margin >= 0)
    return idx[np.lexsort((idx, -d[idx]))], d, margin


def compare(got, depth, margin, count: int) -> tuple:
    """``(points_misplaced, order_violations, largest_flip,
    largest_rise)`` of the returned index list ``got`` against the
    reference's ``depth`` and ``margin`` of the frame's ``count`` points.
    The readings that ``EPS`` and ``DELTA`` bound, in m: ``largest_flip``
    is the largest distance from the frustum of a point on the wrong side
    of it, ``largest_rise`` the most the reference depth rises between
    adjacent returned points."""
    got = np.asarray(got).reshape(-1).astype(np.int64)
    inside = (got >= 0) & (got < count)
    ok = got[inside]
    repeated = ok.size - np.unique(ok).size
    returned = np.zeros(count, bool)
    returned[ok] = True
    flipped = returned != (margin >= 0)
    wrong = flipped & (np.abs(margin) >= EPS)
    misplaced = int((~inside).sum()) + int(repeated) + int(wrong.sum())
    rises = np.diff(depth[ok])
    violations = int((rises > DELTA).sum())
    return (misplaced, violations,
            float(np.abs(margin[flipped]).max(initial=0.0)),
            float(rises.max(initial=0.0)))
