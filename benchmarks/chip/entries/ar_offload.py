"""The AR point-cloud offload deployment (paper §7.1, Fig. 15): a phone
has one server on one chip reconstruct and depth-sort each frame of a
streamed volumetric video for the phone's pose, through
``ClientRuntime``, via the program's ``repro.apps.ar.FrameOffload``.

One request is one frame: its pose from a seeded walk around the
subject, its points from a seeded clip of ``clip_frames`` frames cycled
as a looping video; ``FrameOffload.frame`` writes both, runs the
reconstruct and sort kernels and reads back the visible indices, back to
front.

``correct`` compares ``sample_frames`` frames of the window, drawn from
the seed, with the float64 reference of ``ar_reference.py``, which builds
each pose's camera from its eye, target and the config's numbers, not
from the program's pose buffer.
"""
from __future__ import annotations

import math
import sys
from unittest import mock

import numpy as np

import ar_reference
from repro.apps import ar

POSES = 1024                    # poses drawn from the seed at a time
# the subject: ellipsoid surfaces (centre, radii) in metres, feet at
# y = 0, 1.74 m tall
BODY = [((0.0, 1.15, 0.0), (0.17, 0.30, 0.11)),        # torso
        ((0.0, 0.90, 0.0), (0.16, 0.12, 0.10)),        # pelvis
        ((0.0, 1.47, 0.0), (0.05, 0.06, 0.05)),        # neck
        ((0.0, 1.62, 0.0), (0.085, 0.115, 0.10)),      # head
        ((-0.10, 0.45, 0.0), (0.075, 0.45, 0.08)),     # legs
        ((0.10, 0.45, 0.0), (0.075, 0.45, 0.08)),
        ((-0.24, 1.12, 0.0), (0.045, 0.30, 0.05)),     # arms
        ((0.24, 1.12, 0.0), (0.045, 0.30, 0.05))]
# the pose walk: per frame, each coordinate of the walk advances by its
# speed plus seeded noise and is folded into its range; incommensurate
# speeds make a window of some thousand frames see most poses
SPEEDS = {"angle": 1 / 400, "radius": 1 / 250, "eye": 1 / 170,
          "target": 1 / 210, "dx": 1 / 130, "dz": 1 / 190}
NOISE = 0.005
MOTION_M = 0.002                # per frame, of the clip's deformation


def control():
    """The control of ``correct``: the sort on bfloat16 keys, the
    precision below the configuration's float32, put in place of the
    program's ``ar_sort``. It has to come out not correct."""
    import jax
    import jax.numpy as jnp

    sort = ar.ar_sort.__wrapped__

    @jax.jit
    def bf16_sort(keys):
        return sort(keys.astype(jnp.bfloat16))

    return mock.patch.object(ar, "ar_sort", bf16_sort)


def fold(w):
    """``w`` folded into [0, 1] as a triangle wave of period 2."""
    return 1.0 - np.abs(np.mod(w, 2.0) - 1.0)


def make_clip(config: dict, rng) -> tuple:
    """``(geom, counts)``: ``clip_frames`` frames of ``capacity`` voxel
    slots, each frame's first ``counts[f]`` slots its points, the rest
    zero, packed as the program takes them (``ar.pack_frame``:
    ``uint32[frames, 3, capacity / 2]``)."""
    cap, frames = config["capacity"], config["clip_frames"]
    c = config["content"]
    lo, hi = config["count_range"]
    counts = rng.integers(math.ceil(lo * cap), math.floor(hi * cap),
                          frames, endpoint=True)
    centre = np.array([b[0] for b in BODY])
    radii = np.array([b[1] for b in BODY])
    area = (radii[:, 0] * radii[:, 1] + radii[:, 1] * radii[:, 2]
            + radii[:, 0] * radii[:, 2])
    part = rng.choice(len(BODY), cap, p=area / area.sum())
    u = rng.standard_normal((cap, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    pts = centre[part] + radii[part] * u
    box = np.asarray(c["box_min_m"])
    top = (1 << c["grid_bits"]) - 1
    geom = np.zeros((frames, 3, cap // 2), np.uint32)
    q = np.zeros((3, cap), np.uint16)
    for f in range(frames):
        # a small smooth deformation of the last frame
        k = rng.standard_normal(3) * 2 * np.pi
        pts += (rng.standard_normal(3) * MOTION_M
                * np.sin(pts @ k + rng.uniform(0, 2 * np.pi))[:, None])
        pick = rng.permutation(cap)[:counts[f]]
        q[:] = 0
        q[:, :counts[f]] = np.clip(np.floor((pts[pick] - box)
                                            / c["voxel_m"]), 0, top).T
        geom[f] = ar.pack_frame(q)
    return geom, counts


def camera(config: dict) -> dict:
    """What every pose of the walk shares: the field of view, aspect,
    near and far planes, and the frames' quantisation, in radians and
    metres (the arguments of ``ar.view_params`` and
    ``ar_reference.frame`` after the eye and the target)."""
    p, c = config["pose_path"], config["content"]
    return {"fov_y": math.radians(p["fov_y_deg"]), "aspect": p["aspect"],
            "near": p["near_m"], "far": p["far_m"],
            "box_min": c["box_min_m"], "voxel": c["voxel_m"]}


class PoseWalk:
    """The seeded walk of the phone around the subject, drawn ``POSES`` at
    a time: each call gives the pose's eye and target (``float64[2,
    3]``) and the program's pose buffer for them (``ar.view_params``)."""

    def __init__(self, config: dict, rng):
        self.path, self.camera = config["pose_path"], camera(config)
        self.rng = rng
        self.w = {k: rng.uniform(0, 2) for k in SPEEDS}
        self.next = POSES

    def _draw(self):
        rng, p = self.rng, self.path
        t = np.arange(1, POSES + 1)
        w = {k: self.w[k] + v * t + np.cumsum(
            rng.normal(0, NOISE, POSES)) for k, v in SPEEDS.items()}
        self.w = {k: v[-1] for k, v in w.items()}
        span = lambda r, x: r[0] + (r[1] - r[0]) * x  # noqa: E731
        angle = np.pi * w["angle"]
        # the phone spends more time close to the subject than far
        radius = span(p["orbit_radius_m"], fold(w["radius"]) ** 3)
        eye = np.stack([radius * np.sin(angle),
                        span(p["eye_height_m"], fold(w["eye"])),
                        radius * np.cos(angle)], axis=1)
        off = p["target_offset_m"]
        target = np.stack([off * (2 * fold(w["dx"]) - 1),
                           span(p["target_height_m"], fold(w["target"])),
                           off * (2 * fold(w["dz"]) - 1)], axis=1)
        self.eye_target = np.stack([eye, target], axis=1)
        self.buffers = ar.view_params(eye, target, **self.camera)
        self.next = 0

    def __call__(self) -> tuple:
        if self.next == POSES:
            self._draw()
        i = self.next
        self.next += 1
        return self.eye_target[i], self.buffers[i]


class Deployment:
    span = "bench.chain"        # one chain of commands is one frame

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        rng = np.random.default_rng(seed)
        self.geom, self.counts = make_clip(config, rng)
        self.poses = PoseWalk(config, rng)
        self.sample_rng = np.random.default_rng([seed, 1])
        self.n_sample = config["check"]["sample_frames"]
        self.offload = ar.FrameOffload(devices[0], config["capacity"])
        # warm up: compile both kernels and the prefix copy, and run the
        # runtime's whole path
        self._reset()
        for _ in range(traffic["warmup_frames"]):
            self.serve()
        self._reset()

    def _reset(self):
        self.frames = 0
        self.sample = []            # (clip frame, eye and target, indices)
        self.visible = []           # per frame: visible share

    def serve(self) -> int:
        f = self.frames % len(self.counts)
        eye_target, pose = self.poses()
        got = self.offload.frame(pose, self.geom[f], int(self.counts[f]))
        self.frames += 1
        self.visible.append(len(got) / self.counts[f])
        # a reservoir of frames, drawn uniformly from the seed
        if len(self.sample) < self.n_sample:
            self.sample.append((f, eye_target, got))
        else:
            j = self.sample_rng.integers(self.frames)
            if j < self.n_sample:
                self.sample[j] = (f, eye_target, got)
        return 1

    def describe(self) -> list:
        q = np.percentile(self.visible, [0, 5, 50, 95, 100]) * 100
        return [f"ar: {self.frames} frames; visible share min/p5/p50/p95/"
                f"max {' / '.join(f'{v:.2f}' for v in q)} %"]

    def check(self):
        """The drawn frames against the float64 reference."""
        self.offload = None
        misplaced = violations = failed = 0
        flip = rise = 0.0
        for f, (eye, target), got in self.sample:
            count = int(self.counts[f])
            _, depth, margin = ar_reference.frame(
                eye, target, **self.poses.camera, geom=self.geom[f],
                count=count)
            m, v, fl, r = ar_reference.compare(got, depth, margin, count)
            misplaced += m
            violations += v
            failed += bool(m or v)
            flip, rise = max(flip, fl), max(rise, r)
        print(f"ar: largest distance from the frustum of a point on its "
              f"wrong side {flip!r} m, largest rise of reference depth "
              f"between adjacent returned points {rise!r} m (limits "
              f"{ar_reference.EPS}, {ar_reference.DELTA})", file=sys.stderr)
        return ({"points_misplaced": {"value": misplaced, "limit": 0},
                 "order_violations": {"value": violations, "limit": 0}},
                failed)

