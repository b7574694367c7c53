"""The AR point-cloud offload (``repro.apps.ar``) on the CPU at a small
size: the per-frame chain through ``ClientRuntime`` against the float64
``reference_frame`` on seeded frames and poses, within the tolerances
the chip benchmark holds it to; and a pose transform in bfloat16, which
those tolerances catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import ar

CAP = 4096
BOX, VOXEL = (-0.9, 0.0, -0.9), 1.8 / 1024
FOV, ASPECT, NEAR, FAR = np.radians(60), 4 / 3, 0.1, 20.0
EPS = DELTA = 1e-5      # m: some 40 float32 ulps at 3 m (see ar_reference)


def frame(seed: int, count: int = CAP - 300) -> np.ndarray:
    """A padded frame of ``count`` random voxels of a 1.8-m cube, one
    plane per axis."""
    geom = np.zeros((3, CAP), np.uint16)
    geom[:, :count] = np.random.default_rng(seed).integers(
        0, 1024, (count, 3), endpoint=False).T
    return geom


def pose(eye, target=(0.0, 0.9, 0.0)) -> np.ndarray:
    return ar.view_params(eye, target, FOV, ASPECT, NEAR, FAR, BOX, VOXEL)


def errors(got, ref: ar.ReferenceFrame, count: int) -> tuple:
    """(points misplaced, order violations) of ``got`` against the
    reference, leaving out points within ``EPS`` of a plane."""
    got = np.asarray(got).astype(np.int64)
    inside = (got >= 0) & (got < count)
    ok = got[inside]
    returned = np.zeros(count, bool)
    returned[ok] = True
    wrong = (returned != (ref.margin >= 0)) & (np.abs(ref.margin) >= EPS)
    misplaced = (~inside).sum() + ok.size - np.unique(ok).size + wrong.sum()
    return int(misplaced), int((np.diff(ref.depth[ok]) > DELTA).sum())


@pytest.fixture(scope="module")
def offload():
    return ar.FrameOffload(jax.devices()[0], CAP)


@pytest.mark.parametrize("seed,eye", [
    (1, (1.2, 1.5, 1.1)), (2, (-2.5, 1.0, 0.4)), (3, (0.3, 1.8, -1.0)),
    (4, (1.5, 0.6, -0.2))])
def test_chain_matches_reference(offload, seed, eye):
    count = CAP - 100 * seed
    geom, p = frame(seed, count), pose(eye)
    got = offload.frame(p, ar.pack_frame(geom), count)
    ref = ar.reference_frame(p, geom, count)
    assert got.dtype == np.uint32
    assert 0 < len(got) < count
    assert errors(got, ref, count) == (0, 0)
    # well inside the frustum the program's order is the reference's
    assert np.array_equal(got, ref.order) or \
        np.abs(ref.depth[got] - ref.depth[ref.order]).max() <= DELTA


def test_fully_visible_pose(offload):
    geom, p = frame(5), pose((0.0, 0.9, 6.0))
    got = offload.frame(p, ar.pack_frame(geom), CAP - 300)
    ref = ar.reference_frame(p, geom, CAP - 300)
    assert len(ref.order) == len(got) == CAP - 300
    assert errors(got, ref, CAP - 300) == (0, 0)


def test_mostly_culled_pose(offload):
    geom, p = frame(6), pose((0.2, 0.3, 0.2), target=(0.9, 0.0, 0.9))
    got = offload.frame(p, ar.pack_frame(geom), CAP - 300)
    ref = ar.reference_frame(p, geom, CAP - 300)
    assert 0 < len(ref.order) < (CAP - 300) // 4
    assert errors(got, ref, CAP - 300) == (0, 0)


def test_empty_frame(offload):
    got = offload.frame(pose((1.0, 1.0, 1.0)), ar.pack_frame(frame(7, 0)),
                        0)
    assert got.shape == (0,)


@pytest.mark.parametrize("geom", [np.zeros((3, CAP // 2 + 1), np.uint32),
                                  np.zeros((3, CAP), np.uint16)])
def test_frame_of_another_shape_is_refused(offload, geom):
    with pytest.raises(ValueError, match="uint32"):
        offload.frame(pose((1.0, 1.0, 1.0)), geom, 10)


def test_odd_capacity_is_refused():
    with pytest.raises(ValueError, match="odd"):
        ar.FrameOffload(jax.devices()[0], CAP + 1)


def test_pack_frame_puts_the_second_half_in_the_high_bits():
    geom = frame(9)
    words = ar.pack_frame(geom)
    assert words.dtype == np.uint32 and words.shape == (3, CAP // 2)
    np.testing.assert_array_equal(words & 0xFFFF, geom[:, :CAP // 2])
    np.testing.assert_array_equal(words >> 16, geom[:, CAP // 2:])


def _bf16_coords(m, x, y, z):
    b = jnp.bfloat16
    return tuple((m[4 * r].astype(b) * x.astype(b)
                  + m[4 * r + 1].astype(b) * y.astype(b)
                  + m[4 * r + 2].astype(b) * z.astype(b)
                  + m[4 * r + 3].astype(b)).astype(jnp.float32)
                 for r in range(3))


def test_bf16_pose_transform_is_caught(monkeypatch):
    monkeypatch.setattr(ar, "view_coords", _bf16_coords)
    body = ar.ar_reconstruct.__wrapped__      # traced anew, patched
    monkeypatch.setattr(ar, "ar_reconstruct",
                        jax.jit(lambda *args: body(*args)))
    offload = ar.FrameOffload(jax.devices()[0], CAP)
    geom, p = frame(8), pose((0.7, 1.3, 0.6))
    got = offload.frame(p, ar.pack_frame(geom), CAP - 300)
    misplaced, violations = errors(got, ar.reference_frame(
        p, geom, CAP - 300), CAP - 300)
    assert misplaced > 0 and violations > 0
