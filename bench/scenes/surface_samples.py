"""A batch of ModelNet40-like clouds, as DGCNN's training loader gives
them (Wang et al., TOG 2019; sampling after PointNet, Qi et al., CVPR
2017, sec. 5.1).

Each cloud is the surface of 1 to ``max_primitives`` primitives (boxes,
cylinders, spheres and thin plates of random size, pose and place),
``points`` points sampled uniformly by area, centred on their centroid and
scaled into the unit sphere; then each axis is scaled by U(2/3, 3/2) and
shifted by U(-0.2, 0.2), as DGCNN's ``translate_pointcloud`` augments a
training cloud. Each point's primitive is drawn by area among its own
cloud's, so the points come in no order. Returns float32
``[clouds, points, 3]``. The whole batch is drawn at once, every point
by one set of formulas in float32, so a batch costs a few dozen numpy
calls and not a few per primitive or kind: the producer that makes
batches ahead of the timed loop then keeps well ahead of the device.
"""
import numpy as np

BOX, CYLINDER, SPHERE, PLATE = range(4)
F = np.float32


def _rotations(rng, n):
    q, r = np.linalg.qr(rng.normal(size=(n, 3, 3)))
    return q * np.sign(np.diagonal(r, axis1=1, axis2=2))[:, None, :]


def _areas(kind, size):
    a, b, c = size.T
    box = 2.0 * (a * b + b * c + a * c)
    return np.select([kind == CYLINDER, kind == SPHERE],
                     [2.0 * np.pi * a * (a + b), 4.0 * np.pi * a * a], box)


def _surface(rng, kind, size):
    """One point uniform on the surface of each row's primitive, centred
    at 0 (a cylinder: radius a, height b; a sphere: radius a), as
    [3, rows]."""
    n = len(kind)
    u = rng.random((4, n), dtype=F)
    a, b, c = size.T
    sphere, cyl = kind == SPHERE, kind == CYLINDER
    on_side = u[0] * (a + b) < b
    # sphere: z uniform in [-1, 1] and an angle (Archimedes); cylinder:
    # the side by its share of the area, else a cap, either one by u[2]
    zs = 2.0 * u[2] - 1.0
    rad = np.where(sphere, a * np.sqrt(1.0 - zs * zs),
                   np.where(on_side, a, a * np.sqrt(u[3])))
    z = np.where(sphere, a * zs,
                 np.where(on_side, (u[2] - 0.5) * b,
                          np.where(u[2] < 0.5, -0.5 * b, 0.5 * b)))
    t = F(2.0 * np.pi) * u[1]
    out = np.stack([rad * np.cos(t), rad * np.sin(t), z])
    # box or plate: a face by its area, a point uniform on it
    rows = np.flatnonzero(~(sphere | cyl))
    a, b, c = a[rows], b[rows], c[rows]
    half = 0.5 * size[rows].T
    face = np.stack([b * c, a * c, a * b])      # the two faces across
    ends = np.cumsum(face, axis=0)              # each axis, by area
    pick = u[0, rows] * ends[2]
    axis = (pick >= ends[0]).astype(np.intp) + (pick >= ends[1])
    at = np.arange(len(rows))
    # which of the axis's two faces: the pick's place in its share
    sign = np.where(2.0 * (ends[axis, at] - pick) < face[axis, at], -1.0, 1.0)
    box = (2.0 * u[1:, rows] - 1.0) * half
    box[axis, at] = sign * half[axis, at]
    out[:, rows] = box
    return out


def generate(rng: np.random.Generator, *, clouds: int, points: int,
             max_primitives: int, scale, shift: float) -> np.ndarray:
    per_cloud = rng.integers(1, max_primitives + 1, clouds)
    n_prim = int(per_cloud.sum())
    kind = rng.integers(0, 4, n_prim)
    size = rng.uniform(0.2, 1.0, (n_prim, 3))
    thin = rng.integers(0, 3, n_prim)
    plate = kind == PLATE
    size[np.flatnonzero(plate), thin[plate]] = rng.uniform(
        0.01, 0.05, int(plate.sum()))
    rot = _rotations(rng, n_prim).astype(F)
    place = rng.uniform(-0.5, 0.5, (n_prim, 3)).astype(F)
    # each point's primitive drawn by area among its cloud's own
    owner = np.repeat(np.arange(clouds), per_cloud)
    first = np.cumsum(per_cloud) - per_cloud
    area = _areas(kind, size)
    share = area / np.bincount(owner, area)[owner]
    local = np.arange(n_prim) - first[owner]
    inner = local < per_cloud[owner] - 1     # a cloud's last ends at 1
    ends = np.full((max_primitives - 1, clouds), 2.0)
    ends[local[inner], owner[inner]] = (np.cumsum(share) - owner)[inner]
    u = rng.random((clouds, points))
    prim = (first[:, None] + sum((u >= e[:, None]).astype(np.intp)
                                 for e in ends)).ravel()
    s = _surface(rng, kind[prim], size[prim].astype(F))
    pts = np.einsum("nij,jn->ni", rot[prim], s).reshape(clouds, points, 3)
    pts += place[prim].reshape(clouds, points, 3)
    pts -= pts.mean(axis=1, keepdims=True)
    # into the unit sphere, then the augmentation, in one pass
    reach = np.sqrt(np.einsum("cpi,cpi->cp", pts, pts).max(axis=1))
    pts *= (rng.uniform(scale[0], scale[1], (clouds, 1, 3))
            / reach[:, None, None]).astype(F)
    pts += rng.uniform(-shift, shift, (clouds, 1, 3)).astype(F)
    return pts
