"""Synthetic ShapeNet-Car clouds and the request streams built from them.

A copy of the synthetic generator in ``src/repro/data/shapenet.py`` (car-like
superellipsoid bodies, pressure from stagnation, roof suction and wake
noise; features ``[xyz, normal, 1]``; ball-tree ordering by median
bisection), kept here so that later changes to the program cannot change
the benchmark's inputs.  One difference: normals come from a k-d tree
neighbour search (``scipy.spatial.cKDTree``) and one batched SVD, where the
dataset builds an n x n distance matrix and loops over points.  The
neighbourhoods are the same 12 nearest points.

Everything is drawn from ``numpy.random.default_rng((seed, stream, i))``, so
one seed gives the same clouds and the same requests in every run.  Every
cloud has the same number of points (ShapeNet-Car's 3586 in the cells), so
seeds change the shapes and the order of the work and not its amount.
"""

from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree

N_NORMAL_NEIGHBOURS = 12
POOL_STREAM, REQUEST_STREAM = 1, 3


def _superellipsoid(u, v, a, b, c, e1, e2):
    cu, su = np.cos(u), np.sin(u)
    cv, sv = np.cos(v), np.sin(v)
    sgn = lambda x: np.sign(x) * np.abs(x)
    x = a * sgn(cv) * np.abs(cv) ** (e1 - 1) * sgn(cu) * np.abs(cu) ** (e2 - 1)
    y = b * sgn(cv) * np.abs(cv) ** (e1 - 1) * sgn(su) * np.abs(su) ** (e2 - 1)
    z = c * sgn(sv) * np.abs(sv) ** (e1 - 1)
    return np.stack([x, y, z], -1)


def make_car(rng: np.random.Generator, n: int) -> np.ndarray:
    """n surface points of a car-like shape: length along x, up along z."""
    parts = []
    nb = int(n * 0.55)
    u = rng.uniform(-np.pi, np.pi, nb)
    v = rng.uniform(-np.pi / 2, np.pi / 2, nb)
    body = _superellipsoid(u, v, a=2.0 + 0.3 * rng.uniform(), b=0.8,
                           c=0.45, e1=0.8, e2=0.9)
    body[:, 2] += 0.5
    parts.append(body)
    nc = int(n * 0.25)
    u = rng.uniform(-np.pi, np.pi, nc)
    v = rng.uniform(0, np.pi / 2, nc)
    cab = _superellipsoid(u, v, a=0.9 + 0.2 * rng.uniform(), b=0.7,
                          c=0.4, e1=0.9, e2=0.9)
    cab[:, 0] -= 0.2
    cab[:, 2] += 0.95
    parts.append(cab)
    nw = n - nb - nc
    per = nw // 4
    got = 0
    for sx in (-1.3, 1.15):
        for sy in (-0.75, 0.75):
            m = per if got < 3 * per else nw - 3 * per
            got += m
            th = rng.uniform(0, 2 * np.pi, m)
            wx = 0.33 * np.cos(th) + sx
            wz = 0.33 * np.sin(th) + 0.33
            wy = sy + rng.uniform(-0.08, 0.08, m)
            parts.append(np.stack([wx, wy, wz], -1))
    pts = np.concatenate(parts)[:n]
    pts += rng.normal(0, 0.005, pts.shape)
    return pts.astype(np.float32)


def normals(pts: np.ndarray, k: int = N_NORMAL_NEIGHBOURS) -> np.ndarray:
    """Outward unit normals by PCA of each point's k nearest points (itself
    included), oriented away from the centroid."""
    _, idx = cKDTree(pts).query(pts, k=k)
    nb = pts[idx] - pts[idx].mean(axis=1, keepdims=True)      # (n, k, 3)
    _, _, vt = np.linalg.svd(nb, full_matrices=False)
    nrm = vt[:, -1]                                            # (n, 3)
    flip = np.einsum("nd,nd->n", nrm, pts - pts.mean(0)) < 0
    nrm[flip] *= -1
    return nrm.astype(np.float32)


def pressure(pts: np.ndarray, nrm: np.ndarray, rng) -> np.ndarray:
    """Normalised pressure: stagnation, roof suction and wake noise."""
    v = np.array([-1.0, 0.0, 0.0], np.float32)                 # flow toward -x
    ndv = nrm @ v
    cp = np.where(ndv > 0, ndv ** 2, -0.5 * ndv ** 2)
    cp -= 0.3 * np.clip(nrm[:, 2], 0, None) ** 2
    wake = (pts[:, 0] < -0.8).astype(np.float32)
    cp += wake * rng.normal(0, 0.08, pts.shape[0])
    cp += 0.02 * rng.normal(0, 1, pts.shape[0])
    return ((cp - 0.02) / 0.25).astype(np.float32)[:, None]


def _bisect(points, idx, out, leaf_size):
    if idx.shape[0] <= leaf_size:
        out.append(idx)
        return
    pts = points[idx]
    axis = int(np.argmax(pts.max(axis=0) - pts.min(axis=0)))
    order = np.argsort(pts[:, axis], kind="stable")
    half = idx.shape[0] // 2 + idx.shape[0] % 2
    _bisect(points, idx[order[:half]], out, leaf_size)
    _bisect(points, idx[order[half:]], out, leaf_size)


def ball_order(points: np.ndarray, ball_size: int) -> np.ndarray:
    """Permutation into ball order: recursive median bisection along the
    axis of largest extent, leaves of at most ``ball_size`` points."""
    leaves: list[np.ndarray] = []
    _bisect(np.asarray(points), np.arange(len(points)), leaves, ball_size)
    return np.concatenate(leaves)


def cloud_pool(seed: int, count: int, n_points: int):
    """``count`` clouds of ``n_points``: (points, normals, target) each."""
    pool = []
    for i in range(count):
        rng = np.random.default_rng((seed, POOL_STREAM, i))
        pts = make_car(rng, n_points)
        nrm = normals(pts)
        pool.append((pts, nrm, pressure(pts, nrm, rng)))
    return pool


def features(pts: np.ndarray, nrm: np.ndarray) -> np.ndarray:
    """The model's per-point input: ``[xyz, normal, 1]`` (7 features)."""
    return np.concatenate([pts, nrm, np.ones((len(pts), 1), np.float32)], -1)


def train_rows(pool, ball_size: int, pad_to: int):
    """Each pool cloud as one training row, ball-ordered and padded to
    ``pad_to``: feats (pad_to, 7), target (pad_to, 1), mask (pad_to,)."""
    rows = []
    for pts, nrm, tgt in pool:
        perm = ball_order(pts, ball_size)
        n = len(pts)
        feats = np.zeros((pad_to, 7), np.float32)
        target = np.zeros((pad_to, 1), np.float32)
        feats[:n] = features(pts, nrm)[perm]
        target[:n] = tgt[perm]
        mask = np.arange(pad_to) < n
        rows.append({"feats": feats, "target": target, "mask": mask})
    return rows


def request_cloud(pool, seed: int, j: int,
                  jitter: float) -> tuple[np.ndarray, np.ndarray]:
    """The j-th cloud a client sends: every point of pool cloud
    ``j mod len(pool)`` with fresh position jitter, so that no two requests
    carry the same cloud.  Returns (points (n, 3), feats (n, 7))."""
    pts, nrm, _ = pool[j % len(pool)]
    rng = np.random.default_rng((seed, REQUEST_STREAM, j))
    p = pts + rng.normal(0, jitter, pts.shape).astype(np.float32)
    return p, features(p, nrm)
