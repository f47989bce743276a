"""The benchmark's copy of the synthetic ShapeNet-Car generator."""

import numpy as np

from bench.traffic import generator as g


def test_rows_shapes_layout_and_order():
    pool = g.cloud_pool(2**31 + 3, 2, 3586)
    rows = g.train_rows(pool, 256, 3840)
    for (pts, nrm, tgt), row in zip(pool, rows):
        assert row["feats"].shape == (3840, 7) and row["target"].shape == (3840, 1)
        assert row["mask"].sum() == 3586 and row["mask"][:3586].all()
        perm = g.ball_order(pts, 256)
        np.testing.assert_array_equal(row["feats"][:3586, :3], pts[perm])
        np.testing.assert_array_equal(row["feats"][:3586, 3:6], nrm[perm])
        assert (row["feats"][:3586, 6] == 1).all() and not row["feats"][3586:].any()
        np.testing.assert_allclose(np.linalg.norm(nrm, axis=1), 1, atol=1e-5)


def test_same_seed_same_inputs():
    a = g.cloud_pool(7, 2, 3586)
    b = g.cloud_pool(7, 2, 3586)
    c = g.cloud_pool(8, 2, 3586)
    for x, y, z in zip(a, b, c):
        for u, v, w in zip(x, y, z):
            np.testing.assert_array_equal(u, v)
            assert not np.array_equal(u, w)
    r1 = g.request_cloud(a, 7, 5, 1e-3)
    r2 = g.request_cloud(a, 7, 5, 1e-3)
    r3 = g.request_cloud(a, 7, 5 + len(a), 1e-3)
    np.testing.assert_array_equal(r1[1], r2[1])
    assert r1[1].shape == (3586, 7) and r3[1].shape == r1[1].shape
    np.testing.assert_array_equal(r1[1][:, 3:], r3[1][:, 3:])   # same pool cloud
    assert not np.array_equal(r1[0], r3[0])         # fresh jitter


def test_matches_the_dataset_generator():
    from repro.core.balltree import build_balltree_permutation
    from repro.data.shapenet import _make_car, _normals

    pts = g.make_car(np.random.default_rng(5), 600)
    np.testing.assert_array_equal(pts, _make_car(np.random.default_rng(5), 600))
    np.testing.assert_allclose(g.normals(pts), _normals(pts), atol=1e-5)
    np.testing.assert_array_equal(g.ball_order(pts, 64),
                                  build_balltree_permutation(pts, 64))
