"""The required-work counts against hand counts."""

import pytest

from bench import flops
from bench.tests import tiny


def cfg(attention="bsa", **model):
    c = tiny.config("shapenet-bsa" if attention == "bsa" else "shapenet-full")
    c["model"].update(model)
    return c


def test_pairs_by_hand():
    # tiny: ball 64, l 8, top-k 2, group 8; 150 real points
    c = cfg()
    assert flops.pairs(c, 150) == {
        "ball": 2 * 64 * 64 + 22 * 22,      # two full balls, 22 points left
        "cmp": 150 * 19,                    # 19 blocks hold a real point
        "slc": 150 * 2 * 8,
        "score": 19 * 19}                   # 19 groups of 8 x 19 blocks
    assert flops.pairs(cfg("full"), 150) == {"full": 150 * 150}


def test_model_flops_by_hand():
    c = cfg()
    h, d, L = 4, 8, 2
    p = flops.pairs(c, 150)
    dense = 2 * (L * (32 * 32 + 2 * 32 * 16 + 32 * 32 + 3 * 32 * 64) + 7 * 32 + 32)
    attn = 4 * d * h * L * (p["ball"] + p["cmp"] + p["slc"])
    score = 2 * d * h * L * p["score"]
    assert flops.dense_params(c) * 2 == dense
    assert flops.model_flops(c, [150], train=False) == dense * 150 + attn + score
    assert flops.model_flops(c, [150], train=True) == 3 * (dense * 150 + attn) + score


def test_kernel_work_by_hand():
    c = cfg()
    w = flops.kernel_work(c, [150], train=True)
    h, hkv, d, L = 4, 2, 8, 2
    q, kv, lse = 150 * h * d, 150 * hkv * d, 150 * h
    ball = 2 * 64 * 64 + 22 * 22
    assert w["bsa_ball_fwd"] == (L * 4 * d * h * ball, L * 4 * (2 * q + 2 * kv + lse))
    assert w["bsa_ball_bwd"][0] == 2 * w["bsa_ball_fwd"][0]
    assert w["bsa_flash_fwd"][0] == L * 4 * d * h * 150 * 19
    assert w["bsa_flash_dq"][0] + w["bsa_flash_dkv"][0] == 2 * w["bsa_flash_fwd"][0]
    assert set(flops.kernel_work(c, [150], train=False, layout="packed")) == {
        "bsa_ball_fwd", "bsa_varlen_fwd", "bsa_selection_fwd", "bsa_epilogue_fwd"}
    assert set(flops.kernel_work(cfg("full"), [150], train=True)) == {
        "bsa_flash_fwd", "bsa_flash_dq", "bsa_flash_dkv"}


def test_counts_at_cell_shapes():
    """The shapes of the training cells: 18 layers, d_model 256, 8 x 32
    heads, d_ff 1024, batch 8 of 3586 points."""
    import json
    from pathlib import Path
    configs = Path(__file__).resolve().parents[1] / "configs"
    bsa = json.loads((configs / "shapenet-bsa.json").read_text())
    full = json.loads((configs / "shapenet-full.json").read_text())
    pts = [3586] * 8
    assert flops.dense_params(bsa) == 18 * (4 * 256 * 256 + 3 * 256 * 1024) + 7 * 256 + 256
    dense = 6 * flops.dense_params(bsa) * sum(pts)
    assert dense == pytest.approx(3.25e12, rel=0.01)
    # attention fwd+bwd per point: (ball + blocks + selection keys) x 12 x 32 x 8 x 18
    per_point = (flops.model_flops(bsa, pts, train=True) - dense) / sum(pts)
    assert per_point == pytest.approx(42.5e6, rel=0.06)
    assert flops.model_flops(bsa, pts, train=True) == pytest.approx(4.5e12, rel=0.03)
    assert flops.model_flops(full, pts, train=True) == pytest.approx(8.9e12, rel=0.01)
