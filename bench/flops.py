"""Operations and bytes that the point-cloud model's work requires, from the
configuration and the real point counts alone.

What is counted is the algorithm's work, whatever implements it:

* per real query and head, ball attention over the real points of its ball,
  compression over the pooled blocks that hold a real point, selection over
  ``top_k`` blocks of ``cmp_block`` points, full attention over every real
  point;
* 4·head_dim operations per (query, key) pair and head forward (QK and PV),
  8·head_dim backward (dV, dP, dQ, dK), nothing for recomputation;
* selection scoring (pooled queries against pooled keys, 2·head_dim per
  (group, block) pair and head), forward only: top-k passes no gradient;
* dense layers 2 operations per parameter and real point forward, 4
  backward.

Padded rows, tile padding and activation recomputation are never counted, so
a faster kernel can only raise a share of the peak, and no share can pass
100%.  Bytes are each kernel's inputs read once and outputs written once, at
the stored width, for real rows.
"""

from __future__ import annotations

import math

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _dims(cfg: dict):
    m = cfg["model"]
    return m["n_heads"], m["n_kv_heads"], m["head_dim"]


def dense_params(cfg: dict) -> int:
    """Parameters of the matmuls each point passes through."""
    m = cfg["model"]
    h, hkv, hd = _dims(cfg)
    d = m["d_model"]
    layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * m["d_ff"]
    return m["n_layers"] * layer + m["in_dim"] * d + d * m["out_dim"]


def pairs(cfg: dict, n: int) -> dict:
    """(query, key) pairs per head of one sample of ``n`` real points, per
    attention branch, and the selection scoring's (group, block) pairs."""
    if cfg["model"]["attention"] == "full":
        return {"full": n * n}
    b = cfg["bsa"]
    m, ell = b["ball_size"], b["cmp_block"]
    blocks = math.ceil(n / ell)
    return {"ball": (n // m) * m * m + (n % m) ** 2,
            "cmp": n * blocks,
            "slc": n * b["top_k"] * ell,
            "score": math.ceil(n / b["group_size"]) * blocks}


def model_flops(cfg: dict, points: list[int], *, train: bool) -> float:
    """Operations of one forward (``train``: forward and backward) pass over
    samples of the given real point counts."""
    h, _, hd = _dims(cfg)
    layers = cfg["model"]["n_layers"]
    per_pass = 3 if train else 1                 # backward = 2 x forward
    total = 0.0
    for n in points:
        p = pairs(cfg, n)
        attn = sum(v for k, v in p.items() if k != "score")
        total += per_pass * (2 * dense_params(cfg) * n + 4 * hd * h * layers * attn)
        total += 2 * hd * h * layers * p.get("score", 0)
    return total


def kernel_work(cfg: dict, points: list[int], *, train: bool,
                layout: str = "padded") -> dict:
    """{kernel name: (operations, bytes)} the attention kernels require for
    one pass over samples of the given real point counts, all layers.
    ``layout``: "padded" (a (B, N) batch) or "packed" (one packed varlen
    row; its compression branch runs the varlen flash kernel)."""
    h, hkv, hd = _dims(cfg)
    layers = cfg["model"]["n_layers"]
    w = DTYPE_BYTES[cfg["model"]["compute_dtype"]]
    work: dict[str, list[float]] = {}

    def add(name, flops, nbytes):
        acc = work.setdefault(name, [0.0, 0.0])
        acc[0] += layers * flops
        acc[1] += layers * nbytes * w

    full = cfg["model"]["attention"] == "full"
    flash = "bsa_varlen" if layout == "packed" and not full else "bsa_flash"
    for n in points:
        p = pairs(cfg, n)
        q, kv, lse = n * h * hd, n * hkv * hd, n * h    # q-sized, k-sized, stats
        if full:
            add(f"{flash}_fwd", 4 * hd * h * p["full"], q + 2 * kv + q + lse)
            if train:
                add(f"{flash}_dq", 4 * hd * h * p["full"],
                    2 * q + 2 * kv + 2 * lse + q)
                add(f"{flash}_dkv", 4 * hd * h * p["full"],
                    2 * q + 2 * kv + 2 * lse + 2 * kv)
            continue
        b = cfg["bsa"]
        blocks = math.ceil(n / b["cmp_block"])
        ckv = blocks * hkv * hd                          # pooled keys or values
        groups = math.ceil(n / b["group_size"])
        gathered = groups * hkv * b["top_k"] * b["cmp_block"] * hd
        add("bsa_ball_fwd", 4 * hd * h * p["ball"], q + 2 * kv + q + lse)
        add(f"{flash}_fwd", 4 * hd * h * p["cmp"], q + 2 * ckv + q + lse)
        add("bsa_selection_fwd", 4 * hd * h * p["slc"],
            q + 2 * gathered + q + lse)
        add("bsa_epilogue_fwd", 6 * q, 4 * q)
        if train:
            add("bsa_ball_bwd", 8 * hd * h * p["ball"],
                3 * q + 2 * kv + lse + q + 2 * kv)
            add(f"{flash}_dq", 4 * hd * h * p["cmp"],
                2 * q + 2 * ckv + 2 * lse + q)
            add(f"{flash}_dkv", 4 * hd * h * p["cmp"],
                2 * q + 2 * ckv + 2 * lse + 2 * ckv)
            add("bsa_selection_bwd", 8 * hd * h * p["slc"],
                3 * q + 2 * gathered + lse + q + 2 * gathered)
            add("bsa_epilogue_bwd", 6 * q, 4 * q + 3 * q)
    return {k: (v[0], v[1]) for k, v in work.items()}
