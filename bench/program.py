"""The system under test, as the benchmark builds it from a configuration
file: the registry's model with every size taken from the file, on the
file's attention backend.  Nothing else of the program is configured here."""

from __future__ import annotations

import dataclasses

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "in_dim", "out_dim", "attention", "norm_eps", "param_dtype",
              "compute_dtype", "remat")


def model_config(cfg: dict):
    """``repro`` ModelConfig for a configuration file (see bench/configs)."""
    from repro.configs import get_config

    base = get_config(cfg["registry"])
    bsa = dataclasses.replace(base.bsa, **cfg.get("bsa", {}),
                              backend=cfg["backend"])
    return base.scaled(**{k: cfg["model"][k] for k in MODEL_KEYS}, bsa=bsa)


def model_api(cfg: dict):
    from repro.models.api import model_api as api

    return api(model_config(cfg))
