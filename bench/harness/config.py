"""Configuration files (``bench/configs/<name>.json``) and what they map to.

A configuration file holds the published ``config.json`` keys of its source
as they are run (changed keys listed under ``reduced``), the architecture
facts the source states outside those keys (``arch``), the sizes assumed
(``assumed``), the deployment it stands for, and the name of its plain
reference under ``bench/reference``.
"""
from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent.parent

# published config.json key -> the program's ArchConfig field
_HF_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "hidden_act": "act",
    "torch_dtype": "dtype",
}


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    path = BENCH / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def model_sizes(conf: dict) -> dict:
    """The model as the program and the reference build it: the mapped
    published keys plus ``arch``."""
    out = {field: conf[key] for key, field in _HF_KEYS.items()}
    out["head_dim"] = conf.get("head_dim",
                               conf["hidden_size"]
                               // conf["num_attention_heads"])
    out.update(conf["arch"])
    out["name"] = conf["name"]
    return out
