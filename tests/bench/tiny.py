"""A tiny cell for running the benchmark harness on the CPU in tests."""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CONF = {
    "name": "tiny", "reduced": [], "reference": "dense_gqa",
    "num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
    "vocab_size": 1024, "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "torch_dtype": "bfloat16",
    "arch": {"family": "dense", "qk_norm": True, "glu": True},
}
MIX = {
    "loop": "open", "rate_per_s": 6.0, "order_seed": 20250310,
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 30},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3,
               "max": 12},
}
CELL = {
    "server": {"slots": 4, "max_len": 48, "page_size": 16, "num_pages": 12},
    "quant": {"bits": 4},
    "check": {"sample_tokens": 60, "widest_gap": {"limit": 0.05}},
}
BENCH_SPEC = {
    "workloads": [{"name": "tiny.chat", "config": "tiny", "traffic": "tiny",
                   "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": "out_tok_per_s", "unit": "tokens/s"},
        {"name": "ttft_p70_ms", "unit": "ms"},
        {"name": "itl_p90_ms", "unit": "ms"},
        {"name": "itl_p99_ms", "unit": "ms"},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


def override(**kw) -> dict:
    out = {"bench": BENCH_SPEC, "conf": CONF, "mix": MIX, "cell": CELL}
    out.update(kw)
    return out
