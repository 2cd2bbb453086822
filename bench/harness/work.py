"""Operations and bytes of the served model, counted from shapes.

Counted for a dense GQA decoder served with SplitQuantV2 packed weights:
every projection stores a ``bits``-bit code and a 2-bit cluster id per
weight plus a 3-entry (S, Z) table, rows padded as the packed layout pads
them (to a multiple of 4 up to one 128-row slab, to whole slabs past it).
Activations and KV are bf16. ``cfg`` is a configuration file's ``model``
dict (see ``bench/configs``).
"""
from __future__ import annotations

SLAB = 128
CLUSTERS = 3
ACT_BYTES = 2   # bf16 activations, outputs and KV


def packed_rows(k: int) -> int:
    k4 = -(-max(k, 1) // 4) * 4
    return k4 if k4 <= SLAB else -(-k // SLAB) * SLAB


def packed_weight_bytes(k: int, n: int, bits: int = 4) -> int:
    """Bytes of one packed (K, N) weight: codes, cluster ids, 1/S and Z."""
    rows = packed_rows(k)
    return rows * n * bits // 8 + rows * n * 2 // 8 + 2 * CLUSTERS * 4


def layer_projections(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every quantized projection in one layer."""
    d, h, kv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    return [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
            ("wo", h * hd, d), ("w_gate", d, ff), ("w_up", d, ff),
            ("w_down", ff, d)]


def head_quantized(cfg: dict) -> bool:
    """An untied LM head is a quantized projection; a tied one is the
    bf16 embedding table."""
    return not cfg["tie_embeddings"]


def quantized_bytes(cfg: dict, bits: int = 4) -> int:
    """Stored bytes of every quantized leaf, each tensor on its own."""
    per_layer = sum(packed_weight_bytes(k, n, bits)
                    for _, k, n in layer_projections(cfg))
    total = cfg["n_layers"] * per_layer
    if head_quantized(cfg):
        total += packed_weight_bytes(cfg["d_model"], cfg["vocab_size"], bits)
    return total


def linear_macs_per_token(cfg: dict) -> int:
    """Multiply-adds of the projections and the LM head for one token."""
    per_layer = sum(k * n for _, k, n in layer_projections(cfg))
    return cfg["n_layers"] * per_layer + cfg["d_model"] * cfg["vocab_size"]


def attention_flops(cfg: dict, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` positions, all
    layers: 2 flops per multiply-add, two products per key and head."""
    return 4 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * keys


def decode_token_flops(cfg: dict, position: int) -> int:
    """Model FLOPs of the decode step for one live row whose new token sits
    at ``position`` (it attends over ``position + 1`` keys)."""
    return 2 * linear_macs_per_token(cfg) + attention_flops(cfg, position + 1)


def prefill_flops(cfg: dict, start: int, n: int) -> int:
    """Model FLOPs of prefilling tokens ``start .. start + n - 1`` of one
    prompt, causal attention over the keys each really sees, and the LM
    head at the last position only."""
    per_layer = sum(k * n_ for _, k, n_ in layer_projections(cfg))
    lin = 2 * n * cfg["n_layers"] * per_layer
    keys = n * start + n * (n + 1) // 2
    head = 2 * cfg["d_model"] * cfg["vocab_size"]
    return lin + attention_flops(cfg, keys) + head


def kv_bytes_per_token(cfg: dict) -> int:
    """KV cache bytes one position holds over all layers."""
    return (cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"]
            * ACT_BYTES)


def paged_attention_work(cfg: dict, keys: int) -> tuple[int, int]:
    """(flops, bytes) the paged decode kernel needs for one live row with
    ``keys`` valid positions, all layers: the live K and V pages, the query
    and the output."""
    qo = 2 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * ACT_BYTES
    return attention_flops(cfg, keys), keys * kv_bytes_per_token(cfg) + qo


def packed_matmul_work(m: int, k: int, n: int, bits: int = 4
                       ) -> tuple[int, int]:
    """(flops, bytes) of one packed matmul call: x (M, K) and out (M, N)
    in bf16, the packed weight read once."""
    return (2 * m * k * n,
            packed_weight_bytes(k, n, bits) + (m * k + m * n) * ACT_BYTES)


def layer_launches(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of each packed kernel launch of one layer, with sibling
    projections grouped into one launch (QKV, gate+up) as served."""
    d, h, kv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    return [("wqkv", d, (h + 2 * kv) * hd), ("wo", h * hd, d),
            ("w_gateup", d, 2 * ff), ("w_down", ff, d)]


def decode_packed_calls(cfg: dict, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every packed kernel launch of one decode step over
    ``rows`` batch rows: each layer's launches, and an untied head."""
    calls = [(rows, k, n) for _, k, n in layer_launches(cfg)]
    calls = calls * cfg["n_layers"]
    if head_quantized(cfg):
        calls.append((rows, cfg["d_model"], cfg["vocab_size"]))
    return calls
