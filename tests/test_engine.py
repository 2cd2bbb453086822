"""Quantized execution engine: as_executable() + packed kernels end-to-end.

Covers the acceptance contract of the engine PR:
* executable forward matches materialize() fake-quant logits within float
  tolerance, and the packed/grouped containers dequantize BIT-exactly to
  the per-tensor quantized weights,
* serving decode through the packed kernels emits the same tokens as the
  fake-quant path,
* grouped QKV + gate/up dispatch cuts quantized kernel launches per
  transformer block from 7 to 4,
* the autotuner returns valid MXU-aligned blocks for odd shapes and honors
  the measured JSON cache.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantPolicy, restructure
from repro.core.quantize import packed_rows
from repro.core.split import group_packed, split_quantize_packed
from repro.engine import autotune
from repro.engine.executable import supports_kernel_path, weight_bytes
from repro.kernels import ops
from repro.models import build_model


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("llama32-1b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qm = restructure(params, QuantPolicy(bits=4, packed=True))
    return cfg, model, params, qm


def test_executable_matches_fake_quant_logits(tiny):
    cfg, model, _, qm = tiny
    ex = qm.as_executable(group=True)
    fk = qm.materialize()
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8), dtype=np.int32))
    l_ex, _ = model.prefill(ex, {"tokens": toks}, model.init_cache(2, 16))
    l_fk, _ = model.prefill(fk, {"tokens": toks}, model.init_cache(2, 16))
    np.testing.assert_allclose(
        np.asarray(l_ex), np.asarray(l_fk), rtol=1e-4, atol=1e-4,
    )


def test_grouped_weights_dequantize_bit_exact(tiny):
    _, _, _, qm = tiny
    ex = qm.as_executable(group=True)
    attn = ex["layers"]["attn"]
    mlp_p = ex["layers"]["mlp"]
    assert "wqkv" in attn and "w_gateup" in mlp_p
    # grouped dequant == member dequant, bit for bit (stacked layer axis)
    members = [qm.qleaves[f"layers/attn/{n}"] for n in ("wq", "wk", "wv")]
    got = attn["wqkv"].dequantize()
    for g, m in zip(got, members):
        want = jax.vmap(lambda t: t.dequantize())(m)
        assert (np.asarray(g) == np.asarray(want)).all()
    got = mlp_p["w_gateup"].dequantize()
    for g, name in zip(got, ("w_gate", "w_up")):
        want = jax.vmap(lambda t: t.dequantize())(
            qm.qleaves[f"layers/mlp/{name}"])
        assert (np.asarray(g) == np.asarray(want)).all()


def test_grouped_launch_count_per_block(tiny):
    cfg, model, _, qm = tiny
    toks = jnp.zeros((2, 1), jnp.int32)
    cache = model.init_cache(2, 8)

    def launches(tree):
        with ops.count_launches() as counts:
            jax.eval_shape(lambda p, t, c: model.decode_step(p, t, c)[0],
                           tree, toks, cache)
        return counts

    grouped = launches(qm.as_executable(group=True))
    ungrouped = launches(qm.as_executable(group=False))
    # scan traces the block body once: counts are per transformer block.
    # 7 separate quantized matmuls (q,k,v,o,gate,up,down) collapse to 4
    # launches (fused qkv, o, fused gate+up, down).
    assert ungrouped["total"] == 7, ungrouped
    assert grouped["total"] == 4, grouped
    assert grouped["splitq_packed_group_matmul"] == 2


def test_serve_same_tokens_as_fake_quant(tiny):
    from repro.launch.serve import BatchedServer, Request

    cfg, model, _, qm = tiny

    def run(tree):
        server = BatchedServer(model, tree, batch_slots=2, max_len=16)
        reqs = [
            Request(i, np.random.default_rng(100 + i).integers(
                0, cfg.vocab_size, 6, dtype=np.int32), 3)
            for i in range(2)
        ]
        server.run(reqs)
        return [r.out for r in reqs]

    assert run(qm.as_executable(group=True)) == run(qm.materialize())


def test_packed_halves_weight_bytes_vs_planes(tiny):
    _, _, params, qm = tiny
    planes = restructure(params, QuantPolicy(bits=4, packed=False))
    b_packed = qm.size_bytes()["quantized"]
    b_planes = planes.size_bytes()["quantized"]
    assert b_planes / b_packed >= 1.9  # 12 vs 6 bits/weight
    # executable tree bytes < dense fp32 bytes
    assert weight_bytes(qm.as_executable()) < weight_bytes(params) / 2


def test_unsupported_leaves_fall_back_dense():
    """MoE expert stacks are dequantized once (== materialize) and the
    executable forward still runs end-to-end."""
    cfg = get_config("deepseek-moe-16b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    qm = restructure(params, QuantPolicy(bits=4, packed=True))
    ex = qm.as_executable(group=True)
    experts = ex["layers"]["moe"]["experts"]["w_up"]
    assert isinstance(experts, jax.Array)  # densified, not a container
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (1, 6), dtype=np.int32))
    l_ex, _ = model.prefill(ex, {"tokens": toks}, model.init_cache(1, 8))
    l_fk, _ = model.prefill(qm.materialize(), {"tokens": toks},
                            model.init_cache(1, 8))
    np.testing.assert_allclose(np.asarray(l_ex), np.asarray(l_fk),
                               rtol=1e-4, atol=1e-4)


def test_supports_kernel_path_paths():
    assert supports_kernel_path("layers/attn/wq")
    assert supports_kernel_path("layers/mlp/w_down")
    assert supports_kernel_path("shared_attn/mlp/w_up")
    assert supports_kernel_path("lm_head/w")
    assert not supports_kernel_path("layers/tmix/wk")      # rwkv mixer
    assert not supports_kernel_path("layers/moe/experts/w_up")
    assert not supports_kernel_path("embed/table")


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (1, 100, 130), (4, 128, 64), (13, 777, 333), (128, 4096, 11008),
    (260, 5120, 13824),
])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_autotuner_blocks_valid_for_odd_shapes(m, k, n, bits):
    bm, bn, bk = autotune.heuristic_block(m, k, n, bits)
    assert bm % 8 == 0 and bm >= 8          # fp32 sublane
    assert bn % 128 == 0                    # lane
    # K tile covers whole packing slabs: 128-row multiples, or the whole
    # K of a single-slab weight
    assert bk % 128 == 0 or bk == packed_rows(k) < 128
    assert autotune._vmem_bytes(bm, bn, bk, bits) <= autotune.VMEM_BUDGET
    # bf16 activations need 16-row sublane alignment
    bm16, _, _ = autotune.heuristic_block(m, k, n, bits, bf16_acts=True)
    assert bm16 % 16 == 0


def test_autotuner_grouped_bn_divides_align():
    for align in (128, 512):
        _, bn, _ = autotune.choose_block(4, 1024, 3 * align, 4, max_bn=align)
        assert align % bn == 0


def test_tune_cache_roundtrip_and_dispatch(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    cache = autotune.TuneCache(path)
    cache.put(16, 1024, 1024, 4, (128, 256, 128))
    cache.save()
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune.reset_cache()
    try:
        assert autotune.choose_block(16, 1024, 1024, 4) == (128, 256, 128)
        # invalid cached entries are rejected, falling back to heuristic
        autotune.get_cache().put(8, 256, 256, 4, (100, 100, 100))
        assert autotune.choose_block(8, 256, 256, 4) == \
            autotune.heuristic_block(8, 256, 256, 4)
        raw = json.loads(path.read_text())
        assert raw["blocks"]["16x1024x1024@4"] == [128, 256, 128]
        # bf16-activation entries live in their own +bf16 namespace: a
        # block tuned for bf16 activations must not answer the fp32 lookup
        autotune.get_cache().put(16, 1024, 512, 4, (128, 128, 128),
                                 bf16_acts=True)
        assert autotune.choose_block(16, 1024, 512, 4, bf16_acts=True) == \
            (128, 128, 128)
        assert autotune.choose_block(16, 1024, 512, 4) == \
            autotune.heuristic_block(16, 1024, 512, 4)
    finally:
        monkeypatch.delenv(autotune.ENV_CACHE)
        autotune.reset_cache()


def test_tune_cache_rejects_malformed_entries_at_load(tmp_path, monkeypatch):
    """A hand-edited 2-element (or non-int) entry must degrade to the
    heuristic at LOAD time, not raise inside choose_block on the hot
    path."""
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({"schema": 3, "blocks": {
        "16x1024x1024@4": [128, 256],            # truncated by hand-edit
        "8x256x256@4": ["128", 128, 128],        # non-int member
        "8x512x512@4": None,                     # nulled entry
        "8x768x768@4/d1": [8, 128, 128],         # schema-2 shard-suffix key
        "4x128x128@4": [8, 128, 128],            # the one valid entry
    }}))
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune.reset_cache()
    try:
        assert autotune.choose_block(16, 1024, 1024, 4) == \
            autotune.heuristic_block(16, 1024, 1024, 4)
        assert autotune.choose_block(8, 256, 256, 4) == \
            autotune.heuristic_block(8, 256, 256, 4)
        assert autotune.choose_block(8, 512, 512, 4) == \
            autotune.heuristic_block(8, 512, 512, 4)
        # the stale schema-2 key (/dS shard suffix) is dropped at load: it
        # was tuned for the column-packed layout
        assert "8x768x768@4/d1" not in autotune.get_cache().table
        assert autotune.choose_block(4, 128, 128, 4) == (8, 128, 128)
    finally:
        monkeypatch.delenv(autotune.ENV_CACHE)
        autotune.reset_cache()


def test_tune_cache_hit_rechecks_vmem_budget():
    """A stale entry tuned on a bigger-VMEM machine must not be dispatched
    past this build's budget."""
    autotune.reset_cache()
    try:
        huge = (8, 4096, 4096)  # aligned, but ~25 MB of double-buffered tiles
        assert autotune._vmem_bytes(*huge, 4) > autotune.VMEM_BUDGET
        autotune.get_cache().put(8, 4096, 4096, 4, huge)
        got = autotune.choose_block(8, 4096, 4096, 4)
        assert got == autotune.heuristic_block(8, 4096, 4096, 4)
        assert autotune._vmem_bytes(*got, 4) <= autotune.VMEM_BUDGET
    finally:
        autotune.reset_cache()


def test_autotune_measured_picks_and_records(monkeypatch):
    autotune.reset_cache()
    w = jnp.asarray(np.random.default_rng(0).normal(
        0, 0.05, (256, 256)).astype(np.float32))
    psq = split_quantize_packed(w, 4)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(8, 256)).astype(np.float32))

    def run(block):
        return ops.splitq_packed_matmul(x, psq, block=block)

    best, timings = autotune.autotune(
        run, 8, 256, 256, 4, iters=1,
        candidates=[(8, 128, 128), (8, 256, 128)],
    )
    assert best in [(8, 128, 128), (8, 256, 128)]
    assert timings
    assert autotune.get_cache().get(8, 256, 256, 4) == best
    autotune.reset_cache()


def test_bf16_activations_through_packed_kernel():
    w = jnp.asarray(np.random.default_rng(3).normal(
        0, 0.05, (96, 160)).astype(np.float32))
    psq = split_quantize_packed(w, 4)
    x32 = jnp.asarray(np.random.default_rng(4).normal(
        size=(4, 96)).astype(np.float32))
    y16 = ops.splitq_packed_matmul(x32.astype(jnp.bfloat16), psq)
    assert y16.dtype == jnp.bfloat16
    y32 = ops.splitq_packed_matmul(x32, psq)
    np.testing.assert_allclose(np.asarray(y16, np.float32), np.asarray(y32),
                               rtol=2e-2, atol=2e-2)


def test_group_packed_single_kernel_launch():
    rng = np.random.default_rng(5)
    members = [
        split_quantize_packed(jnp.asarray(
            rng.normal(0, 0.05, (64, n)).astype(np.float32)), 4)
        for n in (128, 64, 64)
    ]
    grp = group_packed(members)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    with ops.count_launches() as counts:
        outs = ops.splitq_packed_group_matmul(x, grp)
    assert counts == {"splitq_packed_group_matmul": 1, "total": 1}
    for o, m in zip(outs, members):
        want = ops.splitq_packed_matmul(x, m)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
