"""Peak table and the operation/byte counts the roofline metrics use."""
import jax
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from harness import config, peaks, work


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def test_v5e_peaks_and_roofline():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    # compute bound vs memory bound
    assert peaks.roofline_s(197e12, 1, p) == pytest.approx(1.0)
    assert peaks.roofline_s(1, 819e9, p) == pytest.approx(1.0)


@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
def test_packed_bytes_match_quantized_model(tie):
    """INT4 packed bytes counted from shapes == what restructure stores."""
    from repro.configs.base import ArchConfig
    from repro.core import QuantPolicy, restructure
    from repro.models import build_model

    conf = dict(tiny.CONF, tie_word_embeddings=tie, num_hidden_layers=2,
                hidden_size=192, intermediate_size=320)
    sizes = config.model_sizes(conf)
    arch = ArchConfig(**{k: v for k, v in sizes.items()
                         if k in ArchConfig.__dataclass_fields__})
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    qm = restructure(params, QuantPolicy(bits=4, packed=True))
    assert work.quantized_bytes(sizes, bits=4) == qm.size_bytes()["quantized"]


def _sizes(**kw):
    base = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
                d_ff=16, vocab_size=10, tie_embeddings=True)
    base.update(kw)
    return base


def test_decode_flops_hand_count():
    s = _sizes()
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16 each, down 16x8
    macs_layer = 64 + 32 + 32 + 64 + 128 + 128 + 128
    macs = 2 * macs_layer + 8 * 10          # + head
    # position 5 attends over 6 keys: 4 flops x layers x heads x hd x keys
    attn = 4 * 2 * 2 * 4 * 6
    assert work.decode_token_flops(s, 5) == 2 * macs + attn


def test_prefill_flops_hand_count():
    s = _sizes()
    macs_layer = 64 + 32 + 32 + 64 + 128 + 128 + 128
    n = 3
    lin = 2 * n * 2 * macs_layer
    keys = 1 + 2 + 3                        # causal: token i sees i + 1
    attn = 4 * 2 * 2 * 4 * keys
    head = 2 * 8 * 10                       # last position only
    assert work.prefill_flops(s, 0, n) == lin + attn + head
    # a continuation from position 4 sees the 4 earlier keys too
    assert work.prefill_flops(s, 4, 1) == (2 * 2 * macs_layer
                                           + 4 * 2 * 2 * 4 * 5 + head)


def test_kernel_work_counts():
    s = _sizes(n_layers=1)
    f, b = work.paged_attention_work(s, 10)
    assert f == 4 * 1 * 2 * 4 * 10
    # K and V of 10 positions, 1 KV head of 4, bf16; q and out 2 heads x 4
    assert b == 10 * 2 * 1 * 4 * 2 + 2 * 2 * 4 * 2
    f, b = work.packed_matmul_work(2, 256, 128)
    assert f == 2 * 2 * 256 * 128
    assert b == 256 * 128 * 6 // 8 + 24 + (2 * 256 + 2 * 128) * 2
    # grouped launches per layer and an untied head
    calls = work.decode_packed_calls(_sizes(tie_embeddings=False), 4)
    assert len(calls) == 2 * 4 + 1 and calls[-1] == (4, 8, 10)
