"""Unit + property tests for basic linear quantization (paper eqs. 1-3)."""
try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
except ImportError:  # offline container: property tests skip, rest run
    from hypothesis_stub import hypothesis, hnp, st
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.quantize as qz

hypothesis.settings.register_profile(
    "repro", deadline=None, max_examples=25,
    suppress_health_check=list(hypothesis.HealthCheck),
)
hypothesis.settings.load_profile("repro")


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_qparams_range_mapping(bits):
    # beta -> qmin, alpha -> qmax per eqs (2),(3)
    x = jnp.array([-3.0, 0.0, 5.0])
    qp = qz.compute_qparams(x, bits)
    q = qz.quantize(x, qp)
    assert int(q[0]) == qp.qmin
    assert int(q[-1]) == qp.qmax


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_roundtrip_error_bound(bits):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    qp = qz.compute_qparams(x, bits)
    xh = qz.dequantize(qz.quantize(x, qp), qp)
    # max error <= one quantization step (0.5/S rounding + clamp at edges)
    step = 1.0 / float(qp.scale)
    assert float(jnp.max(jnp.abs(x - xh))) <= step * 0.5001 + 1e-6


def test_zero_exact_when_in_range():
    # 0 in [beta, alpha] => dequant(quantize(0)) == 0 exactly
    for bits in (2, 4, 8):
        x = jnp.array([-1.5, 0.0, 2.5])
        qp = qz.compute_qparams(x, bits, include_zero=True)
        xh = qz.dequantize(qz.quantize(jnp.zeros(()), qp), qp)
        assert float(xh) == 0.0


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("last", [8, 64, 120])
def test_pack_unpack_roundtrip(bits, last):
    rng = np.random.default_rng(bits + last)
    q = rng.integers(-(2 ** (bits - 1)), 2 ** (bits - 1), size=(last, 5)).astype(
        np.int8
    )
    p = qz.pack_codes(jnp.asarray(q), bits)
    u = qz.unpack_codes(p, bits, out_len=last)
    np.testing.assert_array_equal(np.asarray(u), q)
    if bits < 8:
        assert p.shape == (last // (8 // bits), 5)


@hypothesis.given(
    x=hnp.arrays(
        np.float32,
        st.integers(4, 300),
        elements=st.floats(-100, 100, width=32),
    ),
    bits=st.sampled_from([2, 4, 8]),
)
def test_property_quantize_monotone(x, bits):
    """Quantization is monotone non-decreasing (order preserved)."""
    hypothesis.assume(float(np.ptp(x)) > 1e-3)
    qp = qz.compute_qparams(jnp.asarray(x), bits)
    q = np.asarray(qz.quantize(jnp.asarray(x), qp)).astype(np.int32)
    order = np.argsort(x, kind="stable")
    assert (np.diff(q[order]) >= 0).all()


@hypothesis.given(
    x=hnp.arrays(np.float32, st.integers(8, 200),
                 elements=st.floats(-50, 50, width=32)),
    bits=st.sampled_from([4, 8]),
)
def test_property_codes_in_range(x, bits):
    hypothesis.assume(float(np.ptp(x)) > 1e-3)
    qp = qz.compute_qparams(jnp.asarray(x), bits)
    q = np.asarray(qz.quantize(jnp.asarray(x), qp))
    assert q.min() >= -(2 ** (bits - 1)) and q.max() <= 2 ** (bits - 1) - 1


def test_per_channel_and_group():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    qp_c = qz.compute_qparams(w, 4, channel_axis=0)
    assert qp_c.scale.shape == (16, 1)
    qp_g = qz.compute_qparams(w, 4, group_size=16)
    assert qp_g.scale.shape == (16, 64)
    # finer granularity must not be worse than per-tensor
    qp_t = qz.compute_qparams(w, 4)
    e_t = float(jnp.mean((qz.dequantize(qz.quantize(w, qp_t), qp_t) - w) ** 2))
    e_c = float(jnp.mean((qz.dequantize(qz.quantize(w, qp_c), qp_c) - w) ** 2))
    e_g = float(jnp.mean((qz.dequantize(qz.quantize(w, qp_g), qp_g) - w) ** 2))
    assert e_c <= e_t * 1.05 and e_g <= e_c * 1.05


def test_quantize_tensor_storage():
    w = jnp.asarray(np.random.default_rng(2).normal(size=(32, 100)).astype(np.float32))
    qt = qz.quantize_tensor(w, 4)
    assert qt.packed.shape == (16, 100)  # 2 codes per byte, packed along K
    err = float(jnp.max(jnp.abs(qt.dequantize() - w)))
    assert err < 1.0  # coarse sanity; exact bound tested above
