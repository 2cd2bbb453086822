"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import compute_qparams, quantize, pack_codes
from repro.core.split import split_quantize, split_quantize_packed
from repro.kernels import ops, ref


def _w(shape, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 0.05, size=shape).astype(np.float32)
    flat = w.reshape(-1)
    idx = rng.choice(flat.size, max(2, flat.size // 200), replace=False)
    flat[idx] *= 10  # outliers
    return jnp.asarray(w)


def _x(shape, dtype, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)


# interpret-mode Pallas is slow on CPU: the bit/dtype sweeps run on small
# shapes only; FULL_SHAPE keeps one multi-block case per kernel.
MM_SHAPES = [
    (8, 64, 32),      # tiny, all dims below one block
    (130, 200, 520),  # ragged -> exercises padding
]
FULL_SHAPE = (256, 384, 1024)  # multi-block (full-size case per kernel)


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_quant_matmul_vs_ref(m, k, n, bits, dtype):
    per = 8 // bits
    w = _w((k, n), seed=m + bits)
    qp = compute_qparams(w, bits)
    q = quantize(w, qp)
    pad = (-n) % per
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
    wp = pack_codes(q, bits)
    x = _x((m, k), dtype)
    y_ker = ops.quant_matmul(x, wp, qp.scale, qp.zero, bits)
    y_ref = ref.quant_matmul_ref(x, wp, qp.scale, qp.zero, bits)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(y_ker[:, :n], np.float32),
        np.asarray(y_ref[:, :n], np.float32),
        rtol=tol, atol=tol * max(1.0, float(jnp.abs(y_ref).max())),
    )


def test_quant_matmul_full_size_and_bf16():
    m, k, n = FULL_SHAPE
    test_quant_matmul_vs_ref(m, k, n, 4, jnp.float32)
    test_quant_matmul_vs_ref(128, 128, 512, 4, jnp.bfloat16)


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("bits", [4, 8])
def test_splitq_matmul_vs_ref(m, k, n, bits):
    w = _w((k, n), seed=m * 7 + bits)
    sq = split_quantize(w, bits)
    x = _x((m, k), jnp.float32)
    y_ker = ops.splitq_matmul(x, sq)
    y_ref = ref.splitq_matmul_ref(x, sq.planes, sq.scales, sq.zeros, bits)
    np.testing.assert_allclose(
        np.asarray(y_ker), np.asarray(y_ref[:, :n]), rtol=2e-5, atol=1e-3
    )


def test_splitq_matmul_full_size():
    test_splitq_matmul_vs_ref(*FULL_SHAPE, 4)


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_splitq_packed_matmul_vs_ref(m, k, n, bits):
    w = _w((k, n), seed=m * 3 + bits)
    psq = split_quantize_packed(w, bits)
    x = _x((m, k), jnp.float32)
    y_ker = ops.splitq_packed_matmul(x, psq)
    y_ref = ref.splitq_packed_matmul_ref(
        x, psq.codes, psq.cids, psq.scales, psq.zeros, bits
    )
    np.testing.assert_allclose(
        np.asarray(y_ker), np.asarray(y_ref[:, :n]), rtol=2e-5, atol=1e-3
    )


def test_splitq_packed_matmul_full_size():
    test_splitq_packed_matmul_vs_ref(*FULL_SHAPE, 4)


def test_splitq_kernels_match_dense_dequant():
    """Kernel output == x @ sq.dequantize() — ties kernels to the core."""
    k, n, m = 96, 160, 24
    w = _w((k, n), seed=11)
    x = _x((m, k), jnp.float32)
    sq = split_quantize(w, 4)
    psq = split_quantize_packed(w, 4)
    y_dense = jnp.dot(x, sq.dequantize())
    np.testing.assert_allclose(
        np.asarray(ops.splitq_matmul(x, sq)), np.asarray(y_dense),
        rtol=1e-4, atol=1e-3,
    )
    np.testing.assert_allclose(
        np.asarray(ops.splitq_packed_matmul(x, psq)), np.asarray(y_dense),
        rtol=1e-4, atol=1e-3,
    )


@pytest.mark.parametrize("r,c", [(4, 16), (300, 1000)])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quantize_pack_vs_ref(r, c, bits):
    w = _w((r, c), seed=r + c + bits)
    qp = compute_qparams(w, bits)
    got = ops.quantize_pack(w, qp.scale, qp.zero, bits)
    want = ref.quantize_pack_ref(w, qp.scale, qp.zero, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [100, 4096, 100_000])
@pytest.mark.parametrize("k", [2, 3])
def test_kmeans_assign_reduce_vs_ref(n, k):
    rng = np.random.default_rng(n + k)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    cents = jnp.asarray(np.sort(rng.normal(size=(k,)).astype(np.float32)))
    sums, counts = ops.kmeans_assign_reduce(x, cents)
    rs, rc = ref.kmeans_assign_reduce_ref(x, cents)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rs), rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(rc), rtol=0, atol=0.5)
    assert float(counts.sum()) == n  # padding must not count


def test_kmeans_kernel_drives_lloyd_to_same_fixpoint():
    """Full Lloyd loop on the kernel == core.kmeans1d centroids."""
    from repro.core.kmeans import kmeans1d, quantile_init

    rng = np.random.default_rng(5)
    x = np.concatenate(
        [rng.normal(-4, 0.2, 3000), rng.normal(0, 0.2, 5000), rng.normal(5, 0.2, 2000)]
    ).astype(np.float32)
    xj = jnp.asarray(x)
    cents = quantile_init(xj, 3)
    for _ in range(16):
        sums, counts = ops.kmeans_assign_reduce(xj, cents)
        cents = jnp.sort(jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cents))
    want = np.asarray(kmeans1d(xj, k=3).centroids)
    np.testing.assert_allclose(np.asarray(cents), want, atol=0.05)


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None), ("METAL", None),
])
def test_interpret_mode_by_platform(monkeypatch, platform, interpret):
    """Interpreted on the CPU, compiled on a TPU, refused anywhere else —
    never a silent interpreter in front of an accelerator."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match=platform):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret
