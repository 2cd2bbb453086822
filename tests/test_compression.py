"""Gradient compression: quantizer properties + multi-device collective
exactness (subprocess with 8 fake devices so the main test process keeps
seeing 1 CPU device)."""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from serve_helpers import REPO_ROOT, subprocess_env

from repro.optim.compression import BLOCK, quant_roundtrip


def test_quant_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(10_000,)).astype(np.float32) * 5)
    y = quant_roundtrip(x)
    # per-block symmetric int8: |err| <= max|block| / 127 / 2 (round)
    xb = np.pad(np.asarray(x), (0, (-x.size) % BLOCK)).reshape(-1, BLOCK)
    bound = np.repeat(np.abs(xb).max(1) / 127.0, BLOCK)[: x.size] * 0.5 + 1e-9
    assert (np.abs(np.asarray(y) - np.asarray(x)) <= bound).all()


def test_quant_roundtrip_preserves_zero_and_scale_outliers():
    x = jnp.zeros((512,), jnp.float32)
    assert (np.asarray(quant_roundtrip(x)) == 0).all()
    # an outlier block does not degrade other blocks
    x = jnp.asarray(
        np.concatenate([np.full(256, 1e-3, np.float32),
                        np.full(256, 1e3, np.float32)])
    )
    y = np.asarray(quant_roundtrip(x))
    np.testing.assert_allclose(y[:256], 1e-3, rtol=0.01)
    np.testing.assert_allclose(y[256:], 1e3, rtol=0.01)


_SUBPROCESS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import make_compressed_allreduce, BLOCK

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    n = BLOCK * 8 * 4
    g = jnp.asarray(rng.normal(size=(8, n)).astype(np.float32))
    tree = {"w": g}
    err0 = {"w": jnp.zeros_like(g)}
    ar = jax.jit(make_compressed_allreduce(mesh, "data"))  # jit ONCE
    with mesh:
        mean, err = ar(tree, err0)
    want = np.asarray(g).mean(0)
    got = np.asarray(mean["w" ])[0]
    # int8-compressed mean within quantization tolerance of the true mean
    tol = np.abs(np.asarray(g)).max() / 127.0 * 2.5
    assert np.abs(got - want).max() < tol, (np.abs(got - want).max(), tol)

    # error-feedback accumulation: averaged over steps, compressed means
    # converge to true means (bias ~ 0)
    errs = err0
    acc_c, acc_t = 0.0, 0.0
    for step in range(24):
        gs = jnp.asarray(rng.normal(size=(8, n)).astype(np.float32))
        with mesh:
            mean, errs = ar({"w": gs}, errs)
        acc_c = acc_c + np.asarray(mean["w"])[0]
        acc_t = acc_t + np.asarray(gs).mean(0)
    bias = np.abs(acc_c - acc_t).max() / 24
    raw = np.abs(np.asarray(gs)).max() / 127.0
    assert bias < raw, (bias, raw)  # EF keeps accumulated bias below 1-step q-error
    print("OK")
    """
)


def test_compressed_allreduce_multidevice():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS],
        capture_output=True, text=True, timeout=300,
        env=subprocess_env(8), cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
