"""The plain reference agrees with the program on seeded random weights.

The reference imports nothing of the program; here, on the CPU at a small
size, it is held to the program's own weights, quantization and forward
pass (computed in float32 at highest precision on the program's side too).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import check, config

ref = check.load_reference("dense_gqa")


@pytest.fixture(scope="module")
def program():
    from repro.configs.base import ArchConfig
    from repro.core import QuantPolicy, restructure
    from repro.models import build_model

    sizes = config.model_sizes(dict(tiny.CONF, intermediate_size=384))
    arch = ArchConfig(**{k: v for k, v in sizes.items()
                         if k in ArchConfig.__dataclass_fields__})
    key = jax.random.PRNGKey(11)
    params = jax.jit(build_model(arch).init)(key)
    qm = restructure(params, QuantPolicy(bits=4, packed=True))
    return sizes, arch, key, params, qm


def test_weights_and_quantization_are_the_programs(program):
    sizes, _, key, params, qm = program
    rc = ref.RefConfig.from_model(sizes)
    _, k_layers, _, _ = ref.top_keys(key)
    keys = jax.random.split(k_layers, rc.n_layers)
    for i in range(rc.n_layers):
        w = ref._layer_weights(keys[i], rc)
        for n, t in w.items():
            group = "attn" if n in ("wq", "wk", "wv", "wo") else "mlp"
            assert np.array_equal(np.asarray(t),
                                  np.asarray(params["layers"][group][n][i]))
            got = np.asarray(ref.split_quantize(t))
            want = np.asarray(jax.vmap(lambda q: q.dequantize())(
                qm.qleaves[f"layers/{group}/{n}"])[i])
            assert np.array_equal(got, want), n


def test_forward_agrees_with_the_program_in_float32(program):
    from repro.models import transformer as tfm

    sizes, arch, key, _, qm = program
    cfg32 = dataclasses.replace(arch, dtype="float32")
    mat = jax.tree.map(lambda a: a.astype(jnp.float32), qm.materialize())
    seq = np.random.default_rng(0).integers(0, arch.vocab_size, 40)
    with jax.default_matmul_precision("highest"):
        x = tfm.embed_tokens(cfg32, mat, jnp.asarray(seq)[None])
        hid, _, _ = tfm.decoder_forward(cfg32, mat, x, jnp.arange(40)[None])
        logits = np.asarray(tfm.logits_fn(cfg32, mat, hid))[0]
    served = logits.argmax(-1)
    out = ref.gaps(ref.RefConfig.from_model(sizes), key, [seq], [served],
                   ("f32", "fp8"))
    gap, best = out["f32"][0]
    assert np.array_equal(best, served)
    assert float(gap.max()) < 1e-4
    # the control (fp8 matmul operands) does not agree so closely
    assert float(out["fp8"][0][0].max()) > 1e-2


@pytest.mark.parametrize("seed", range(6))
def test_kmeans_bounds_are_the_programs(seed):
    """Thresholds from counted bf16 values agree with the program's
    k-means up to float32 summation order (an ulp or two)."""
    from repro.core import kmeans

    rng = np.random.default_rng(seed)
    shape = (int(rng.integers(64, 700)), int(rng.integers(64, 900)))
    w = jnp.asarray(rng.standard_normal(shape) * rng.uniform(0.01, 0.2),
                    jnp.bfloat16)
    got = ref.kmeans_bounds(w)
    want = np.asarray(kmeans.kmeans1d(w).boundaries)
    np.testing.assert_allclose(got, want, rtol=3e-7, atol=0)
