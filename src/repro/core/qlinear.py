"""Quantized linear execution paths.

Three ways to run ``y = x @ Ŵ + b`` with SplitQuantV2 weights, all producing
identical values (tested):

* ``splitq_linear_3pass`` — the **paper's deployment**: three real layers,
  one matmul per plane, outputs summed. This is the paper-faithful baseline
  (and its §5 limitation: 3× matmul work).
* ``splitq_linear_fused`` — dequantize-and-add the planes, then a single
  matmul (what our Pallas kernel ``splitq_matmul`` does tile-wise in VMEM).
* ``splitq_linear_packed`` — single matmul from the 6-bit packed layout
  (Pallas kernel ``splitq_packed``), half the paper's weight bandwidth.

``qlinear`` is the non-split baseline (per-tensor quantized linear). The jnp
bodies here double as the oracles for the Pallas kernels in
``repro/kernels``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor, dequantize, unpack_codes
from repro.core.split import PackedSplitQTensor, SplitQTensor


def linear(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b
    return y.astype(x.dtype)


def qlinear(x: jax.Array, qt: QTensor, b: jax.Array | None = None) -> jax.Array:
    """Baseline: per-tensor linear-quantized weight."""
    return linear(x, qt.dequantize(), b)


def splitq_linear_3pass(
    x: jax.Array, sq: SplitQTensor, b: jax.Array | None = None
) -> jax.Array:
    """Paper-faithful: k separate (de)quantized layers, outputs summed."""
    y = jnp.zeros(x.shape[:-1] + (sq.shape[-1],), jnp.float32)
    for c in range(sq.k):
        q = unpack_codes(sq.planes[c], sq.bits, out_len=sq.shape[-2])
        wc = dequantize(q, sq.plane_qparams(c))
        y = y + jnp.dot(x.astype(jnp.float32), wc)
    if b is not None:
        y = y + b
    return y.astype(x.dtype)


def splitq_linear_fused(
    x: jax.Array, sq: SplitQTensor, b: jax.Array | None = None
) -> jax.Array:
    """Fused: sum planes first (one matmul). Value-identical to 3pass up to
    float summation order; bit-identical weight sum because plane supports
    are disjoint and off-support entries are exact zeros."""
    return linear(x, sq.dequantize(), b)


def splitq_linear_packed(
    x: jax.Array, psq: PackedSplitQTensor, b: jax.Array | None = None
) -> jax.Array:
    """Single matmul from the 6-bit packed layout."""
    return linear(x, psq.dequantize(), b)
