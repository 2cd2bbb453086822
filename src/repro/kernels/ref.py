"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here is the semantic ground truth; tests sweep shapes/dtypes
and ``assert_allclose`` kernel-vs-oracle with ``interpret=True`` on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import unpack_codes


def quant_matmul_ref(
    x: jax.Array,          # (M, K) activations, fp32/bf16
    w_packed: jax.Array,   # (Kp // per, N) packed int-b codes along K
    scale: jax.Array,      # per-tensor () or per-group (K // G, N)
    zero: jax.Array,       # same shape as scale
    bits: int,
) -> jax.Array:
    """y = x @ dequant(W). Weights packed along the K (row) axis."""
    q = unpack_codes(w_packed, bits, out_len=x.shape[-1]).astype(jnp.float32)
    if scale.ndim == 0:
        w = (q - zero) / scale
    else:
        g = q.shape[0] // scale.shape[0]
        s = jnp.repeat(scale, g, axis=0)
        z = jnp.repeat(zero, g, axis=0)
        w = (q - z) / s
    return jnp.dot(x.astype(jnp.float32), w).astype(x.dtype)


def splitq_matmul_ref(
    x: jax.Array,          # (M, K)
    planes: jax.Array,     # (k, Kp // per, N) packed int-b codes
    scales: jax.Array,     # (k,)
    zeros: jax.Array,      # (k,)
    bits: int,
) -> jax.Array:
    """Fused SplitQuantV2 matmul: y = x @ sum_c dequant(plane_c)."""
    kdim = x.shape[-1]
    w = jnp.zeros((kdim, planes.shape[-1]), jnp.float32)
    for c in range(planes.shape[0]):
        q = unpack_codes(planes[c], bits, out_len=kdim).astype(jnp.float32)
        w = w + (q - zeros[c]) / scales[c]
    return jnp.dot(x.astype(jnp.float32), w).astype(x.dtype)


def splitq_packed_matmul_ref(
    x: jax.Array,          # (M, K)
    codes: jax.Array,      # (Kp // per, N) packed int-b codes
    cids: jax.Array,       # (Kp // 4, N) packed 2-bit cluster ids
    scales: jax.Array,     # (k,)
    zeros: jax.Array,      # (k,)
    bits: int,
) -> jax.Array:
    """Beyond-paper 6-bit layout: w_ij = (q_ij - Z[cid_ij]) / S[cid_ij]."""
    kdim = x.shape[-1]
    q = unpack_codes(codes, bits, out_len=kdim).astype(jnp.float32)
    cid = unpack_codes(cids, 2, out_len=kdim).astype(jnp.int32) & 0x3
    w = (q - zeros[cid]) / scales[cid]
    return jnp.dot(x.astype(jnp.float32), w).astype(x.dtype)


def quantize_pack_ref(
    w: jax.Array,          # (R, C)
    scale: jax.Array,      # ()
    zero: jax.Array,       # ()
    bits: int,
) -> jax.Array:
    """Fused quantize+pack: codes = clip(round(S*w)+Z), packed along R."""
    from repro.core.quantize import pack_codes

    q = jnp.round(scale * w.astype(jnp.float32)) + zero
    q = jnp.clip(q, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1).astype(jnp.int8)
    return pack_codes(q, bits)


def flash_attention_ref(
    q: jax.Array,  # (BH, Sq, hd)
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    """Plain softmax attention oracle for the flash kernel."""
    hd = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    sq, sk = s.shape[1], s.shape[2]
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= (qp - kp) < window
    s = jnp.where(mask[None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)).astype(q.dtype)


def kmeans_assign_reduce_ref(
    x: jax.Array,          # (n,) values
    centroids: jax.Array,  # (k,)
) -> tuple[jax.Array, jax.Array]:
    """Per-cluster (sum, count) for one Lloyd update step."""
    d = jnp.abs(x[:, None].astype(jnp.float32) - centroids[None, :])
    ids = jnp.argmin(d, axis=1)
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(ids, k, dtype=jnp.float32)
    sums = onehot.T @ x.astype(jnp.float32)
    counts = onehot.sum(0)
    return sums, counts
