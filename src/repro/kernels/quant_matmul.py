"""W4A16/W8A16/W2A16 dequant-in-VMEM matmul Pallas kernel.

The baseline deployment kernel for linearly-quantized weights: packed int-b
codes are streamed HBM→VMEM, unpacked + dequantized tile-by-tile in VMEM,
and fed to the MXU as fp32 with an fp32 VMEM accumulator.

Tiling: grid (M/bm, N/bn, K/bk), K innermost (sequential on TPU) so the
(bm, bn) accumulator lives in a VMEM scratch across the K sweep. The packed
weight tile is (bk/per, bn) int8; bk covers whole packing slabs (see
``repro.core.quantize.pack_codes``), so a tile unpacks on its own.

Weight layout: codes packed along the K (row) axis in slabs of
``PACK_SLAB`` rows — within a slab of S rows, byte row r holds logical row
i*(S/per) + r in its i-th b-bit field. Unpacking is int32 shifts of the
whole tile, stacked along rows: no uint8 shifts and no lane interleave,
which Mosaic rejects or pads to full lanes in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import PACK_SLAB

# (M, N) parallel + K arbitrary => Mosaic double-buffers the packed weight
# DMA against the MXU sweep (decode is weight-bandwidth-bound).
MATMUL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def _unpack_tile(packed: jax.Array, bits: int) -> jax.Array:
    """(r, c) int8 carriers -> (r*per, c) int32 sign-extended codes.

    ``r*per`` is either a multiple of ``PACK_SLAB`` or the tensor's whole
    (single-slab) row count, so the tile's slabs are the tensor's slabs."""
    x = packed.astype(jnp.int32)
    if bits == 8:
        return x
    per = 8 // bits
    slab_rows = min(PACK_SLAB, x.shape[0] * per) // per
    parts = []
    for s in range(0, x.shape[0], slab_rows):
        xs = x[s:s + slab_rows]
        for i in range(per):
            # field i to the top of the word, arithmetic shift back down
            parts.append((xs << (32 - bits * (i + 1))) >> (32 - bits))
    return jnp.concatenate(parts, axis=0)


def _quant_matmul_kernel(
    x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits: int, nk: int
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack_tile(w_ref[...], bits).astype(jnp.float32)
    inv_s = s_ref[0]  # reciprocal scale, precomputed host-side
    z = s_ref[1]
    w = (q - z) * inv_s
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w,
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "bm", "bn", "bk", "interpret"),
)
def quant_matmul_pallas(
    x: jax.Array,        # (M, K)
    w_packed: jax.Array, # (K//per, N) int8 carriers
    scale: jax.Array,    # () per-tensor
    zero: jax.Array,     # ()
    bits: int,
    bm: int = 128,
    bn: int = 512,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Caller must pre-pad M/N/K to block multiples (see ops.quant_matmul)."""
    per = 8 // bits
    m, k = x.shape
    n = w_packed.shape[1]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert w_packed.shape[0] * per == k, (w_packed.shape, k, bits)
    nk = k // bk
    # [1/S, Z] as SMEM scalars: the kernel reads them as scalars per tile
    sz = jnp.stack([1.0 / scale.reshape(()), zero.reshape(())]).astype(
        jnp.float32)
    return pl.pallas_call(
        functools.partial(_quant_matmul_kernel, bits=bits, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // per, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=None if interpret else MATMUL_PARAMS,
        interpret=interpret,
    )(x, w_packed, sz)
