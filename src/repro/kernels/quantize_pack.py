"""Fused quantize+pack elementwise Pallas kernel.

One pass over the weights computes codes = clip(round(S·w) + Z) and packs
them ``per`` per byte along the row axis in the slab layout of
``repro.core.quantize.pack_codes`` — HBM traffic is read-once/write-b/8,
instead of a quantize pass + a separate pack pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import PACK_SLAB


def _quantize_pack_kernel(w_ref, sz_ref, o_ref, *, bits: int, rows: int):
    per = 8 // bits
    br = w_ref.shape[0]
    q = jnp.round(sz_ref[0] * w_ref[...].astype(jnp.float32)) + sz_ref[1]
    q = jnp.clip(q, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1).astype(jnp.int32)
    # rows past the tensor are code 0, as pack_codes pads them
    row = pl.program_id(0) * br + jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
    q = jnp.where(row < rows, q, 0)
    if per > 1:
        slab = min(PACK_SLAB, br)
        sr = slab // per
        mask = (1 << bits) - 1
        slabs = []
        for s0 in range(0, br, slab):
            packed = q[s0:s0 + sr] & mask
            for i in range(1, per):
                lo = s0 + i * sr
                packed = packed | ((q[lo:lo + sr] & mask) << (i * bits))
            slabs.append(packed)
        q = jnp.concatenate(slabs, axis=0)
        q = jnp.where(q >= 128, q - 256, q)
    o_ref[...] = q.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("bits", "rows", "br", "bc", "interpret"))
def quantize_pack_pallas(
    w: jax.Array,      # (R, C), R a multiple of br
    scale: jax.Array,  # ()
    zero: jax.Array,   # ()
    bits: int,
    rows: int,         # logical rows; rows past it pack as code 0
    br: int = 256,
    bc: int = 512,
    interpret: bool = False,
) -> jax.Array:
    per = 8 // bits
    r, c = w.shape
    assert r % br == 0 and c % bc == 0 and br % per == 0
    sz = jnp.stack([scale.reshape(()), zero.reshape(())]).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_quantize_pack_kernel, bits=bits, rows=rows),
        grid=(r // br, c // bc),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((br // per, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r // per, c), jnp.int8),
        interpret=interpret,
    )(w, sz)
