"""Fused SplitQuantV2 matmul — the paper's 3 layers in ONE kernel pass.

The paper deploys a split layer as 3 real low-bit layers (its §5 limitation:
3× matmuls, 3× activation reads). On TPU we fuse: for each (bm, bn, bk)
tile, all k packed planes are unpacked + dequantized + **summed in VMEM**,
then a single MXU matmul consumes the sum. Per tile this is 3 cheap VPU
unpack/dequant passes + 1 MXU matmul instead of 3 MXU matmuls + 3 HBM
activation streams.

Correctness relies on the split invariant (tested in test_split_equiv):
plane supports are disjoint and off-support entries dequantize to exactly
0.0, so the VMEM sum reconstructs Ŵ bit-exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_matmul import MATMUL_PARAMS, _unpack_tile


def _splitq_kernel(
    x_ref, planes_ref, s_ref, z_ref, o_ref, acc_ref, *, bits: int, nk: int, k: int
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = None
    for c in range(k):  # static unroll: k == 3
        q = _unpack_tile(planes_ref[c], bits).astype(jnp.float32)
        wc = (q - z_ref[c]) * s_ref[c]  # s_ref holds reciprocals
        w = wc if w is None else w + wc
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bits", "bm", "bn", "bk", "interpret")
)
def splitq_matmul_pallas(
    x: jax.Array,       # (M, K)
    planes: jax.Array,  # (k, K//per, N) int8 carriers
    scales: jax.Array,  # (k,)
    zeros: jax.Array,   # (k,)
    bits: int,
    bm: int = 128,
    bn: int = 512,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    per = 8 // bits
    kclusters = planes.shape[0]
    m, kdim = x.shape
    n = planes.shape[2]
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    assert planes.shape[1] * per == kdim
    nk = kdim // bk
    inv_s = (1.0 / scales).astype(jnp.float32)
    z = zeros.astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_splitq_kernel, bits=bits, nk=nk, k=kclusters),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec(
                (kclusters, bk // per, bn), lambda i, j, kk: (0, kk, j)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=None if interpret else MATMUL_PARAMS,
        interpret=interpret,
    )(x, planes, inv_s, z)
