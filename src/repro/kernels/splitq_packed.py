"""Beyond-paper (b+2)-bit packed SplitQuantV2 matmul kernel.

Storage: one b-bit code + one 2-bit cluster id per weight + a k-entry
(1/S, Z) LUT. For INT4 that is 6 bits/weight — **half** the paper's 3-plane
footprint (12 bits) and half its HBM weight traffic, with bit-identical
dequantized values. Decode-time matmuls are weight-bandwidth-bound, so this
directly converts the paper's §5 limitation into a ~2× bandwidth win.

In-kernel dequant: the 3-way LUT gather is realized as a chain of
vectorized selects (TPU has no VMEM gather; k is static and tiny, so
2 selects per element on the VPU beat any gather emulation).

Grouped projections: the same kernel serves a fused QKV (or gate+up)
launch. Members are concatenated along N with each member's span padded to
a multiple of the block width, so an output block j belongs to exactly one
member; ``group_starts`` (static, in units of bn) tells the kernel which
member's k LUT rows to use. Cluster ids stay 2 bits — grouping costs zero
extra weight bandwidth.

Pipelining: grid dims (M, N) are declared ``parallel`` and the K sweep
``arbitrary`` so Mosaic double-buffers the packed weight DMA against the
MXU work (weight HBM streaming is the decode bottleneck this kernel
exists to hide).

Layout: codes and cluster ids are both packed along K in whole slabs (see
``repro.core.quantize.pack_codes``), so the code tile (bk/per, bn) and the
id tile (bk/4, bn) of one output block unpack to the same (bk, bn) rows.
The LUTs sit in SMEM and are read as scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_matmul import MATMUL_PARAMS, _unpack_tile


def _lut_select(cid: jax.Array, lut_ref, k: int) -> jax.Array:
    """out[i] = lut[cid[i]] via select chain; cid int32, lut_ref (k,)."""
    out = jnp.full(cid.shape, lut_ref[0], jnp.float32)
    for c in range(1, k):
        out = jnp.where(cid == c, lut_ref[c], out)
    return out


def _lut_select_grouped(cid, g, lut_ref, k: int, groups: int) -> jax.Array:
    """out[i] = lut[g*k + cid[i]] with g a traced scalar member index.

    The member's k LUT entries are picked with (groups-1)*k SCALAR selects
    (register ops, once per tile); the per-element vector work stays at the
    same k-1 selects as the ungrouped path."""
    vals = []
    for c in range(k):
        v = lut_ref[c]
        for gg in range(1, groups):
            v = jnp.where(g == gg, lut_ref[gg * k + c], v)
        vals.append(v)
    out = jnp.full(cid.shape, vals[0], jnp.float32)
    for c in range(1, k):
        out = jnp.where(cid == c, vals[c], out)
    return out


def _splitq_packed_kernel(
    x_ref, codes_ref, cids_ref, s_ref, z_ref, o_ref, acc_ref,
    *, bits: int, nk: int, k: int, group_starts: tuple[int, ...],
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack_tile(codes_ref[...], bits).astype(jnp.float32)
    cid = _unpack_tile(cids_ref[...], 2) & 0x3  # int32, 2-bit ids unsigned
    if len(group_starts) <= 1:
        inv_s = _lut_select(cid, s_ref, k)
        z = _lut_select(cid, z_ref, k)
    else:
        j = pl.program_id(1)
        g = jnp.int32(0)
        for b in group_starts[1:]:
            g = g + (j >= b).astype(jnp.int32)
        inv_s = _lut_select_grouped(cid, g, s_ref, k, len(group_starts))
        z = _lut_select_grouped(cid, g, z_ref, k, len(group_starts))
    w = (q - z) * inv_s
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "bm", "bn", "bk", "group_starts", "interpret"),
)
def splitq_packed_matmul_pallas(
    x: jax.Array,      # (M, K)
    codes: jax.Array,  # (K//per, N) int8 carriers
    cids: jax.Array,   # (K//4, N) packed 2-bit ids
    scales: jax.Array, # (G*k,)  member-major LUT (G==1 for a single tensor)
    zeros: jax.Array,  # (G*k,)
    bits: int,
    bm: int = 128,
    bn: int = 512,
    bk: int = 128,
    group_starts: tuple[int, ...] = (),
    interpret: bool = False,
) -> jax.Array:
    per = 8 // bits
    groups = max(1, len(group_starts))
    k = scales.shape[0] // groups
    m, kdim = x.shape
    n = codes.shape[1]
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    assert codes.shape[0] * per == kdim and cids.shape[0] * 4 == kdim
    nk = kdim // bk
    inv_s = (1.0 / scales).astype(jnp.float32)
    z = zeros.astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _splitq_packed_kernel, bits=bits, nk=nk, k=k,
            group_starts=group_starts,
        ),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // per, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=None if interpret else MATMUL_PARAMS,
        interpret=interpret,
    )(x, codes, cids, inv_s, z)
