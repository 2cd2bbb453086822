"""Basic linear (affine) quantization — the paper's eqs. (1)-(3).

    Q(x) = INT(S·x) + Z,   S = (2^b − 1)/(α − β),   Z = −2^{b−1} − INT(S·β)
    dequant(q) = (q − Z) / S

This is deliberately the *de-facto-standard* scheme the paper targets: the
whole point of SplitQuantV2 is that after its preprocessing, this basic
scheme matches advanced GPU-hungry algorithms. We implement:

* per-tensor / per-channel / per-group granularity (per-tensor is what edge
  frameworks give you and what the paper evaluates; the others exist for the
  ablation "is SplitQuantV2 ≈ group quant without framework support?"),
* symmetric ranges optionally (``symmetric=True``) for kernels that want
  zero-point-free matmuls,
* ``include_zero`` range extension — required by the split transform so that
  masked-out weights encode exactly to the zero-point (see core/split.py),
* int4/int2 bit-packing into int8 carriers for real deployment storage
  (kernels unpack in VMEM); codes pack along the contraction axis (-2), so
  quantized tensors are at least 2-D.

All ops are pure jnp and jit-safe; scalars stay in fp32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

INT_DTYPE = jnp.int8  # carrier for all b <= 8


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["scale", "zero"],
    meta_fields=["bits"],
)
@dataclasses.dataclass(frozen=True)
class QParams:
    """Affine quantization parameters. scale/zero broadcast against q.

    ``bits`` is static pytree metadata (it controls code paths, so it must
    never become a tracer)."""

    scale: jax.Array  # S, fp32
    zero: jax.Array  # Z, fp32 (integral values; kept float for arithmetic)
    bits: int

    @property
    def qmin(self) -> int:
        return -(2 ** (self.bits - 1))

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1


def _minmax(x: jax.Array, axes, keepdims: bool) -> tuple[jax.Array, jax.Array]:
    return (
        jnp.min(x, axis=axes, keepdims=keepdims),
        jnp.max(x, axis=axes, keepdims=keepdims),
    )


def compute_qparams(
    x: jax.Array,
    bits: int,
    *,
    channel_axis: int | None = None,
    group_size: int | None = None,
    symmetric: bool = False,
    include_zero: bool = False,
    beta: jax.Array | None = None,
    alpha: jax.Array | None = None,
) -> QParams:
    """Derive (S, Z) from data range (or an explicit [beta, alpha] range).

    channel_axis: per-channel granularity — one (S, Z) per index of that axis.
    group_size:   per-group along the *last* axis (reshape-based).
    include_zero: extend the range hull to contain 0.0.
    """
    xf = x.astype(jnp.float32)
    if beta is None or alpha is None:
        if group_size is not None:
            assert channel_axis is None, "group and channel are exclusive"
            g = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // group_size, group_size))
            beta, alpha = _minmax(g, -1, True)
            beta = jnp.repeat(beta, group_size, axis=-1).reshape(xf.shape)
            alpha = jnp.repeat(alpha, group_size, axis=-1).reshape(xf.shape)
        elif channel_axis is not None:
            axes = tuple(i for i in range(xf.ndim) if i != channel_axis % xf.ndim)
            beta, alpha = _minmax(xf, axes, True)
        else:
            beta, alpha = _minmax(xf, None, False)
    beta = jnp.asarray(beta, jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32)
    if symmetric:
        m = jnp.maximum(jnp.abs(beta), jnp.abs(alpha))
        beta, alpha = -m, m
    if include_zero:
        beta = jnp.minimum(beta, 0.0)
        alpha = jnp.maximum(alpha, 0.0)
    span = jnp.maximum(alpha - beta, 1e-12)
    scale = (2.0**bits - 1.0) / span  # eq. (2)
    zero = -(2.0 ** (bits - 1)) - jnp.round(scale * beta)  # eq. (3)
    return QParams(scale=scale, zero=zero, bits=bits)


def quantize(x: jax.Array, qp: QParams) -> jax.Array:
    """eq. (1) with saturation to the signed b-bit range. Returns int8 codes."""
    q = jnp.round(qp.scale * x.astype(jnp.float32)) + qp.zero
    return jnp.clip(q, qp.qmin, qp.qmax).astype(INT_DTYPE)


def dequantize(q: jax.Array, qp: QParams) -> jax.Array:
    return (q.astype(jnp.float32) - qp.zero) / qp.scale


def fake_quant(
    x: jax.Array,
    bits: int,
    **kw,
) -> tuple[jax.Array, QParams]:
    """quantize → dequantize round-trip (what accuracy eval measures)."""
    qp = compute_qparams(x, bits, **kw)
    return dequantize(quantize(x, qp), qp), qp


# ---------------------------------------------------------------------------
# Bit packing (int4 / int2 codes into int8 carriers, slab-striped rows).
#
# Codes pack along the CONTRACTION axis (-2; rows of a (K, N) weight) so the
# output axis N stays unpacked: a kernel tile of any lane-aligned width, a
# tensor-parallel shard of N, and the N-concatenation of grouped projections
# are all plain slices of the packed array. Rows go in slabs of
# ``PACK_SLAB``; within a slab of S rows, byte row r holds, in its i-th
# b-bit field (little end first), logical row i*(S/per) + r. Unpacking a
# slab is therefore ``per`` shifts of the whole carrier tile stacked along
# rows — whole (8, 128) tiles on a TPU, no lane or sublane interleave.
# ---------------------------------------------------------------------------

PACK_SLAB = 128


def packed_rows(n: int) -> int:
    """Logical rows a packed tensor of ``n`` rows stores.

    Padded to a multiple of 4 (every carrier byte full for 2- and 4-bit
    codes) and, past one slab, to whole slabs: the slab a row lands in,
    and so the layout, never depends on how a kernel tiles the rows."""
    n4 = -(-max(n, 1) // 4) * 4
    return n4 if n4 <= PACK_SLAB else -(-n // PACK_SLAB) * PACK_SLAB


def pack_codes(q: jax.Array, bits: int) -> jax.Array:
    """Pack signed b-bit codes (stored in int8) along axis -2.

    Rows are zero-padded to :func:`packed_rows`; bits=8 stores them as is,
    bits=4 packs 2 rows per byte, bits=2 packs 4.
    """
    n = q.shape[-2]
    rows = packed_rows(n)
    if rows != n:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 2) + [(0, rows - n), (0, 0)])
    if bits == 8:
        return q.astype(INT_DTYPE)
    per = 8 // bits
    slab = min(PACK_SLAB, rows)
    lead, cols = q.shape[:-2], q.shape[-1]
    u = (q.astype(jnp.int32) & ((1 << bits) - 1)).reshape(
        lead + (rows // slab, per, slab // per, cols))
    packed = u[..., 0, :, :]
    for i in range(1, per):
        packed = packed | (u[..., i, :, :] << (i * bits))
    packed = jnp.where(packed >= 128, packed - 256, packed)
    return packed.reshape(lead + (rows // per, cols)).astype(INT_DTYPE)


def unpack_codes(p: jax.Array, bits: int, out_len: int | None = None) -> jax.Array:
    """Inverse of :func:`pack_codes`: sign-extended int8 codes, rows cut to
    ``out_len`` when given."""
    out = p
    if bits != 8:
        per = 8 // bits
        rows = p.shape[-2] * per
        slab = min(PACK_SLAB, rows)
        lead, cols = p.shape[:-2], p.shape[-1]
        x = p.astype(jnp.int32).reshape(
            lead + (rows // slab, 1, slab // per, cols))
        left = (32 - bits - bits * jnp.arange(per, dtype=jnp.int32))
        # field i to the top of the word, arithmetic shift back: sign-extends
        v = (x << left[:, None, None]) >> (32 - bits)
        out = v.reshape(lead + (rows, cols)).astype(INT_DTYPE)
    if out_len is not None:
        out = out[..., :out_len, :]
    return out


# ---------------------------------------------------------------------------
# Whole-tensor convenience (used by the baseline quantizer and benchmarks).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["packed", "qp"],
    meta_fields=["shape"],
)
@dataclasses.dataclass(frozen=True)
class QTensor:
    """A quantized tensor: packed codes + params + logical shape (static)."""

    packed: jax.Array
    qp: QParams
    shape: tuple[int, ...]

    def dequantize(self) -> jax.Array:
        q = unpack_codes(self.packed, self.qp.bits, out_len=self.shape[-2])
        return dequantize(q, self.qp)


@functools.partial(jax.jit, static_argnames=("bits", "symmetric", "include_zero"))
def quantize_tensor(
    x: jax.Array, bits: int, symmetric: bool = False, include_zero: bool = False
) -> QTensor:
    """Per-tensor quantize + pack (the paper's deployment storage format)."""
    qp = compute_qparams(x, bits, symmetric=symmetric, include_zero=include_zero)
    return QTensor(packed=pack_codes(quantize(x, qp), bits), qp=qp,
                   shape=x.shape)
