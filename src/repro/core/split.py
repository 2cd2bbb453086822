"""The SplitQuantV2 transform (paper §3) — layer splitting by 1-D k-means.

Given a weight tensor ``W``, partition its scalar values into ``k=3``
contiguous clusters (lower / middle / upper) with 1-D k-means, and represent

    W = Σ_c  W ⊙ m_c            (m_c = membership mask of cluster c)

Each plane ``W ⊙ m_c`` is quantized *per-tensor* with its own (S, Z) over the
hull of the cluster's value range **extended to include 0**. The extension is
what makes the split exact under quantization: masked-out entries encode to
the zero-point ``Z_c`` (guaranteed in-range because 0 ∈ [β, α]) and therefore
dequantize to exactly 0.0 — planes never leak error into each other's
support. The dense middle cluster of a bell-shaped weight distribution gets a
range ~10–20× narrower than the full tensor, i.e. a ~10–20× larger scale
factor — the paper's resolution win.

Two storage formats:

* :class:`SplitQTensor` — the **paper-faithful** format: k full-shape packed
  int-b planes (model size k·b/32 of FP32 — the paper's "3/8 for INT4").
* :class:`PackedSplitQTensor` — **beyond-paper**: every element belongs to
  exactly one cluster, so store one b-bit code + a 2-bit cluster id
  (b+2 bits/weight, e.g. 6 bits for INT4 → 3/16 of FP32) plus a k-entry
  (S, Z) LUT. Bit-identical dequantized values, half the paper's footprint,
  directly addressing the paper's §5 limitation.

Everything is jit-safe; the transform runs under pjit on sharded weights.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kmeans
from repro.core.quantize import (
    QParams,
    QTensor,
    compute_qparams,
    dequantize,
    pack_codes,
    quantize,
    unpack_codes,
)


class SplitInfo(NamedTuple):
    """Clustering metadata for one tensor."""

    centroids: jax.Array  # (k,)
    boundaries: jax.Array  # (k-1,)
    counts: jax.Array  # (k,) cluster populations


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["planes", "scales", "zeros", "info"],
    meta_fields=["bits", "shape"],
)
@dataclasses.dataclass(frozen=True)
class SplitQTensor:
    """Paper-faithful storage: k packed planes, each full logical shape."""

    planes: jax.Array  # (k, ...packed shape) int8 carriers
    scales: jax.Array  # (k,) fp32
    zeros: jax.Array  # (k,) fp32
    info: SplitInfo
    bits: int
    shape: tuple[int, ...]

    @property
    def k(self) -> int:
        return self.planes.shape[0]

    def plane_qparams(self, c: int) -> QParams:
        return QParams(self.scales[c], self.zeros[c], self.bits)

    def dequantize(self) -> jax.Array:
        """Effective weight Ŵ = Σ_c dequant(plane_c)."""
        out = jnp.zeros(self.shape, jnp.float32)
        for c in range(self.k):
            q = unpack_codes(self.planes[c], self.bits, out_len=self.shape[-2])
            out = out + dequantize(q, self.plane_qparams(c))
        return out


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["codes", "cids", "scales", "zeros"],
    meta_fields=["bits", "shape"],
)
@dataclasses.dataclass(frozen=True)
class PackedSplitQTensor:
    """Beyond-paper storage: one b-bit code + 2-bit cluster id per element."""

    codes: jax.Array  # packed int-b codes, int8 carriers
    cids: jax.Array  # packed 2-bit cluster ids, int8 carriers
    scales: jax.Array  # (k,) fp32
    zeros: jax.Array  # (k,) fp32
    bits: int
    shape: tuple[int, ...]

    def dequantize(self) -> jax.Array:
        q = unpack_codes(self.codes, self.bits, out_len=self.shape[-2])
        q = q.astype(jnp.float32)
        cid = unpack_codes(self.cids, 2, out_len=self.shape[-2])
        cid = cid.astype(jnp.int32) & 0x3
        s = self.scales[cid]
        z = self.zeros[cid]
        return (q - z) / s


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["codes", "cids", "scales", "zeros"],
    meta_fields=["bits", "kclusters", "rows", "widths", "align"],
)
@dataclasses.dataclass(frozen=True)
class PackedSplitQGroup:
    """Several packed tensors sharing one K dim, concatenated along N.

    The serving engine fuses sibling projections (QKV; gate+up) into ONE
    kernel launch: members are quantized *independently* (bit-identical to
    their standalone PackedSplitQTensor form) and their packed codes/cids are
    concatenated along N, each member padded to a multiple of ``align`` so
    every (bn ≤ align) output block maps to exactly one member. The kernel
    selects the member's k-entry (1/S, Z) LUT rows by block index, keeping
    cluster ids at 2 bits — the 6-bit/weight footprint survives grouping.
    """

    codes: jax.Array   # (..., Kp//per, Np_tot) int8 carriers
    cids: jax.Array    # (..., Kp//4, Np_tot) packed 2-bit ids
    scales: jax.Array  # (..., G*k) fp32, member-major
    zeros: jax.Array   # (..., G*k) fp32
    bits: int
    kclusters: int
    rows: int                 # logical K shared by every member
    widths: tuple[int, ...]   # logical N of each member
    align: int                # member padding granularity along N

    @property
    def groups(self) -> int:
        return len(self.widths)

    def padded_widths(self) -> tuple[int, ...]:
        return tuple(-(-w // self.align) * self.align for w in self.widths)

    def dequantize(self) -> list[jax.Array]:
        """Per-member effective weights (the padding columns are dropped)."""
        q = unpack_codes(self.codes, self.bits, out_len=self.rows)
        q = q.astype(jnp.float32)
        cid = unpack_codes(self.cids, 2, out_len=self.rows)
        cid = cid.astype(jnp.int32) & 0x3
        out, off = [], 0
        for g, (w, pw) in enumerate(zip(self.widths, self.padded_widths())):
            qs = q[..., off:off + w]
            cs = cid[..., off:off + w]
            s = self.scales[..., g * self.kclusters:(g + 1) * self.kclusters]
            z = self.zeros[..., g * self.kclusters:(g + 1) * self.kclusters]
            sg = jnp.take_along_axis(
                jnp.broadcast_to(s[..., None, :], cs.shape[:-1] + s.shape[-1:]),
                cs, axis=-1,
            ) if s.ndim > 1 else s[cs]
            zg = jnp.take_along_axis(
                jnp.broadcast_to(z[..., None, :], cs.shape[:-1] + z.shape[-1:]),
                cs, axis=-1,
            ) if z.ndim > 1 else z[cs]
            out.append((qs - zg) / sg)
            off += pw
        return out


def group_packed(
    members: list[PackedSplitQTensor], align: int | None = None
) -> PackedSplitQGroup:
    """Concatenate independently-quantized packed tensors along N.

    Bit-exact: member codes/scales are reused untouched; only zero bytes are
    appended so each member's span is a multiple of ``align`` (the padded
    output columns are garbage and sliced off by the kernel wrapper).
    """
    bits = members[0].bits
    k = members[0].scales.shape[-1]
    rows = members[0].shape[-2]
    assert all(m.bits == bits and m.scales.shape[-1] == k
               and m.shape[-2] == rows for m in members)
    widths = tuple(m.shape[-1] for m in members)
    if align is None:
        align = 512 if all(w % 512 == 0 for w in widths) else 128
    lead = [(0, 0)] * (members[0].codes.ndim - 1)

    def padded(a, w):
        return jnp.pad(a, lead + [(0, -(-w // align) * align - w)])

    return PackedSplitQGroup(
        codes=jnp.concatenate(
            [padded(m.codes, w) for m, w in zip(members, widths)], axis=-1),
        cids=jnp.concatenate(
            [padded(m.cids, w) for m, w in zip(members, widths)], axis=-1),
        scales=jnp.concatenate([m.scales for m in members], axis=-1),
        zeros=jnp.concatenate([m.zeros for m in members], axis=-1),
        bits=bits, kclusters=k, rows=rows, widths=widths, align=align,
    )


def split_masks(w: jax.Array, k: int = 3, bins: int = kmeans.DEFAULT_BINS,
                iters: int = kmeans.DEFAULT_ITERS) -> tuple[jax.Array, SplitInfo]:
    """Cluster ids (int32, shape of w) + clustering metadata."""
    res = kmeans.kmeans1d(w, k=k, bins=bins, iters=iters)
    ids = kmeans.cluster_masks(w, res.boundaries)
    counts = jnp.bincount(ids.reshape(-1), length=k).astype(jnp.int32)
    return ids, SplitInfo(res.centroids, res.boundaries, counts)


def plane_qparams_from_ids(
    w: jax.Array, ids: jax.Array, k: int, bits: int
) -> tuple[jax.Array, jax.Array]:
    """Per-cluster (S, Z) over hull(cluster range ∪ {0}). Returns ((k,),(k,))."""
    wf = w.astype(jnp.float32)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    scales, zeros = [], []
    for c in range(k):
        sel = ids == c
        beta = jnp.min(jnp.where(sel, wf, big))
        alpha = jnp.max(jnp.where(sel, wf, -big))
        empty = ~jnp.any(sel)
        beta = jnp.where(empty, 0.0, beta)
        alpha = jnp.where(empty, 0.0, alpha)
        qp = compute_qparams(
            wf, bits, beta=jnp.minimum(beta, 0.0), alpha=jnp.maximum(alpha, 0.0)
        )
        scales.append(qp.scale)
        zeros.append(qp.zero)
    return jnp.stack(scales), jnp.stack(zeros)


@functools.partial(jax.jit, static_argnames=("bits", "k", "bins", "iters"))
def split_quantize(
    w: jax.Array,
    bits: int,
    k: int = 3,
    bins: int = kmeans.DEFAULT_BINS,
    iters: int = kmeans.DEFAULT_ITERS,
) -> SplitQTensor:
    """SplitQuantV2 on one tensor → paper-faithful k-plane storage."""
    ids, info = split_masks(w, k=k, bins=bins, iters=iters)
    scales, zeros = plane_qparams_from_ids(w, ids, k, bits)
    planes = []
    for c in range(k):
        qp = QParams(scales[c], zeros[c], bits)
        wc = jnp.where(ids == c, w.astype(jnp.float32), 0.0)
        planes.append(pack_codes(quantize(wc, qp), bits))
    return SplitQTensor(
        planes=jnp.stack(planes), scales=scales, zeros=zeros, bits=bits,
        shape=tuple(w.shape), info=info,
    )


@functools.partial(jax.jit, static_argnames=("bits", "k", "bins", "iters"))
def split_quantize_packed(
    w: jax.Array,
    bits: int,
    k: int = 3,
    bins: int = kmeans.DEFAULT_BINS,
    iters: int = kmeans.DEFAULT_ITERS,
) -> PackedSplitQTensor:
    """SplitQuantV2 → beyond-paper (b+2)-bit packed storage.

    Bit-identical dequantized values to :func:`split_quantize`: each element
    is encoded with its own cluster's (S, Z); the other planes' exact zeros
    are implicit rather than stored.
    """
    assert k <= 4, "cluster id is stored in 2 bits"
    ids, _ = split_masks(w, k=k, bins=bins, iters=iters)
    scales, zeros = plane_qparams_from_ids(w, ids, k, bits)
    s = scales[ids]
    z = zeros[ids]
    q = jnp.round(s * w.astype(jnp.float32)) + z
    q = jnp.clip(q, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1).astype(jnp.int8)
    codes = pack_codes(q, bits)
    cids = pack_codes(ids.astype(jnp.int8), 2)
    return PackedSplitQTensor(
        codes=codes, cids=cids, scales=scales, zeros=zeros, bits=bits,
        shape=tuple(w.shape),
    )


def split_fp(w: jax.Array, k: int = 3) -> tuple[jax.Array, SplitInfo]:
    """FP split only (no quantization): planes (k, *w.shape) with Σ = w exactly.

    This is the "preservation of functionality" object (paper §4.1)."""
    ids, info = split_masks(w, k=k)
    planes = jnp.stack(
        [jnp.where(ids == c, w, jnp.zeros_like(w)) for c in range(k)]
    )
    return planes, info


# ---------------------------------------------------------------------------
# Error metrics (benchmarks & tests)
# ---------------------------------------------------------------------------


def sqnr_db(w: jax.Array, w_hat: jax.Array) -> jax.Array:
    """Signal-to-quantization-noise ratio in dB."""
    sig = jnp.mean(jnp.square(w.astype(jnp.float32)))
    err = jnp.mean(jnp.square(w.astype(jnp.float32) - w_hat.astype(jnp.float32)))
    return 10.0 * jnp.log10(sig / jnp.maximum(err, 1e-30))


@functools.partial(jax.jit, static_argnames=("bits", "k"))
def split_error_stats(w: jax.Array, bits: int, k: int = 3) -> dict[str, jax.Array]:
    """Baseline per-tensor linear quant vs SplitQuantV2, on one tensor."""
    qp = compute_qparams(w, bits)
    base = dequantize(quantize(w, qp), qp)
    sq = split_quantize(w, bits, k=k)
    sp = sq.dequantize()
    return {
        "sqnr_base_db": sqnr_db(w, base),
        "sqnr_split_db": sqnr_db(w, sp),
        "mse_base": jnp.mean(jnp.square(w - base)),
        "mse_split": jnp.mean(jnp.square(w - sp)),
    }


@functools.partial(jax.jit, static_argnames=("bits", "k"))
def tensor_quant_stats(w: jax.Array, bits: int, k: int = 3) -> dict[str, jax.Array]:
    """Everything the per-layer quant report needs from ONE tensor.

    Extends :func:`split_error_stats` with the attribution signals that
    explain *why* a layer's SQNR looks the way it does: the fraction of
    values the baseline quantizer saturates (``clip_frac_base``), the
    population of the outer k-means clusters (``outlier_frac`` — the mass
    SplitQuantV2 peels off into their own planes), and the range-resolution
    win of the middle cluster vs the full tensor (``range_gain`` ≈ the
    paper's 10–20× scale-factor claim). Shares one clustering pass between
    the error metrics and the attribution stats."""
    wf = w.astype(jnp.float32)
    qp = compute_qparams(wf, bits)
    raw = jnp.round(qp.scale * wf) + qp.zero
    clip_frac = jnp.mean(((raw < qp.qmin) | (raw > qp.qmax)).astype(jnp.float32))
    base = dequantize(quantize(wf, qp), qp)

    ids, info = split_masks(wf, k=k)
    scales, zeros = plane_qparams_from_ids(wf, ids, k, bits)
    # packed-formula dequant (bit-identical to the k-plane sum)
    s = scales[ids]
    z = zeros[ids]
    q = jnp.clip(jnp.round(s * wf) + z, -(2 ** (bits - 1)), 2 ** (bits - 1) - 1)
    sp = (q - z) / s

    total = jnp.float32(wf.size)
    counts = info.counts.astype(jnp.float32)
    # k-means boundaries are sorted, so clusters 0 and k-1 hold the tails
    outlier_frac = (counts[0] + counts[-1]) / total
    full_span = jnp.max(wf) - jnp.min(wf)
    # middle cluster = densest; its (S) vs the full-tensor scale is the
    # per-weight resolution multiplier the split buys
    mid = jnp.argmax(counts)
    range_gain = scales[mid] / qp.scale
    return {
        "sqnr_base_db": sqnr_db(wf, base),
        "sqnr_split_db": sqnr_db(wf, sp),
        "mse_base": jnp.mean(jnp.square(wf - base)),
        "mse_split": jnp.mean(jnp.square(wf - sp)),
        "clip_frac_base": clip_frac,
        "outlier_frac": outlier_frac,
        "range_gain": range_gain,
        "cluster_counts": info.counts,
    }


def choose_k(w: jax.Array, bits: int, max_k: int = 3, min_gain_db: float = 3.0) -> int:
    """Dynamic per-layer k (paper §5 future work): smallest k whose marginal
    SQNR gain over k-1 exceeds ``min_gain_db``. Host-side helper (concrete)."""
    import numpy as np

    prev = None
    best = 1
    for k in range(1, max_k + 1):
        if k == 1:
            qp = compute_qparams(w, bits)
            w_hat = dequantize(quantize(w, qp), qp)
        else:
            w_hat = split_quantize(w, bits, k=k).dequantize()
        s = float(sqnr_db(w, w_hat))
        if prev is None or s - prev >= min_gain_db:
            best = k
            prev = s
        else:
            break
    return best
