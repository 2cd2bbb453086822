"""Public jit'd wrappers for the Pallas kernels.

Handles: padding to block multiples (zero-padding K on the activation side
is value-preserving; N/M padding is sliced off), backend dispatch
(:func:`interpret_mode`: compiled Pallas on a TPU, the interpreter on the
CPU, an error anywhere else), block-shape dispatch via the engine
autotuner when the caller passes ``block=None``, and pytree-level entry
points taking the core's SplitQTensor / PackedSplitQTensor /
PackedSplitQGroup containers directly.

On a serving mesh (exact-TP hints) every kernel call runs inside a
``shard_map``: GSPMD cannot partition a Mosaic kernel. The call's batch
rows split over the data axes when they divide; its weights, page pools
and everything else are gathered whole to each device, so each device
computes exact full-width results for its own rows.

``count_launches()`` is a tracing-time hook: wrappers bump a counter when a
quantized kernel is dispatched, so tests can assert launches-per-block of a
traced forward (e.g. grouped QKV + gate/up decode: 4 instead of 7).
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantize import PACK_SLAB, packed_rows
from repro.core.split import PackedSplitQGroup, PackedSplitQTensor, SplitQTensor
from repro.kernels import paged_attention as paged_mod
from repro.kernels import ref
from repro.kernels.kmeans1d import kmeans_assign_reduce_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.quantize_pack import quantize_pack_pallas
from repro.kernels.splitq_matmul import splitq_matmul_pallas
from repro.kernels.splitq_packed import splitq_packed_matmul_pallas
from repro.runtime.sharding import (dp_axes, mesh_dims, serving_mesh,
                                    sharding_hints)

DEFAULT_BLOCK = (128, 512, 128)

_counter = threading.local()


@contextlib.contextmanager
def count_launches():
    """Count quantized-kernel dispatches (per trace) by kind."""
    prev = getattr(_counter, "counts", None)
    _counter.counts = {}
    try:
        yield _counter.counts
    finally:
        _counter.counts = prev


def _bump(kind: str):
    c = getattr(_counter, "counts", None)
    if c is not None:
        c[kind] = c.get(kind, 0) + 1
        c["total"] = c.get("total", 0) + 1


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend.

    Compiled on a TPU, interpreted on the CPU (tests). Any other platform
    is an error: these are Mosaic TPU kernels, and interpreting them on an
    accelerator would hide the device behind a slow emulation."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {platform!r}")


def _on_mesh(fn, batched: tuple[int, ...], *args):
    """``fn(*args)``, inside a shard_map over the serving mesh if any.

    Arguments whose positions are in ``batched`` split their leading
    (batch) dim over the data axes when it divides; the rest are
    replicated. The body runs outside the hints, so the wrapper it calls
    does not open a second shard_map."""
    mesh = serving_mesh()
    if mesh is None:
        return fn(*args)
    n_dp, _ = mesh_dims(mesh)
    split = n_dp > 1 and args[0].shape[0] % n_dp == 0
    rows = P(dp_axes(mesh)) if split else P()

    def body(*a):
        with sharding_hints(None):
            return fn(*a)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(rows if i in batched else P()
                       for i in range(len(args))),
        out_specs=rows, check_vma=False,
    )(*args)


def _choose(m, k, n, bits, *, max_bn=None, bf16=False):
    from repro.engine.autotune import choose_block

    # every device runs the whole (m, k, n) matmul of its rows: on a mesh
    # the weights are gathered for the call (see _on_mesh)
    return choose_block(m, k, n, bits, max_bn=max_bn, bf16_acts=bf16)


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def quant_matmul(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    bits: int,
    *,
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """y = x @ dequant(W).  x: (..., K); w_packed: (K//per, N)."""
    return _on_mesh(
        functools.partial(_quant_matmul, bits=bits, block=block), (0,),
        x, w_packed, jnp.asarray(scale), jnp.asarray(zero))


def _quant_matmul(x, w_packed, scale, zero, *, bits, block):
    per = 8 // bits
    lead = x.shape[:-1]
    m = math.prod(lead)
    k = x.shape[-1]
    n = w_packed.shape[1]
    bm, bn, bk = block or _choose(m, k, n, bits, bf16=x.dtype == jnp.bfloat16)
    _bump("quant_matmul")
    x2 = _pad_to(x.reshape(m, k), (bm, bk))
    wp = _pad_to(w_packed, (bk // per, bn))
    y = quant_matmul_pallas(
        x2, wp, scale, zero, bits,
        bm=bm, bn=bn, bk=bk, interpret=interpret_mode(),
    )
    return y[:m, :n].reshape(*lead, n)


def splitq_matmul(
    x: jax.Array, sq: SplitQTensor, *,
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """Fused k-plane SplitQuantV2 matmul. x: (..., K); sq.shape == (K, N)."""
    return _on_mesh(functools.partial(_splitq_matmul, block=block), (0,),
                    x, sq)


def _splitq_matmul(x, sq, *, block):
    per = 8 // sq.bits
    lead = x.shape[:-1]
    m = math.prod(lead)
    k = x.shape[-1]
    n = sq.shape[-1]
    bm, bn, bk = block or _choose(m, k, n, sq.bits,
                                  bf16=x.dtype == jnp.bfloat16)
    _bump("splitq_matmul")
    x2 = _pad_to(x.reshape(m, k), (bm, bk))
    planes = _pad_to(sq.planes, (1, bk // per, bn))
    y = splitq_matmul_pallas(
        x2, planes, sq.scales, sq.zeros, sq.bits,
        bm=bm, bn=bn, bk=bk, interpret=interpret_mode(),
    )
    return y[:m, :n].reshape(*lead, n)


def splitq_packed_matmul(
    x: jax.Array,
    psq: PackedSplitQTensor,
    *,
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """6-bit packed SplitQuantV2 matmul. x: (..., K)."""
    return _on_mesh(functools.partial(_splitq_packed_matmul, block=block),
                    (0,), x, psq)


def _splitq_packed_matmul(x, psq, *, block):
    per = 8 // psq.bits
    lead = x.shape[:-1]
    m = math.prod(lead)
    k = x.shape[-1]
    n = psq.shape[-1]
    bm, bn, bk = block or _choose(m, k, n, psq.bits,
                                  bf16=x.dtype == jnp.bfloat16)
    _bump("splitq_packed_matmul")
    x2 = _pad_to(x.reshape(m, k), (bm, bk))
    codes = _pad_to(psq.codes, (bk // per, bn))
    cids = _pad_to(psq.cids, (bk // 4, bn))
    y = splitq_packed_matmul_pallas(
        x2, codes, cids, psq.scales, psq.zeros, psq.bits,
        bm=bm, bn=bn, bk=bk, interpret=interpret_mode(),
    )
    return y[:m, :n].reshape(*lead, n)


def splitq_packed_group_matmul(
    x: jax.Array,
    grp: PackedSplitQGroup,
    *,
    block: tuple[int, int, int] | None = None,
) -> list[jax.Array]:
    """ONE kernel launch for a fused projection group (QKV / gate+up).

    Returns the per-member outputs (padding columns sliced off). Activation
    x is read once instead of once per member — at decode this halves the
    activation HBM traffic of the attention + MLP input projections.
    """
    return _on_mesh(
        functools.partial(_splitq_packed_group_matmul, block=block), (0,),
        x, grp)


def _splitq_packed_group_matmul(x, grp, *, block):
    per = 8 // grp.bits
    lead = x.shape[:-1]
    m = math.prod(lead)
    k = x.shape[-1]
    padded = grp.padded_widths()
    n_tot = sum(padded)
    bm, bn, bk = block or _choose(
        m, k, n_tot, grp.bits, max_bn=grp.align,
        bf16=x.dtype == jnp.bfloat16,
    )
    bn = min(bn, grp.align)
    assert grp.align % bn == 0, (grp.align, bn)
    _bump("splitq_packed_group_matmul")
    x2 = _pad_to(x.reshape(m, k), (bm, bk))
    codes = _pad_to(grp.codes, (bk // per, n_tot))
    cids = _pad_to(grp.cids, (bk // 4, n_tot))
    starts, off = [], 0
    for pw in padded:
        starts.append(off // bn)
        off += pw
    y = splitq_packed_matmul_pallas(
        x2, codes, cids, grp.scales, grp.zeros, grp.bits,
        bm=bm, bn=bn, bk=bk, group_starts=tuple(starts),
        interpret=interpret_mode(),
    )
    out, off = [], 0
    for w, pw in zip(grp.widths, padded):
        out.append(y[:m, off:off + w].reshape(*lead, w))
        off += pw
    return out


def paged_attention(
    q: jax.Array,           # (B, KV, G, hd)
    k_pages: jax.Array,     # (P, page, KV, hd)
    v_pages: jax.Array,     # (P, page, KV, hd)
    page_table: jax.Array,  # (B, NP)
    lengths: jax.Array,     # (B,)
    window: jax.Array | int = 0,
    chunk: jax.Array | int = 0,
) -> jax.Array:
    """Paged decode attention (``kernels.paged_attention``) over a page
    pool; the pools are gathered whole on a serving mesh, so page ids stay
    global."""
    def run(q, k_pages, v_pages, page_table, lengths, window, chunk):
        return paged_mod.paged_attention_pallas(
            q, k_pages, v_pages, page_table, lengths, window=window,
            chunk=chunk, interpret=interpret_mode())

    return _on_mesh(run, (0, 3, 4), q, k_pages, v_pages, page_table,
                    lengths, jnp.asarray(window, jnp.int32),
                    jnp.asarray(chunk, jnp.int32))


def quantize_pack(
    w: jax.Array, scale: jax.Array, zero: jax.Array, bits: int,
    *, block: tuple[int, int] = (256, 512),
) -> jax.Array:
    """Fused quantize+pack. w: (R, C) -> (packed_rows(R)//per, C) int8,
    the layout of ``repro.core.quantize.pack_codes``."""
    per = 8 // bits
    r, c = w.shape
    rows = packed_rows(r)
    br, bc = block
    if rows <= PACK_SLAB:
        br = rows  # one slab: the tile is the whole tensor
    w2 = _pad_to(w, (br, bc))
    out = quantize_pack_pallas(
        w2, jnp.asarray(scale), jnp.asarray(zero), bits, rows=r,
        br=br, bc=bc, interpret=interpret_mode(),
    )
    return out[: rows // per, :c]


def kmeans_assign_reduce(
    x: jax.Array, centroids: jax.Array, *, block: tuple[int, int] = (256, 512)
) -> tuple[jax.Array, jax.Array]:
    """Per-cluster (sum, count) over all elements of x (any shape)."""
    br, bc = block
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    cols = bc
    rows = -(-n // cols)
    pad = rows * cols - n
    x2 = jnp.pad(flat, (0, pad)).reshape(rows, cols)
    mask = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)).reshape(rows, cols)
    x2 = _pad_to(x2, (br, bc))
    mask = _pad_to(mask, (br, bc))
    return kmeans_assign_reduce_pallas(
        x2, mask, centroids, k=centroids.shape[0],
        br=br, bc=bc, interpret=interpret_mode(),
    )


# Re-export oracles for test convenience.
oracle = ref
