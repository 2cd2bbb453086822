"""Paged-attention decode Pallas kernel — block-table KV gather on TPU.

Decode attention over a PAGED KV cache: instead of indexing one contiguous
``(B, Smax, KV, hd)`` strip, each batch row follows its page-table row
through a shared pool of fixed-size pages. The kernel uses
``PrefetchScalarGridSpec``: the page table and per-row lengths are scalar-
prefetched so the K/V BlockSpec index maps can resolve ``logical page i of
row b`` -> physical page id BEFORE the body runs — K/V never need to be
gathered into a contiguous per-row strip in HBM (the XLA reference path
materialises exactly that gather). The grid still sweeps every logical
page slot per row, so fetch traffic is O(table width), not O(len): dead
slots re-fetch a clamped page and are masked in the body. Skipping them
(and multi-page blocks / double-buffered fetches) is the scheduled TPU
perf pass — see ROADMAP; this kernel is the reference-quality baseline.

Grid ``(B, NP)`` with the page dim innermost (sequential on TPU): one
step fetches one physical page with ALL its kv heads — a ``(page, KV,
hd)`` block whose minor dims are the pool's own, the only block shape the
TPU accepts when KV is below 8 sublanes or not a multiple of them. The
page is viewed as ``page*KV`` key rows, every query head ``(KV*G rows)``
scores against all of them, and a constant head mask keeps each query
head on its own kv head (GQA). The per-row output tile and the running
online-softmax stats live in VMEM scratch across the page sweep, exactly
like the flash kernel's Sk sweep.

Masks: positions ``>= len`` are dead, plus optional sliding-window and
chunked-attention masks on absolute positions (traced scalars, prefetched).
Fully-masked rows (``len == 0``) produce EXACT zeros — the same contract
as the reference softmax guard in ``models/attention.py``, not a uniform
average over garbage.

``paged_attention_reference`` is the pure-jnp oracle (gather + masked
softmax) used for CPU CI and the kernel-equivalence test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    pt_ref,    # (B, NP) scalar-prefetch: physical page ids
    len_ref,   # (B,)    scalar-prefetch: valid KV length per row
    meta_ref,  # (2,)    scalar-prefetch: [window, chunk] (0 => disabled)
    q_ref,     # (1, KV*G, hd)
    k_ref,     # (1, page, KV, hd) — physical page selected by index_map
    v_ref,     # (1, page, KV, hd)
    head_ref,  # (KV*G, page*KV) int32: 1 where query and key head match
    off_ref,   # (1, page*KV) int32: in-page position of each key row
    o_ref,     # (1, KV*G, hd)
    acc_ref, m_ref, l_ref,
    *, scale: float, page: int, n_pages: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _, _, kvh, hd = k_ref.shape
    q = q_ref[0].astype(jnp.float32)                                # (R, hd)
    k = k_ref[0].astype(jnp.float32).reshape(page * kvh, hd)        # (C, hd)
    v = v_ref[0].astype(jnp.float32).reshape(page * kvh, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                                       # (R, C)

    k_len = len_ref[b]
    q_pos = k_len - 1  # the decode token sits at the last valid position
    k_pos = i * page + off_ref[...]                                 # (1, C)
    mask = (head_ref[...] != 0) & (k_pos < k_len)
    w, c = meta_ref[0], meta_ref[1]
    # boolean and/or, not where: Mosaic has no select over i1 vectors
    mask &= (w <= 0) | ((q_pos - k_pos) < w)
    cs = jnp.maximum(c, 1)
    mask &= (c <= 0) | ((q_pos // cs) == (k_pos // cs))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                    # (R, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    # mask p explicitly: when every key so far is dead, m_cur == NEG_INF and
    # exp(s - m_cur) would be exp(0) == 1 per dead key — the classic
    # garbage-average bug for empty rows. Masked p keeps l at exactly 0.
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(i == n_pages - 1)
    def _done():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(
    q: jax.Array,           # (B, KV, G, hd)
    k_pages: jax.Array,     # (P, page, KV, hd)
    v_pages: jax.Array,     # (P, page, KV, hd)
    page_table: jax.Array,  # (B, NP) int32
    lengths: jax.Array,     # (B,) int32 valid KV length (post-write)
    window: jax.Array | int = 0,
    chunk: jax.Array | int = 0,
    interpret: bool = False,
) -> jax.Array:
    b, kvh, g, hd = q.shape
    p_total, page = k_pages.shape[0], k_pages.shape[1]
    n_pages = page_table.shape[1]
    rows, cols = kvh * g, page * kvh
    meta = jnp.stack([jnp.asarray(window, jnp.int32).reshape(()),
                      jnp.asarray(chunk, jnp.int32).reshape(())])
    # key row p*KV + h of a page is (position p, kv head h); query row
    # h*G + g belongs to kv head h (kv-major head split)
    key_head = jnp.arange(cols, dtype=jnp.int32) % kvh
    head = (jnp.arange(rows, dtype=jnp.int32)[:, None] // g
            == key_head[None, :]).astype(jnp.int32)
    off = (jnp.arange(cols, dtype=jnp.int32) // kvh)[None, :]

    def kv_map(bb, i, pt, ln, mt):
        # stale table entries past a row's live pages still index SOME real
        # page; their contributions are masked by len in the body
        return (jnp.clip(pt[bb, i], 0, p_total - 1), 0, 0, 0)

    def fixed(bb, i, pt, ln, mt):
        return (0, 0)

    def row_map(bb, i, pt, ln, mt):
        return (bb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, rows, hd), row_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
            pl.BlockSpec((1, page, kvh, hd), kv_map),
            pl.BlockSpec((rows, cols), fixed),
            pl.BlockSpec((1, cols), fixed),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), row_map),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=hd ** -0.5, page=page, n_pages=n_pages,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, hd), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lengths.astype(jnp.int32), meta,
        q.reshape(b, rows, hd), k_pages, v_pages, head, off,
    )
    return out.reshape(q.shape)


def paged_attention_reference(
    q: jax.Array,           # (B, KV, G, hd)
    k_pages: jax.Array,     # (P, page, KV, hd)
    v_pages: jax.Array,     # (P, page, KV, hd)
    page_table: jax.Array,  # (B, NP)
    lengths: jax.Array,     # (B,)
    window: jax.Array | int = 0,
    chunk: jax.Array | int = 0,
) -> jax.Array:
    """Pure-jnp oracle: logical gather + masked softmax (fp32)."""
    from repro.kvcache.paged import logical_view

    b, kvh, g, hd = q.shape
    page = k_pages.shape[1]
    n_pages = page_table.shape[1]
    # one source of truth for the page addressing math
    kl, vl = logical_view(jnp.stack([k_pages, v_pages]), page_table)
    s_log = n_pages * page
    k_pos = jnp.arange(s_log, dtype=jnp.int32)[None]          # (1, S_log)
    q_pos = (lengths.astype(jnp.int32) - 1)[:, None]          # (B, 1)
    mask = k_pos < lengths.astype(jnp.int32)[:, None]
    w = jnp.asarray(window)
    mask &= jnp.where(w > 0, (q_pos - k_pos) < w, True)
    c = jnp.asarray(chunk)
    mask &= jnp.where(c > 0, (q_pos // jnp.maximum(c, 1))
                      == (k_pos // jnp.maximum(c, 1)), True)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", q.astype(jnp.float32), kl.astype(jnp.float32)
    ) * (hd ** -0.5)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vl.astype(jnp.float32))
    out = jnp.where(l > 0, out / jnp.where(l > 0, l, 1.0), 0.0)
    return out.astype(q.dtype)
