"""Paged KV-cache layout math (device side).

Layout: one pool of ``P`` fixed-size pages per layer, every layer in one
array ``pages`` shaped ``(L, 2, P, page, KV, hd)`` (the 2 is K/V). A
request's logical token position ``t`` lives in logical page ``t // page``
at offset ``t % page``; the per-slot ``page_table`` row maps logical page
index -> physical page id, so the flat physical index within one layer's
K or V plane is::

    phys(b, t) = page_table[b, t // page] * page + t % page

Inside a step the whole pool is what moves: the layer scan carries it,
each layer scatters into its own planes (:func:`paged_write`) and reads
them (:func:`logical_view`, the paged-attention kernel) by layer index,
and the serving programs take it donated, so a step updates the pool in
place and never copies it.

Logical position == absolute token position, which is what keeps RoPE,
causal/sliding-window/chunked masks and the per-slot ``len`` contract
identical between the paged and contiguous cache layouts.

Pages may be SHARED read-only between slots (prefix sharing: several
page-table rows map different logical pages onto one physical page), but
writers require exclusive ownership (refcount 1 — see
``kvcache.allocator``): the scheduler copy-on-writes any shared page
before a slot scatters into it (``allocator.cow`` for the bookkeeping,
:func:`copy_page` for the device contents), so distinct slots never
scatter into the same physical page. Page-table entries beyond a slot's
allocated range may be stale/zero; reads clamp them and attention masks
positions ``>= len``, so stale pages are unreachable the same way stale
dense-cache rows are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to back ``n_tokens`` logical positions."""
    return -(-max(n_tokens, 0) // page_size)


def rewind(cache_len: jax.Array, mask: jax.Array,
           new_len: jax.Array) -> jax.Array:
    """Rewind per-slot fill lengths: rows selected by ``mask`` (B,) bool
    take ``new_len``; others keep theirs.

    This is how speculative decoding UN-WRITES rejected draft tokens: the
    verify forward scattered KV for all k+1 fed positions, and a rejection
    pulls ``len`` back to the accepted count — the rejected positions
    become unreachable (attention masks keys ``>= len``) and the next wave
    overwrites them, exactly the invariant that makes recycled slots and
    stale dense rows safe. No page changes hands: reservation math is
    untouched, and every rewound position sits in a page the slot
    exclusively owns (the scheduler's COW guard ran before the write), so
    nothing is leaked or double-written."""
    return jnp.where(mask, new_len, cache_len).astype(jnp.int32)


def restore_rows(cache: dict, snap: dict, mask: jax.Array,
                 keys: list[str]) -> dict:
    """Restore snapshot rows of recurrent cache leaves for the batch
    slots selected by ``mask`` (B,) bool.

    Recurrent state cannot be un-written by a length rewind (it has no
    positional axis to mask), so speculative rollback restores a
    pre-write snapshot instead — for the slots that absorbed rejected
    tokens only. All recurrent leaves are laid out ``(L, B, ...)``
    (``models.model._RECURRENT_KEYS``); that batch-on-axis-1 convention
    lives HERE, shared by the target verifier and the drafter. Snapshots
    are plain references (jax arrays are immutable), so this is one
    ``where`` per leaf, no copies held."""
    out = dict(cache)
    for key in keys:
        leaf = cache[key]
        sel = mask.reshape((1, -1) + (1,) * (leaf.ndim - 2))
        out[key] = jnp.where(sel, snap[key], leaf)
    return out


def copy_page(pool: jax.Array, src: int, dst: int) -> jax.Array:
    """Copy one physical page's contents onto another (copy-on-write).

    ``pool`` is any per-layer page pool laid out ``(L, 2, P, page, KV,
    hd)`` (``pages`` or the zamba2 ``shared_pages``) — the copy spans all
    layers and both K/V planes of the page in one device op. Runs on the
    admission path (off the jitted decode/prefill hot loop), so it is a
    plain functional update, not a fused kernel."""
    return pool.at[:, :, dst].set(pool[:, :, src])


def read_pages(pool: jax.Array, ids) -> jax.Array:
    """Gather the contents of physical pages ``ids`` from a per-layer
    page pool laid out ``(L, 2, P, page, ...)`` -> ``(L, 2, n, page, ...)``.

    This is the spill path (preempt-to-disk): the scheduler snapshots a
    victim's pages to the host store *before* freeing them, so a later
    re-admission can reload contents instead of replaying the sequence
    through prefill. Like :func:`copy_page` it runs on the admission path,
    off the jitted hot loop. Shared (refcount > 1) pages read fine — the
    snapshot is a copy, not a claim."""
    idx = jnp.asarray(ids, jnp.int32)
    return jnp.take(pool, idx, axis=2)


def write_pages(pool: jax.Array, ids, values) -> jax.Array:
    """Scatter page contents back into physical pages ``ids`` of a pool
    laid out ``(L, 2, P, page, ...)`` (inverse of :func:`read_pages`).

    Restore-side of the spill tier: the target pages must be exclusively
    owned by the restoring slot (the scheduler allocates FRESH pages for a
    restore and never maps them into the prefix index), so no shared page
    is ever overwritten."""
    idx = jnp.asarray(ids, jnp.int32)
    return pool.at[:, :, idx].set(jnp.asarray(values, pool.dtype))


def _plane_base(pages: jax.Array, layer) -> tuple[jax.Array, int]:
    """Flat row index of layer ``layer``'s K plane in ``pages`` viewed as
    ``(L*2*P*page, KV, hd)``, and the rows one plane spans (its V plane
    starts that many rows later)."""
    p_total, page = pages.shape[2], pages.shape[3]
    plane_n = p_total * page
    return jnp.asarray(layer, jnp.int32) * (2 * plane_n), plane_n


def paged_write(
    pages: jax.Array,       # (L, 2, P, page, KV, hd)
    layer: jax.Array | int,  # which layer's planes to write
    k: jax.Array,           # (B, S, KV, hd)
    v: jax.Array,           # (B, S, KV, hd)
    page_table: jax.Array,  # (B, NP) int32 physical page ids
    starts: jax.Array,      # (B,) logical write offset per row
    seq_lens: jax.Array | None = None,  # (B,) valid new tokens (None => S)
) -> jax.Array:
    """Scatter new K/V tokens into layer ``layer``'s physical page slots
    of the whole pool; returns the whole pool.

    Row ``b`` writes its first ``seq_lens[b]`` tokens at logical positions
    ``starts[b] + j``; invalid positions (frozen rows, right-padding,
    out-of-table) map to an out-of-bounds flat index and are DROPPED by the
    scatter — the paged equivalent of the dense path's per-row masked
    ``dynamic_update_slice``. O(B*S) work: one scatter addressed by layer,
    plane and flat slot, so no layer's pool is cut out or rebuilt and a
    pool carried through the layer scan is updated in place.
    """
    n_layers, _, _, page, kvh, hd = pages.shape
    b, s = k.shape[0], k.shape[1]
    np_max = page_table.shape[1]
    t = starts.astype(jnp.int32)[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    valid = t < np_max * page
    if seq_lens is not None:
        valid &= jnp.arange(s, dtype=jnp.int32)[None] < seq_lens.astype(jnp.int32)[:, None]
    logical = jnp.clip(t // page, 0, np_max - 1)
    phys_page = jnp.take_along_axis(page_table.astype(jnp.int32), logical, axis=1)
    base, plane_n = _plane_base(pages, layer)
    flat_n = n_layers * 2 * plane_n
    k_slot = base + phys_page * page + t % page
    # OOB => drop; the V plane's slots sit one plane past the K plane's
    idx = jnp.where(valid[None], jnp.stack([k_slot, k_slot + plane_n]),
                    flat_n).reshape(2 * b * s)
    new = jnp.stack([k, v]).astype(pages.dtype).reshape(2 * b * s, kvh, hd)
    flat = pages.reshape(flat_n, kvh, hd).at[idx].set(new, mode="drop")
    return flat.reshape(pages.shape)


def logical_view(
    pages: jax.Array,        # (L, 2, P, page, KV, hd)
    layer: jax.Array | int,
    page_table: jax.Array,   # (B, NP)
) -> tuple[jax.Array, jax.Array]:
    """Gather each row's logical K/V strip ``(B, NP*page, KV, hd)`` of
    layer ``layer``, straight from the whole pool (the layer is folded
    into the flat index, so no layer slice is materialised).

    This is the interpret-mode / XLA reference data path: the gathered
    strip feeds the exact same attention math as the contiguous cache
    (positions ``>= len`` are masked identically), so paged and dense
    decoding are bit-identical. On TPU the paged-attention kernel reads
    pages directly in VMEM instead of materialising this gather in HBM.
    """
    p_total, page = pages.shape[2], pages.shape[3]
    flat = pages.reshape(-1, *pages.shape[4:])
    pt = jnp.clip(page_table.astype(jnp.int32), 0, p_total - 1)
    phys = (pt[:, :, None] * page
            + jnp.arange(page, dtype=jnp.int32)[None, None, :])
    phys = phys.reshape(page_table.shape[0], -1)  # (B, NP*page)
    base, plane_n = _plane_base(pages, layer)
    return flat[base + phys], flat[base + plane_n + phys]
