"""GQA attention: global / sliding-window / chunked masks, qk-norm, RoPE and
M-RoPE, KV caches, cross-attention, and a memory-bounded query-chunked
softmax path for long sequences.

Memory plan: training/prefill attention scans over query chunks of
``Q_CHUNK`` so live score tensors are (B, q_chunk, H, Sk) instead of
(B, Sq, H, Sk) — at prefill_32k production scale that is the difference
between 0.8 GB and 26 GB per chip. Decode (Sq == 1) takes the direct path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.qmm import q_proj, qdot, qkv_proj
from repro.models.layers import apply_mrope, apply_rope, rms_norm
from repro.runtime.sharding import act_constraint

import contextlib
import threading

Q_CHUNK = 1024
NEG_INF = -1e30

_FLASH = threading.local()


@contextlib.contextmanager
def flash_fusion(enabled: bool = True):
    """Mark the attention core as the hand-written flash kernel for the
    roofline (jax.named_scope 'fused_kernel' — see roofline/hlocost.py).
    Numerics are identical; only the HLO byte accounting changes, modeling
    kernels/flash_attention.py which the CPU backend cannot lower."""
    prev = getattr(_FLASH, "on", False)
    _FLASH.on = enabled
    try:
        yield
    finally:
        _FLASH.on = prev


def _flash_scope():
    if getattr(_FLASH, "on", False):
        return jax.named_scope("fused_kernel_flash_attn")
    return contextlib.nullcontext()


def _use_paged_kernel() -> bool:
    """Route paged decode through the Pallas kernel wherever it runs
    compiled (a TPU; other accelerators raise in ``interpret_mode``). On
    the CPU decode takes the XLA path; tests monkeypatch this to exercise
    the dispatch glue in interpret mode (CI would otherwise never trace
    it)."""
    from repro.kernels.ops import interpret_mode

    return not interpret_mode()


def init_attention(rng, cfg, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, kv * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, kv * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * (h * hd) ** -0.5,
    }
    if cfg.qk_norm:
        p["q_norm_scale"] = jnp.zeros((hd,), dtype)
        p["k_norm_scale"] = jnp.zeros((hd,), dtype)
    return p


def _mask(
    q_pos: jax.Array,  # (B, Sq)
    k_pos: jax.Array,  # (B, Sk)
    *,
    causal: bool,
    window: jax.Array | int,      # 0 => unlimited
    chunk: jax.Array | int,       # 0 => no chunking
    k_len: jax.Array | None,      # (B,) valid cache length (decode); None => all
) -> jax.Array:
    """Boolean (B, Sq, Sk) attention mask from absolute positions."""
    q = q_pos[:, :, None]
    k = k_pos[:, None, :]
    m = jnp.ones(q.shape[:2] + (k_pos.shape[-1],), bool)
    if causal:
        m &= k <= q
    w = jnp.asarray(window)
    m &= jnp.where(w > 0, (q - k) < w, True)
    c = jnp.asarray(chunk)
    m &= jnp.where(c > 0, (q // jnp.maximum(c, 1)) == (k // jnp.maximum(c, 1)), True)
    if k_len is not None:
        m &= k < k_len[:, None, None]
    return m


def _sdpa(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,  # (B, Sk, KV, hd)
    mask: jax.Array,  # (B, Sq, Sk)
) -> jax.Array:
    """GQA via explicit KV-head expansion.

    Fully-masked query rows (no valid key at all — an empty request slot)
    return EXACT zeros: softmax over an all-``NEG_INF`` row is uniform (the
    max subtraction turns every score into ``exp(0)``), which would silently
    average garbage keys. The explicit guard makes "attends nothing" mean
    "outputs nothing" instead of clamping in one fake key.

    Expanding K/V to H heads (instead of a (KV, G) split) keeps the score
    tensor shardable on the *head* dim even when KV doesn't divide the TP
    degree (kv=8 on a 16-wide model axis): with the (KV, G) formulation
    GSPMD contracts over the sharded head_dim and materializes UNSHARDED
    (B, KV, G, Sq, Sk) scores — 12.9 GB/device at nemotron prefill_32k.
    The expanded K/V is a broadcast XLA fuses into the matmul; the
    head-sharding constraint pins scores to P(batch, 'model', ...)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    any_valid = mask.any(axis=-1)  # (B, Sq)
    if g > 1 and sq == 1:
        # decode: grouped formulation — expanding K/V would re-materialize
        # the whole 32k cache x G per token (~600 GB/step at internlm2
        # scale). Scores are tiny at sq=1, so the (KV, G) split is free.
        qf = q.reshape(b, sq, kvh, g, hd).astype(jnp.float32)
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32)
        ) * (hd ** -0.5)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
        out = jnp.where(any_valid[:, :, None, None, None], out, 0.0)
        return out.reshape(b, sq, h, hd).astype(q.dtype)
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = act_constraint(q.astype(jnp.float32), "heads")
    kf = act_constraint(k.astype(jnp.float32), "heads")
    vf = act_constraint(v.astype(jnp.float32), "heads")
    scores = jnp.einsum("bqhd,bshd->bhqs", qf, kf) * (hd ** -0.5)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", w, vf)
    out = jnp.where(any_valid[:, :, None, None], out, 0.0)
    return out.astype(q.dtype)


def attend(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    causal: bool = True,
    window: jax.Array | int = 0,
    chunk: jax.Array | int = 0,
    k_len: jax.Array | None = None,
) -> jax.Array:
    """Query-chunked SDPA. Shapes as in :func:`_sdpa`."""
    sq = q.shape[1]
    if sq <= Q_CHUNK or sq % Q_CHUNK != 0:
        with _flash_scope():
            return _sdpa(q, k, v, _mask(q_pos, k_pos, causal=causal,
                                        window=window, chunk=chunk,
                                        k_len=k_len))

    n = sq // Q_CHUNK
    k = act_constraint(k, "heads")
    v = act_constraint(v, "heads")

    def body(_, qc):
        qi, qpi = qc
        with _flash_scope():
            m = _mask(qpi, k_pos, causal=causal, window=window, chunk=chunk,
                      k_len=k_len)
            out = _sdpa(qi, k, v, m)
        return None, out

    qs = q.reshape(q.shape[0], n, Q_CHUNK, *q.shape[2:])
    qs = act_constraint(qs, "heads5").swapaxes(0, 1)
    qps = q_pos.reshape(q_pos.shape[0], n, Q_CHUNK).swapaxes(0, 1)
    # remat per chunk: without it the scan saves EVERY chunk's score tensor
    # for backward — the full S^2 scores, exactly what chunking avoids
    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qs, qps))
    out = outs.swapaxes(0, 1).reshape(q.shape)
    return out


def attention_block(
    p: dict,
    cfg,
    x: jax.Array,            # (B, S, D)
    pos: jax.Array,          # (B, S) or (B, S, 3) for mrope
    *,
    layer_window: jax.Array | int = 0,
    layer_chunk: jax.Array | int = 0,
    kv_cache: jax.Array | None = None,   # (2, B, Smax, KV, hd)
    kv_pages: jax.Array | None = None,   # (L, 2, P, page, KV, hd) paged pool
    kv_layer: jax.Array | int = 0,       # this layer's index into kv_pages
    page_table: jax.Array | None = None,  # (B, NP) with kv_pages
    cache_len: jax.Array | None = None,  # (B,) per-row fill (scalar ok)
    seq_lens: jax.Array | None = None,   # (B,) valid new tokens per row
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Returns (output (B,S,D), updated kv_cache/kv_pages or None).

    Self-attention when ``cross_kv`` is None; cross-attention (no cache
    update, no RoPE on k) otherwise.

    Cache writes land at each row's own ``cache_len`` offset; when
    ``seq_lens`` is given, rows with ``seq_lens == 0`` are left untouched
    (no KV write, frozen valid length) and rows with ``seq_lens < S`` only
    expose their true prefix to attention — right-padded batched prefill
    and inactive-slot decode both reduce to this one contract.

    Paged layout (``kv_pages`` + ``page_table`` instead of ``kv_cache``):
    identical contract over a shared page pool — writes scatter into each
    row's physical pages and attention reads the row's logical view.
    ``kv_pages`` is every layer's pool and ``kv_layer`` this layer's index
    into it; the whole pool comes back, updated in place (no layer or K/V
    plane is cut out), so the layer scan can carry it. Since
    logical position == absolute position, RoPE and every mask are shared
    with the contiguous path. Decode on TPU dispatches to the Pallas
    paged-attention kernel; elsewhere (and for prefill) the logical gather
    feeds the exact same ``attend`` math as the dense path, so paged and
    contiguous decoding are bit-identical on CPU CI.

    Prefix sharing rides on the same contract: several page-table rows may
    alias one physical page read-only, and a fresh row's ``cache_len`` can
    start PAST its shared prefix — writes then begin at that offset (the
    scatter never touches the shared pages) while reads cover the full
    logical strip, positions below ``cache_len`` included. The scheduler
    guarantees every page written here has refcount 1 (copy-on-write
    happens host-side before the wave — see ``kvcache.prefix``).

    Speculative verification is the MULTI-TOKEN decode case of the paged
    branch: ``S = k + 1`` drafted tokens scatter at each row's
    ``cache_len`` offset and attend causally over the row's logical strip
    with ``k_len = cache_len + seq_lens`` — exactly a prefill chunk, which
    is why verify logits match sequential decoding position for position.
    The Pallas paged-decode kernel stays on the ``S == 1`` fast path
    (scalar-prefetch page lookups assume one query row); multi-token
    verify takes the gather path on every backend. Rejected drafts are
    un-written by REWINDING the row's length afterwards
    (``kvcache.rewind``) — the scattered KV past the rewound length is
    unreachable here (``k < k_len`` masks it) and the next wave's scatter
    overwrites it, so no wipe pass is ever needed.
    """
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    if cross_kv is not None:
        q = q_proj(p, x).reshape(b, s, h, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm_scale"], cfg.norm_eps)
        ck, cv = cross_kv
        out = attend(
            q, ck, cv,
            q_pos=jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
            k_pos=jnp.broadcast_to(jnp.arange(ck.shape[1])[None], (b, ck.shape[1])),
            causal=False,
        )
        return qdot(out.reshape(b, s, h * hd), p["wo"]), None

    # fused QKV: one quantized kernel launch (x read once) when grouped
    q2, k2, v2 = qkv_proj(p, x)
    q = q2.reshape(b, s, h, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm_scale"], cfg.norm_eps)
    k = k2.reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm_scale"], cfg.norm_eps)
    v = v2.reshape(b, s, kvh, hd)

    if pos.ndim == 3:  # M-RoPE
        q = apply_mrope(q, pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos, cfg.rope_theta, cfg.mrope_sections)
        pos1 = pos[..., 0]
    else:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        pos1 = pos

    if kv_cache is None and kv_pages is None:
        out = attend(q, k, v, pos1, pos1, causal=True,
                     window=layer_window, chunk=layer_chunk)
        new_cache = None
    elif kv_pages is not None:
        from repro.kvcache.paged import logical_view, paged_write

        starts = jnp.broadcast_to(
            jnp.atleast_1d(cache_len), (b,)
        ).astype(jnp.int32)
        new_cache = paged_write(kv_pages, kv_layer, k, v, page_table, starts,
                                seq_lens)
        inc = s if seq_lens is None else seq_lens.astype(jnp.int32)
        k_len = starts + inc
        if s == 1 and _use_paged_kernel():
            from repro.kernels.ops import paged_attention

            # kv-major head split: h = (kvh, g), matching _sdpa's grouped
            # decode reshape and the kernel's (B, KV, G, hd) layout
            qg = q[:, 0].reshape(b, kvh, h // kvh, hd)
            og = paged_attention(
                qg, new_cache, kv_layer, page_table, k_len,
                window=layer_window, chunk=layer_chunk,
            )
            out = og.reshape(b, 1, h, hd)
        else:
            kl, vl = logical_view(new_cache, kv_layer, page_table)
            s_log = kl.shape[1]
            k_pos = jnp.broadcast_to(jnp.arange(s_log)[None], (b, s_log))
            out = attend(
                q, kl.astype(q.dtype), vl.astype(q.dtype), pos1, k_pos,
                causal=True, window=layer_window, chunk=layer_chunk,
                k_len=k_len,
            )
    else:
        smax = kv_cache.shape[2]
        starts = jnp.broadcast_to(
            jnp.atleast_1d(cache_len), (b,)
        ).astype(jnp.int32)

        if seq_lens is None:

            def _write(row, new, s0):  # per-row offset into the cache
                return jax.lax.dynamic_update_slice(row, new, (s0, 0, 0))

            kc = jax.vmap(_write)(kv_cache[0], k.astype(kv_cache.dtype),
                                  starts)
            vc = jax.vmap(_write)(kv_cache[1], v.astype(kv_cache.dtype),
                                  starts)
            k_len = starts + s
        else:
            # per-position masked scatter, O(B*s) on the decode hot path:
            # frozen rows (seq_lens == 0), right-padding beyond each row's
            # length, and positions past the buffer all map to an
            # out-of-bounds index and are DROPPED. A dynamic_update_slice
            # of the padded tile would instead CLAMP its start when
            # ``starts + s > Smax`` (late chunked-prefill wave of a nearly
            # full row) and silently shift the tile onto live positions.
            t = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
            valid = (jnp.arange(s, dtype=jnp.int32)[None]
                     < seq_lens.astype(jnp.int32)[:, None]) & (t < smax)
            rows_idx = jnp.arange(b, dtype=jnp.int32)[:, None] * smax
            flat_t = jnp.where(valid, rows_idx + t, b * smax)  # OOB => drop
            idx = flat_t.reshape(b * s)
            kvh_, hd_ = kv_cache.shape[-2:]

            def _scatter(buf, new):
                flat = buf.reshape(b * smax, kvh_, hd_)
                flat = flat.at[idx].set(
                    new.astype(kv_cache.dtype).reshape(b * s, kvh_, hd_),
                    mode="drop",
                )
                return flat.reshape(b, smax, kvh_, hd_)

            kc = _scatter(kv_cache[0], k)
            vc = _scatter(kv_cache[1], v)
            k_len = starts + seq_lens.astype(jnp.int32)
        # fully-masked rows (k_len == 0) come out as exact zeros via the
        # _sdpa guard — no clamp-in-one-garbage-key fallback needed
        new_cache = jnp.stack([kc, vc])
        k_pos = jnp.broadcast_to(jnp.arange(smax)[None], (b, smax))
        out = attend(
            q, kc.astype(q.dtype), vc.astype(q.dtype), pos1, k_pos,
            causal=True, window=layer_window, chunk=layer_chunk, k_len=k_len,
        )
    return qdot(out.reshape(b, s, h * hd), p["wo"]), new_cache
