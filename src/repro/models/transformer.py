"""Model assembly: decoder-only LMs (dense / MoE / SSM / hybrid / VLM) and
the Whisper-style encoder-decoder, all as ``lax.scan`` over stacked layer
params.

Scan-over-layers keeps the HLO O(1) in depth (critical for the 48-layer
full-scale dry-run compiles) and gives the checkpoint/remat boundary; layer
heterogeneity (gemma3 local:global, llama4 chunked:global, zamba2 shared
block cadence) is expressed as *data* scanned alongside the params
(per-layer window/chunk scalars, layer indices), so one compiled body
serves every layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.engine.qmm import kv_proj, qdot
from repro.models import ssm as ssm_mod
from repro.models.attention import attention_block, init_attention
from repro.models.layers import init_mlp, init_sinusoid, mlp, rms_norm
from repro.models.moe import init_moe, moe_block
from repro.runtime.sharding import act_constraint


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Parameter init (single layer -> vmapped stack)
# ---------------------------------------------------------------------------


def _init_block(rng, cfg):
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(rng, 4)
    if cfg.family == "ssm":  # rwkv6
        return {
            "norm1_scale": jnp.zeros((d,), dt),
            "tmix": ssm_mod.init_rwkv6(ks[0], cfg, dt),
            "norm2_scale": jnp.zeros((d,), dt),
        }
    if cfg.family == "hybrid":  # zamba2 mamba backbone
        return {
            "norm1_scale": jnp.zeros((d,), dt),
            "mamba": ssm_mod.init_mamba2(ks[0], cfg, dt),
        }
    p = {
        "norm1_scale": jnp.zeros((d,), dt),
        "attn": init_attention(ks[0], cfg, dt),
        "norm2_scale": jnp.zeros((d,), dt),
    }
    if cfg.moe is not None:
        p["moe"] = init_moe(ks[1], cfg, dt)
    else:
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.glu, dt)
    if cfg.encdec:
        p["norm_cross_scale"] = jnp.zeros((d,), dt)
        p["cross_attn"] = init_attention(ks[2], cfg, dt)
    return p


def _init_enc_block(rng, cfg):
    dt = _dtype(cfg)
    d = cfg.d_model
    k1, k2 = jax.random.split(rng)
    return {
        "norm1_scale": jnp.zeros((d,), dt),
        "attn": init_attention(k1, cfg, dt),
        "norm2_scale": jnp.zeros((d,), dt),
        "mlp": init_mlp(k2, d, cfg.d_ff, cfg.glu, dt),
    }


def init_params(rng, cfg) -> dict:
    dt = _dtype(cfg)
    d, v = cfg.d_model, cfg.vocab_size
    k_embed, k_layers, k_extra, k_head, k_enc = jax.random.split(rng, 5)
    params: dict = {
        "embed": {"table": jax.random.normal(k_embed, (v, d), dt) * d ** -0.5},
        "layers": jax.vmap(lambda k: _init_block(k, cfg))(
            jax.random.split(k_layers, cfg.n_layers)
        ),
        "final_norm_scale": jnp.zeros((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": jax.random.normal(k_head, (d, v), dt) * d ** -0.5
        }
    if cfg.shared_attn_every:  # zamba2 shared transformer block
        ka, km = jax.random.split(k_extra)
        params["shared_attn"] = {
            "norm1_scale": jnp.zeros((d,), dt),
            "attn": init_attention(ka, cfg, dt),
            "norm2_scale": jnp.zeros((d,), dt),
            "mlp": init_mlp(km, d, cfg.d_ff, cfg.glu, dt),
        }
    if cfg.encdec:
        params["enc"] = {
            "layers": jax.vmap(lambda k: _init_enc_block(k, cfg))(
                jax.random.split(k_enc, cfg.n_enc_layers)
            ),
            "final_norm_scale": jnp.zeros((d,), dt),
        }
    return params


# ---------------------------------------------------------------------------
# Layer metadata scanned alongside params
# ---------------------------------------------------------------------------


def layer_meta(cfg):
    kinds = cfg.layer_kinds()
    windows = jnp.array(
        [cfg.window if k == "local" else 0 for k in kinds], jnp.int32
    )
    chunks = jnp.array(
        [cfg.attn_chunk if k == "chunked" else 0 for k in kinds], jnp.int32
    )
    return windows, chunks


# ---------------------------------------------------------------------------
# Shared zamba2 block
# ---------------------------------------------------------------------------


def _shared_block(sp, cfg, x, pos, kv_slot=None, cache_len=None,
                  seq_lens=None, page_table=None, pages=None, slot=0):
    """The zamba2 shared block; its KV is the dense ``kv_slot`` or slot
    ``slot`` of the paged pool ``pages`` (the whole pool comes back)."""
    h, new_kv = attention_block(
        sp["attn"], cfg, rms_norm(x, sp["norm1_scale"], cfg.norm_eps), pos,
        kv_cache=kv_slot, kv_pages=pages, kv_layer=slot,
        page_table=page_table,
        cache_len=cache_len, seq_lens=seq_lens,
    )
    x = x + h
    x = x + mlp(sp["mlp"], rms_norm(x, sp["norm2_scale"], cfg.norm_eps),
                cfg.act, cfg.glu)
    return x, new_kv


# ---------------------------------------------------------------------------
# Decoder stack forward (training/prefill: cache optional; decode: S==1)
# ---------------------------------------------------------------------------


def decoder_forward(
    cfg,
    params,
    x: jax.Array,        # (B, S, D) embedded inputs
    pos: jax.Array,      # (B, S) or (B, S, 3)
    cache: dict | None = None,
    cross_kv: tuple | None = None,   # whisper decoder: (Ldec,B,Senc,KV,hd) x2
    remat: bool = False,
    remat_group: int = 0,
    seq_lens: jax.Array | None = None,  # (B,) valid new tokens per row
) -> tuple[jax.Array, dict | None, jax.Array]:
    """Returns (hidden (B,S,D), new_cache, aux_loss).

    ``cache["len"]`` is per-row ``(B,)``: each batch slot advances by its
    own ``seq_lens`` entry (default: the full input length S), so
    heterogeneous requests can share one cache without corrupting each
    other's positions. Rows with ``seq_lens == 0`` are frozen: no KV/state
    write, no length advance — the decode-time inactive-slot mask.

    Paged cache contract: when the cache carries ``pages`` (attention
    families) or ``shared_pages`` (zamba2 shared block) plus a
    ``page_table``, attention KV lives in a shared page pool addressed per
    row through the table; recurrent leaves (ssm/conv/wkv/shift) stay
    dense — only positional KV benefits from paging. The whole pool rides
    the scan CARRY, not the scanned inputs and outputs: each layer
    scatters into and reads its own planes by layer index, so XLA updates
    the loop state in place, and a caller that donates the cache gets its
    pool back in the same buffer. Every other cache leaf is scanned.
    """
    if not remat_group:
        remat_group = getattr(cfg, "remat_group", 1)
    windows, chunks = layer_meta(cfg)
    layers = params["layers"]
    n_layers = cfg.n_layers
    cache_len = cache["len"] if cache is not None else None
    page_table = cache.get("page_table") if cache is not None else None
    every = cfg.shared_attn_every

    def block(x, layer_params, window, chunk, layer_cache, layer_cross, idx,
              shared_kv, pages):
        aux = jnp.float32(0.0)
        new_cache = layer_cache
        if cfg.family == "ssm":
            h, c1 = ssm_mod.rwkv6_time_mix(
                layer_params["tmix"],
                cfg,
                rms_norm(x, layer_params["norm1_scale"], cfg.norm_eps),
                layer_cache,
                seq_lens=seq_lens,
            )
            x = x + h
            h, c2 = ssm_mod.rwkv6_channel_mix(
                layer_params["tmix"],
                cfg,
                rms_norm(x, layer_params["norm2_scale"], cfg.norm_eps),
                layer_cache,
                seq_lens=seq_lens,
            )
            x = x + h
            if layer_cache is not None:
                new_cache = {**c1, **c2}
        elif cfg.family == "hybrid":
            h, c1 = ssm_mod.mamba2_block(
                layer_params["mamba"],
                cfg,
                rms_norm(x, layer_params["norm1_scale"], cfg.norm_eps),
                layer_cache,
                seq_lens=seq_lens,
            )
            x = x + h
            if layer_cache is not None:
                new_cache = {**layer_cache, **c1}
            if every:
                slot = idx // every

                def apply_shared(operands):
                    xx, skv = operands
                    if skv is None:
                        y, _ = _shared_block(params["shared_attn"], cfg, xx, pos)
                        return y, skv
                    if shared_paged:
                        return _shared_block(
                            params["shared_attn"], cfg, xx, pos,
                            cache_len=cache_len, seq_lens=seq_lens,
                            page_table=page_table, pages=skv, slot=slot,
                        )
                    kv_slot = jax.lax.dynamic_index_in_dim(
                        skv, slot, keepdims=False
                    )
                    y, new_slot = _shared_block(
                        params["shared_attn"], cfg, xx, pos, kv_slot,
                        cache_len, seq_lens,
                    )
                    skv = jax.lax.dynamic_update_index_in_dim(
                        skv, new_slot.astype(skv.dtype), slot, 0
                    )
                    return y, skv

                def skip(operands):
                    return operands

                x, shared_kv = jax.lax.cond(
                    (idx + 1) % every == 0, apply_shared, skip, (x, shared_kv)
                )
        else:  # attention families
            kv = layer_cache.get("kv") if layer_cache is not None else None
            h, new_kv = attention_block(
                layer_params["attn"], cfg,
                rms_norm(x, layer_params["norm1_scale"], cfg.norm_eps), pos,
                layer_window=window, layer_chunk=chunk,
                kv_cache=kv, kv_pages=pages, kv_layer=idx,
                page_table=page_table, cache_len=cache_len, seq_lens=seq_lens,
            )
            x = x + h
            if layer_cross is not None:
                h, _ = attention_block(
                    layer_params["cross_attn"], cfg,
                    rms_norm(x, layer_params["norm_cross_scale"], cfg.norm_eps),
                    pos, cross_kv=layer_cross,
                )
                x = x + h
            h2 = rms_norm(x, layer_params["norm2_scale"], cfg.norm_eps)
            if cfg.moe is not None:
                h, aux = moe_block(layer_params["moe"], cfg, h2)
            else:
                h = mlp(layer_params["mlp"], h2, cfg.act, cfg.glu)
            x = x + h
            if kv is not None:
                new_cache = {"kv": new_kv}
            elif pages is not None:
                pages = new_kv
        return x, new_cache, aux, shared_kv, pages

    idxs = jnp.arange(n_layers, dtype=jnp.int32)
    per_layer_cache = None
    shared_kv0 = pages0 = None
    shared_paged = cache is not None and "shared_pages" in cache
    if cache is not None:
        per_layer_cache = {
            k: v for k, v in cache.items()
            if k not in ("len", "shared_kv", "shared_pages", "page_table",
                         "pages")
        }
        pages0 = cache.get("pages")
        shared_kv0 = (cache["shared_pages"] if shared_paged
                      else cache.get("shared_kv"))
    cross = None
    if cross_kv is not None:
        cross = cross_kv  # (k, v) each (L, B, Senc, KV, hd)

    g = remat_group if (remat and cache is None
                        and n_layers % max(remat_group, 1) == 0) else 1

    if g <= 1:
        blk = jax.checkpoint(block) if remat else block

        def body(carry, scanned):
            x, aux_tot, shared_kv, pages = carry
            layer_params, window, chunk, layer_cache, layer_cross, idx = scanned
            x, new_cache, aux, shared_kv, pages = blk(
                x, layer_params, window, chunk, layer_cache, layer_cross,
                idx, shared_kv, pages,
            )
            # SP: the scan-carried residual stream is the remat save point —
            # sequence-sharding it over `model` divides saved-activation
            # memory by the TP degree (no-op outside a mesh context).
            x = act_constraint(x, "residual")
            return (x, aux_tot + aux, shared_kv, pages), new_cache

        (x, aux_tot, shared_kv, pages), new_layer_cache = jax.lax.scan(
            body, (x, jnp.float32(0.0), shared_kv0, pages0),
            (layers, windows, chunks, per_layer_cache, cross, idxs),
        )
    else:
        # grouped activation checkpointing: save the residual every g
        # layers, recompute the inner g-1 in backward — stack memory /g
        # for ~(g-1)/g extra forward FLOPs in the backward pass.
        def rg(t):
            return jax.tree.map(
                lambda a: a.reshape(n_layers // g, g, *a.shape[1:]), t
            )

        def body(carry, scanned):
            x, aux_tot, shared_kv = carry
            lp, w, c, lcross, idx = scanned

            def group(x, shared_kv):
                aux_g = jnp.float32(0.0)
                for i in range(g):
                    lpi = jax.tree.map(lambda a: a[i], lp)
                    lci = None
                    if lcross is not None:
                        lci = jax.tree.map(lambda a: a[i], lcross)
                    x, _, aux, shared_kv, _ = block(
                        x, lpi, w[i], c[i], None, lci, idx[i], shared_kv,
                        None,
                    )
                return x, aux_g + aux, shared_kv

            x, aux_g, shared_kv = jax.checkpoint(group)(x, shared_kv)
            x = act_constraint(x, "residual")
            return (x, aux_tot + aux_g, shared_kv), None

        (x, aux_tot, shared_kv), new_layer_cache = jax.lax.scan(
            body, (x, jnp.float32(0.0), shared_kv0),
            (rg(layers), windows.reshape(-1, g), chunks.reshape(-1, g),
             rg(cross), idxs.reshape(-1, g)),
        )

    new_cache = None
    if cache is not None:
        new_cache = dict(new_layer_cache)
        if seq_lens is not None:
            inc = seq_lens.astype(jnp.int32)
        else:
            inc = pos.shape[1] if pos.ndim >= 2 else 1
        new_cache["len"] = cache["len"] + inc
        if pages0 is not None:
            new_cache["pages"] = pages
        if shared_kv is not None:
            new_cache["shared_pages" if shared_paged else "shared_kv"] = (
                shared_kv
            )
        if page_table is not None:
            new_cache["page_table"] = page_table
    return x, new_cache, aux_tot


# ---------------------------------------------------------------------------
# Whisper encoder
# ---------------------------------------------------------------------------


def encoder_forward(cfg, params, embeds: jax.Array, remat: bool = False):
    """embeds: (B, S_enc, D) stub frame embeddings -> (B, S_enc, D)."""
    b, s, d = embeds.shape
    x = embeds + init_sinusoid(s, d)[None].astype(embeds.dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    # Bidirectional attention needs causal=False; attention_block is causal
    # for self-attn, so encode via the cross-attention path against itself.
    def enc_block(x, lp):
        xn = rms_norm(x, lp["norm1_scale"], cfg.norm_eps)
        kvh, hd = cfg.n_kv_heads, cfg.hd
        k2, v2 = kv_proj(lp["attn"], xn)
        k = k2.reshape(b, s, kvh, hd)
        v = v2.reshape(b, s, kvh, hd)
        h, _ = attention_block(lp["attn"], cfg, xn, pos, cross_kv=(k, v))
        x = x + h
        x = x + mlp(lp["mlp"], rms_norm(x, lp["norm2_scale"], cfg.norm_eps),
                    cfg.act, cfg.glu)
        return x

    if remat:
        enc_block = jax.checkpoint(enc_block)

    def body(x, lp):
        return enc_block(x, lp), None

    x, _ = jax.lax.scan(body, x, params["enc"]["layers"])
    return rms_norm(x, params["enc"]["final_norm_scale"], cfg.norm_eps)


def build_cross_kv(cfg, params, enc_out: jax.Array):
    """Per-decoder-layer cross K/V from encoder output (cached at prefill)."""
    b, s, _ = enc_out.shape
    kvh, hd = cfg.n_kv_heads, cfg.hd

    def per_layer(lp):
        k2, v2 = kv_proj(lp["cross_attn"], enc_out)
        k = k2.reshape(b, s, kvh, hd)
        v = v2.reshape(b, s, kvh, hd)
        return k, v

    return jax.vmap(per_layer)(params["layers"])  # (L,B,S,KV,hd) x2


# ---------------------------------------------------------------------------
# Heads
# ---------------------------------------------------------------------------


def logits_fn(cfg, params, hidden: jax.Array) -> jax.Array:
    h = rms_norm(hidden, params["final_norm_scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = (h @ params["embed"]["table"].T).astype(jnp.float32)
    else:
        logits = qdot(h, params["lm_head"]["w"]).astype(jnp.float32)
    return act_constraint(logits, "logits")


def embed_tokens(cfg, params, tokens: jax.Array) -> jax.Array:
    """Embedding lookup as a chunked one-hot matmul.

    A plain gather from a vocab-sharded table makes GSPMD replicate the
    table (and, in backward, a full fp32 scatter buffer — 4×5.9 GiB at
    nemotron scale). one_hot @ table is a dot: vocab-sharded, reduce-
    scatter backward, no replication. Seq-chunked so the one-hot tile
    stays ~256 MB/device."""
    table = params["embed"]["table"]
    v, d = table.shape
    b, s = tokens.shape
    if s <= 8:  # decode: tiny one-hot, no chunking machinery
        oh = jax.nn.one_hot(tokens, v, dtype=table.dtype)
        return oh @ table
    ck = 512 if s % 512 == 0 else s
    nc = s // ck
    tks = tokens.reshape(b, nc, ck).swapaxes(0, 1)

    def body(_, t):
        oh = jax.nn.one_hot(t, v, dtype=table.dtype)
        return None, oh @ table

    _, chunks = jax.lax.scan(jax.checkpoint(body), None, tks)
    return chunks.swapaxes(0, 1).reshape(b, s, d)
