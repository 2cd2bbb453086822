"""Paged KV-cache subsystem: paged == contiguous, token for token.

The contract under test (the paged-serving tentpole):
* every request served through a PAGED ``BatchedServer`` — shuffled
  physical pages, shared pool, per-request reservations — produces
  token-for-token the same output as a fresh isolated single-request
  decode on a contiguous cache (attention and hybrid cache families),
* chunked prefill (prompt fed in page-sized waves) produces identical
  tokens to whole-prompt prefill while interleaving decode steps for
  ongoing requests between waves,
* the Pallas paged-attention kernel (interpret mode) matches the pure-jnp
  reference, including sliding-window / chunked masks and page-table
  indirection,
* fully-masked rows (``len == 0``) produce EXACT zeros from attention —
  the regression for the old ``k_len = max(k_len, 1)`` clamp that silently
  attended one garbage key,
* the pool of every layer is read and written by layer index (kernel and
  scatter touch their own layer only), and a server whose decode and
  prefill programs take the cache donated hands the pool back in place
  with its streams unchanged.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_attention_reference,
)
from repro.kvcache import pages_for, paged_write
from repro.launch.serve import BatchedServer, Request
from repro.models import build_model
from repro.models.attention import attention_block, init_attention


def _tiny_model(arch="llama32-1b", n_layers=2, seed=0):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _isolated_decode(model, params, prompt: np.ndarray, gen: int,
                     max_len: int) -> list[int]:
    """Greedy decode of one request alone in a fresh contiguous cache."""
    cache = model.init_cache(1, max_len)
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, cache
    )
    out = [int(jnp.argmax(logits[0, -1]))]
    while len(out) < gen:
        logits, cache = model.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache
        )
        out.append(int(jnp.argmax(logits[0, 0])))
    return out


def _requests(cfg, lens, gen, seed0=100):
    return [
        Request(i, np.random.default_rng(seed0 + i).integers(
            0, cfg.vocab_size, ln, dtype=np.int32), gen)
        for i, ln in enumerate(lens)
    ]


# ---------------------------------------------------------------------------
# Paged serving == contiguous serving == isolated decode
# ---------------------------------------------------------------------------


def test_paged_slot_swap_matches_isolated():
    """Acceptance: heterogeneous prompts (incl. exact page multiples and
    generations crossing page boundaries) through a paged server with a
    pool SMALLER than slots x max_len — every request token-for-token
    equals its isolated contiguous decode."""
    cfg, model, params = _tiny_model()
    gen, max_len, page = 3, 48, 8
    lens = [4, 16, 23, 8, 17, 9]  # 8 = exact page; 23+2 crosses a boundary
    reqs = _requests(cfg, lens, gen)
    server = BatchedServer(model, params, batch_slots=2, max_len=max_len,
                           paged=True, page_size=page, num_pages=8)
    assert server.num_pages < 2 * (max_len // page), "pool must undercut dense"
    stats = server.run(reqs)
    assert stats["requests"] == len(lens)
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, len(r.prompt), r.out, want)
    assert stats["decode_compiles"] == 1, stats
    assert stats["pages"]["leaked"] == 0, stats
    assert stats["pages"]["peak_in_use"] <= 8, stats
    # per-request reservation is by need, not by global max_len
    assert stats["kv_bytes_reserved_per_request"]["max"] < (
        server._page_bytes * (max_len // page)
    ), stats


@pytest.mark.parametrize("arch", ["zamba2-1.2b"])
def test_paged_slot_swap_hybrid_family(arch):
    """Hybrid (mamba2 + shared attention): only the shared-attention KV is
    paged; recurrent ssm/conv rows stay dense. Slot swaps must still match
    isolated decoding exactly."""
    cfg, model, params = _tiny_model(arch, n_layers=4, seed=1)
    gen, max_len = 3, 32
    reqs = _requests(cfg, [4, 9, 5], gen)
    server = BatchedServer(model, params, batch_slots=2, max_len=max_len,
                           paged=True, page_size=4, num_pages=10)
    stats = server.run(reqs)
    assert stats["requests"] == 3
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (arch, r.rid, r.out, want)
    assert stats["pages"]["leaked"] == 0, stats
    assert stats["decode_compiles"] == 1, stats


def test_paged_composes_with_packed_engine():
    """The paged gather/scatter must compose with the packed quantized
    kernel path (fused QKV/gate+up launches feed the paged writes)."""
    from repro.core import QuantPolicy, restructure

    cfg, model, params = _tiny_model()
    qm = restructure(params, QuantPolicy(bits=4, packed=True))
    ex = qm.as_executable(group=True)
    gen, max_len = 3, 32
    reqs = _requests(cfg, [4, 11, 6], gen)
    server = BatchedServer(model, ex, batch_slots=2, max_len=max_len,
                           paged=True, page_size=8, num_pages=6,
                           prefill_chunk=8)
    stats = server.run(reqs)
    assert stats["requests"] == 3
    for r in reqs:
        want = _isolated_decode(model, ex, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, r.out, want)
    assert stats["pages"]["leaked"] == 0
    assert stats["decode_compiles"] == 1


def test_paged_pool_backpressure_defers_admission():
    """When the free-page budget can't host another request, admission
    waits for a retirement instead of failing — and every request still
    completes correctly."""
    cfg, model, params = _tiny_model()
    gen, page = 2, 4
    lens = [14, 13, 12, 5]
    reqs = _requests(cfg, lens, gen)
    # each request needs ceil((len+1)/4) pages: 4,4,4,2 — pool of 6 forces
    # strictly serial admission even though 2 slots are free
    server = BatchedServer(model, params, batch_slots=2, max_len=24,
                           paged=True, page_size=page, num_pages=6)
    stats = server.run(reqs)
    assert stats["requests"] == len(lens)
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, 24)
        assert r.out == want, (r.rid, r.out, want)
    assert stats["pages"]["leaked"] == 0
    assert stats["pages"]["peak_in_use"] <= 6


def test_paged_request_larger_than_pool_rejected():
    cfg, model, params = _tiny_model()
    server = BatchedServer(model, params, batch_slots=1, max_len=40,
                           paged=True, page_size=4, num_pages=3)
    [big] = _requests(cfg, [20], gen=4)  # needs 6 pages > pool of 3
    with pytest.raises(ValueError, match="pool size"):
        server._fill_slots([big])


def test_zero_gen_request_rejected():
    """max_new == 0 under-reserves pages (prompt - 1 rows) while prefill
    writes the full prompt — the tail would scatter into a live
    neighbour's page. Rejected up front, dense and paged alike."""
    cfg, model, params = _tiny_model()
    for kw in ({}, {"paged": True, "page_size": 8, "num_pages": 6}):
        server = BatchedServer(model, params, batch_slots=1, max_len=24,
                               **kw)
        [zero] = _requests(cfg, [9], gen=1)
        zero.max_new = 0
        with pytest.raises(ValueError, match="max_new"):
            server._fill_slots([zero])


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_whole_prompt_and_interleaves():
    """Acceptance: a prompt longer than the decode bucket is fed in
    page-sized waves; tokens are identical to whole-prompt prefill AND at
    least one decode step runs between prefill waves (the long prompt must
    not stall the short request's decode)."""
    cfg, model, params = _tiny_model()
    gen, max_len = 6, 64
    lens = [5, 33, 6]  # 33 >> chunk of 8 -> 5 waves
    reqs = _requests(cfg, lens, gen)
    server = BatchedServer(model, params, batch_slots=2, max_len=max_len,
                           paged=True, page_size=8, num_pages=12,
                           prefill_chunk=8)
    stats = server.run(reqs)
    assert stats["requests"] == len(lens)
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, len(r.prompt), r.out, want)
    assert stats["decode_compiles"] == 1, stats
    assert stats["pages"]["leaked"] == 0, stats
    # interleave proof: some decode step ran BETWEEN two prefill waves
    ev = server.events
    first_p, last_p = ev.index("prefill"), len(ev) - 1 - ev[::-1].index("prefill")
    assert "decode" in ev[first_p:last_p], ev
    # chunking bounds the prefill bucket: never the whole 33-token prompt
    assert max(stats["prefill_buckets"]) <= 8, stats


def test_chunked_prefill_final_wave_at_buffer_edge_dense():
    """Regression: a late chunk wave whose PADDED bucket tile overruns the
    cache buffer (starts + bucket > max_len) must not corrupt live KV. A
    dynamic_update_slice would clamp its start and shift the tile onto
    positions 1..7; the per-position scatter drops the padding instead."""
    cfg, model, params = _tiny_model()
    gen, max_len = 1, 9
    reqs = _requests(cfg, [9], gen)  # 9 + 1 - 1 == max_len: admissible
    server = BatchedServer(model, params, batch_slots=1, max_len=max_len,
                           prefill_chunk=8)  # final wave: starts=8, lb=8
    server.run(reqs)
    want = _isolated_decode(model, params, reqs[0].prompt, gen, max_len)
    assert reqs[0].out == want, (reqs[0].out, want)


def test_chunked_prefill_dense_cache():
    """Chunked prefill is orthogonal to paging: the contiguous cache path
    must produce identical tokens too."""
    cfg, model, params = _tiny_model(seed=2)
    gen, max_len = 4, 48
    lens = [21, 4]
    reqs = _requests(cfg, lens, gen, seed0=40)
    server = BatchedServer(model, params, batch_slots=2, max_len=max_len,
                           prefill_chunk=8)
    stats = server.run(reqs)
    assert stats["requests"] == 2
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, r.out, want)


@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-1.2b"])
def test_chunked_prefill_recurrent_families(arch):
    """Recurrent state (wkv/ssm/conv/shift carries) must continue exactly
    across prefill waves — chunked prefill is a state-carry stress test."""
    cfg, model, params = _tiny_model(arch, n_layers=2, seed=1)
    gen, max_len = 3, 32
    reqs = _requests(cfg, [13, 4], gen)
    server = BatchedServer(model, params, batch_slots=2, max_len=max_len,
                           prefill_chunk=4)
    stats = server.run(reqs)
    assert stats["requests"] == 2
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (arch, r.rid, r.out, want)


# ---------------------------------------------------------------------------
# Prefix sharing: copy-on-write paged serving == isolated decoding
# ---------------------------------------------------------------------------


def test_prefix_shared_serving_matches_isolated_and_saves_work():
    """Acceptance: a common-system-prompt workload served with the prefix
    cache produces token-for-token the isolated decodes, actually SHARES
    (hits, retained pages, fewer prefill tokens than the prompts sum) and
    leaks nothing — including after the cache itself is dropped."""
    cfg, model, params = _tiny_model()
    gen, max_len, page = 3, 64, 8
    rng = np.random.default_rng(17)
    common = rng.integers(0, cfg.vocab_size, 19, dtype=np.int32)
    tails = [4, 9, 1, 6, 13]
    reqs = [
        Request(i, np.concatenate(
            [common, rng.integers(0, cfg.vocab_size, t, dtype=np.int32)]
        ), gen)
        for i, t in enumerate(tails)
    ]
    server = BatchedServer(model, params, batch_slots=2, max_len=max_len,
                           paged=True, page_size=page, num_pages=24,
                           prefix_cache=True)
    stats = server.run(reqs)
    assert stats["requests"] == len(tails)
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, len(r.prompt), r.out, want)
    # sharing really happened: 19 common tokens = 2 full pages of 8
    assert stats["prefix"]["hits"] > 0, stats["prefix"]
    assert stats["prefix"]["hit_tokens"] > 0
    assert stats["pages"]["peak_shared"] > 0, stats["pages"]
    # the matched prefix was NOT recomputed
    assert stats["prefill_tokens"] < sum(len(r.prompt) for r in reqs)
    # reservation accounting is net of shared pages
    assert stats["kv_bytes_reserved_per_request"]["mean"] < (
        server._page_bytes * pages_for(len(reqs[0].prompt) + gen - 1, page)
    )
    assert stats["pages"]["leaked"] == 0, stats["pages"]
    assert stats["decode_compiles"] == 1, stats
    server.drop_prefix_cache()
    assert server.alloc.in_use == 0


def test_prefix_full_page_aligned_hit_copy_on_writes():
    """A prompt matched IN FULL on a page boundary rolls back one token to
    recompute its logits; that write would land in a shared page — the
    scheduler must copy-on-write it, never scatter into refcount > 1."""
    cfg, model, params = _tiny_model()
    gen, page = 3, 8
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab_size, 2 * page, dtype=np.int32)
    reqs = [Request(i, prompt.copy(), gen) for i in range(2)]
    server = BatchedServer(model, params, batch_slots=1, max_len=32,
                           paged=True, page_size=page, num_pages=10,
                           prefix_cache=True)
    stats = server.run(reqs)
    want = _isolated_decode(model, params, prompt, gen, 32)
    for r in reqs:
        assert r.out == want, (r.rid, r.out, want)
    assert stats["pages"]["cow_copies"] == 1, stats["pages"]
    assert stats["prefix"]["hits"] == 1
    # second request re-ran exactly ONE prompt token (the rollback)
    assert stats["prefill_tokens"] == len(prompt) + 1
    assert stats["pages"]["leaked"] == 0
    server.drop_prefix_cache()
    assert server.alloc.in_use == 0


def test_prefix_cache_eviction_under_pool_pressure():
    """When the pool cannot host a new request, cached prefixes are
    evicted LRU-first instead of stalling admission forever."""
    cfg, model, params = _tiny_model()
    gen, page = 2, 4
    # distinct prompts: each fills the index; a pool of 6 cannot hold the
    # accumulated cache AND admit the next request
    reqs = _requests(cfg, [11, 10, 12, 9], gen)
    server = BatchedServer(model, params, batch_slots=1, max_len=20,
                           paged=True, page_size=page, num_pages=6,
                           prefix_cache=True)
    stats = server.run(reqs)
    assert stats["requests"] == 4
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, 20)
        assert r.out == want, (r.rid, r.out, want)
    assert stats["prefix"]["evicted"] > 0, stats["prefix"]
    assert stats["pages"]["leaked"] == 0
    server.drop_prefix_cache()
    assert server.alloc.in_use == 0


def test_cross_wave_identical_prefix_dedup():
    """Requests with identical prefixes arriving in the SAME wave used to
    all prefill in full (the index only learns a prompt once it is fully
    prefilled). Admission now detects the pending overlap and serializes
    just their prefill: the first request admits alone, the rest admit one
    wave later as ordinary cache hits."""
    cfg, model, params = _tiny_model()
    gen, max_len, page = 3, 32, 8
    rng = np.random.default_rng(29)
    common = rng.integers(0, cfg.vocab_size, 2 * page, dtype=np.int32)
    tails = [3, 5, 2]
    prompts = [np.concatenate(
        [common, rng.integers(0, cfg.vocab_size, t, dtype=np.int32)]
    ) for t in tails]

    def serve(slots):
        reqs = [Request(i, p.copy(), gen) for i, p in enumerate(prompts)]
        server = BatchedServer(model, params, batch_slots=slots,
                               max_len=max_len, paged=True, page_size=page,
                               num_pages=24, prefix_cache=True)
        stats = server.run(reqs)
        server.drop_prefix_cache()
        assert server.alloc.in_use == 0
        return reqs, stats

    # 3 slots, 3 requests: without dedup they would all admit in wave 1
    # and share NOTHING; with it, every later request hits the cache
    reqs, stats = serve(3)
    assert stats["prefix"]["hits"] == len(tails) - 1, stats["prefix"]
    assert stats["prefix"]["admission_deferrals"] > 0, stats["prefix"]
    # the shared prefix prefilled ONCE, not three times
    assert stats["prefill_tokens"] < sum(len(p) for p in prompts)
    assert stats["pages"]["leaked"] == 0
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, r.out, want)
    # sharing behaviour must be slot-count independent in outcome
    reqs1, stats1 = serve(1)
    assert [r.out for r in reqs1] == [r.out for r in reqs]
    assert stats1["prefix"]["hits"] == len(tails) - 1


def test_prefix_state_budget_degrades_depth_not_correctness():
    """zamba2 with a snapshot budget too small for ANY boundary state:
    recurrent prefix hits disappear (match walks back to nothing) but
    every request still decodes exactly — the budget trades hit depth for
    memory, never correctness."""
    cfg, model, params = _tiny_model("zamba2-1.2b", n_layers=2, seed=1)
    gen, max_len, page = 2, 32, 4
    rng = np.random.default_rng(13)
    common = rng.integers(0, cfg.vocab_size, 2 * page, dtype=np.int32)
    prompts = [np.concatenate(
        [common, rng.integers(0, cfg.vocab_size, t, dtype=np.int32)]
    ) for t in (3, 5)]
    reqs = [Request(i, p.copy(), gen) for i, p in enumerate(prompts)]
    server = BatchedServer(model, params, batch_slots=1, max_len=max_len,
                           paged=True, page_size=page, num_pages=24,
                           prefix_cache=True, prefix_state_budget=1)
    stats = server.run(reqs)
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, gen, max_len)
        assert r.out == want, (r.rid, r.out, want)
    assert stats["prefix"]["states_held"] == 0, stats["prefix"]
    assert stats["prefix"]["states_evicted"] > 0, stats["prefix"]
    assert stats["pages"]["leaked"] == 0
    server.drop_prefix_cache()
    assert server.alloc.in_use == 0


def test_cross_wave_dedup_no_deadlock_on_distinct_prompts():
    """Distinct prompts must never defer — admission proceeds exactly as
    before when there is nothing to share."""
    cfg, model, params = _tiny_model()
    reqs = _requests(cfg, [9, 11, 6], gen=2)
    server = BatchedServer(model, params, batch_slots=3, max_len=24,
                           paged=True, page_size=4, num_pages=24,
                           prefix_cache=True)
    stats = server.run(reqs)
    assert stats["requests"] == 3
    assert stats["prefix"]["admission_deferrals"] == 0, stats["prefix"]
    assert stats["pages"]["leaked"] == 0
    server.drop_prefix_cache()
    assert server.alloc.in_use == 0


@pytest.mark.parametrize("arch", ["llama32-1b", "zamba2-1.2b"])
def test_prefix_shared_differential_fuzz(arch):
    """Differential fuzz: randomized prompt sets with overlapping prefixes
    served through prefix-shared paged serving must be token-for-token
    identical to isolated per-request decoding — attention (llama) and
    hybrid recurrent (zamba2, boundary-state snapshots) cache families."""
    cfg, model, params = _tiny_model(arch, n_layers=2, seed=1)
    gen, max_len, page = 2, 40, 4
    total_hits = 0
    for trial in range(3):
        rng = np.random.default_rng(1000 * trial + 7)
        bases = [rng.integers(0, cfg.vocab_size, int(n), dtype=np.int32)
                 for n in rng.integers(5, 14, size=2)]
        prompts = []
        for _ in range(5):
            base = bases[int(rng.integers(0, 2))]
            cut = int(rng.integers(1, len(base) + 1))
            tail = rng.integers(0, cfg.vocab_size, int(rng.integers(0, 6)),
                                dtype=np.int32)
            p = np.concatenate([base[:cut], tail])
            prompts.append(p[: max_len - gen - 1])
        reqs = [Request(i, p, gen) for i, p in enumerate(prompts)]
        server = BatchedServer(model, params, batch_slots=2,
                               max_len=max_len, paged=True, page_size=page,
                               num_pages=40, prefix_cache=True,
                               prefill_chunk=int(rng.integers(0, 2)) * 8)
        stats = server.run(reqs)
        assert stats["requests"] == len(reqs)
        for r in reqs:
            want = _isolated_decode(model, params, r.prompt, gen, max_len)
            assert r.out == want, (arch, trial, r.rid, list(r.prompt),
                                   r.out, want)
        assert stats["pages"]["leaked"] == 0, (arch, trial, stats["pages"])
        total_hits += stats["prefix"]["hits"]
        server.drop_prefix_cache()
        assert server.alloc.in_use == 0, (arch, trial)
    # across trials the overlapping prefixes must actually share
    assert total_hits > 0, (arch, total_hits)


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel vs reference
# ---------------------------------------------------------------------------


def test_paged_attention_kernel_matches_reference():
    rng = np.random.default_rng(0)
    b, kvh, g, hd, p_total, page, n_pages = 3, 2, 4, 32, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(p_total, page, kvh, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(p_total, page, kvh, hd)).astype(np.float32))
    # shuffled, non-overlapping physical pages per row
    pt = jnp.asarray(rng.permutation(p_total)[: b * n_pages]
                     .reshape(b, n_pages).astype(np.int32))
    lens = jnp.asarray([17, 1, 31], jnp.int32)
    pool = jnp.stack([kp, vp])[None]  # one layer's planes as a whole pool
    for kw in ({}, {"window": 9}, {"chunk": 16}):
        ref = paged_attention_reference(q, kp, vp, pt, lens, **kw)
        out = paged_attention_pallas(q, pool, 0, pt, lens, interpret=True,
                                     **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=str(kw))


def test_attention_block_kernel_dispatch_glue(monkeypatch):
    """CPU CI never takes the TPU kernel branch of attention_block — force
    it (interpret mode) and pin that the dispatch glue (kv-major q reshape,
    post-write k_len, window/chunk passthrough) matches the gather path."""
    import repro.kernels.paged_attention as pa_mod
    import repro.models.attention as attn_mod

    cfg, _, _ = _tiny_model()
    p = init_attention(jax.random.PRNGKey(5), cfg, jnp.float32)
    rng = np.random.default_rng(6)
    b, smax, page, pool, n_layers, layer = 2, 16, 4, 10, 3, 1
    n_pages = smax // page
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)).astype(np.float32))
    lens = jnp.asarray([7, 3], jnp.int32)
    pos = lens[:, None]
    pages = jnp.asarray(rng.normal(
        size=(n_layers, 2, pool, page, cfg.n_kv_heads, cfg.hd)
    ).astype(np.float32))
    table = jnp.asarray(rng.permutation(pool)[: b * n_pages]
                        .reshape(b, n_pages).astype(np.int32))

    def paged(window=0):
        return attention_block(
            p, cfg, x, pos, kv_pages=pages, kv_layer=layer,
            page_table=table, cache_len=lens,
            seq_lens=jnp.asarray([1, 1], jnp.int32), layer_window=window,
        )

    out_ref, cache_ref = paged()
    calls = {"n": 0}
    real = pa_mod.paged_attention_pallas

    def counting(*a, **k):
        calls["n"] += 1
        assert k.get("interpret"), "CPU dispatch must use interpret mode"
        return real(*a, **k)

    monkeypatch.setattr(pa_mod, "paged_attention_pallas", counting)
    monkeypatch.setattr(attn_mod, "_use_paged_kernel", lambda: True)
    out_k, cache_k = paged()
    assert calls["n"] == 1, "kernel branch was not taken"
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(cache_k), np.asarray(cache_ref))
    others = [i for i in range(n_layers) if i != layer]
    np.testing.assert_array_equal(np.asarray(cache_k)[others],
                                  np.asarray(pages)[others])
    out_w, _ = paged(window=4)  # window plumb-through, still via kernel
    assert calls["n"] == 2
    monkeypatch.setattr(attn_mod, "_use_paged_kernel", lambda: False)
    out_w_ref, _ = paged(window=4)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(out_w_ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_kernel_empty_row_exact_zeros():
    """len == 0 rows must come out EXACTLY zero (not a garbage average —
    the online-softmax p-masking guard)."""
    rng = np.random.default_rng(1)
    b, kvh, g, hd, p_total, page, n_pages = 2, 1, 2, 32, 8, 8, 3
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(p_total, page, kvh, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(p_total, page, kvh, hd)).astype(np.float32))
    pt = jnp.zeros((b, n_pages), jnp.int32)
    lens = jnp.asarray([0, 5], jnp.int32)
    out = paged_attention_pallas(q, jnp.stack([kp, vp])[None], 0, pt, lens,
                                 interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    assert np.abs(np.asarray(out[1])).max() > 0


# ---------------------------------------------------------------------------
# The pool of every layer, read and written by layer index
# ---------------------------------------------------------------------------

N_LAYERS = 3


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_paged_kernel_reads_one_layer_of_the_whole_pool(layer):
    """The kernel (interpret mode), handed every layer's pool and a traced
    layer index, equals the reference over that layer's K and V planes
    alone — plain, windowed and chunked, with an empty row exactly zero."""
    rng = np.random.default_rng(10 + layer)
    b, kvh, g, hd, p_total, page, n_pages = 3, 2, 4, 32, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)).astype(np.float32))
    pool = jnp.asarray(rng.normal(
        size=(N_LAYERS, 2, p_total, page, kvh, hd)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(p_total)[: b * n_pages]
                     .reshape(b, n_pages).astype(np.int32))
    lens = jnp.asarray([17, 0, 31], jnp.int32)
    for kw in ({}, {"window": 9}, {"chunk": 16}):
        ref = paged_attention_reference(q, pool[layer, 0], pool[layer, 1],
                                        pt, lens, **kw)
        out = paged_attention_pallas(q, pool, jnp.int32(layer), pt, lens,
                                     interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=str(kw))
        np.testing.assert_array_equal(np.asarray(out[1]), 0.0)


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_paged_write_touches_only_its_layer(layer):
    """A scatter into layer ``layer`` of the whole pool writes exactly that
    layer's K and V slots of each valid position (a frozen row and the
    positions past a row's table are dropped) and leaves every other
    layer bit-equal."""
    rng = np.random.default_rng(20 + layer)
    p_total, page, kvh, hd, b, s, n_pages = 12, 4, 2, 8, 3, 5, 3
    pool = rng.normal(size=(N_LAYERS, 2, p_total, page, kvh, hd)
                      ).astype(np.float32)
    k = rng.normal(size=(b, s, kvh, hd)).astype(np.float32)
    v = rng.normal(size=(b, s, kvh, hd)).astype(np.float32)
    table = rng.permutation(p_total)[: b * n_pages].reshape(b, n_pages)
    starts, seq_lens = [0, 9, 4], [5, 5, 0]  # row 1 runs off its table
    out = np.asarray(paged_write(
        jnp.asarray(pool), jnp.int32(layer), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(table, jnp.int32), jnp.asarray(starts, jnp.int32),
        jnp.asarray(seq_lens, jnp.int32)))
    want = pool.copy()
    for r in range(b):
        for j in range(seq_lens[r]):
            t = starts[r] + j
            if t < n_pages * page:
                phys = table[r, t // page]
                want[layer, 0, phys, t % page] = k[r, j]
                want[layer, 1, phys, t % page] = v[r, j]
    np.testing.assert_array_equal(out, want)
    others = [i for i in range(N_LAYERS) if i != layer]
    np.testing.assert_array_equal(out[others], pool[others])


def _cow_requests(cfg, page):
    """Two copies of a page-aligned prompt (the second is a full prefix
    hit whose one-token rollback copy-on-writes a shared page) and a
    third that shares its first page."""
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab_size, 2 * page, dtype=np.int32)
    tail = rng.integers(0, cfg.vocab_size, page + 3, dtype=np.int32)
    return [Request(0, prompt.copy(), 4), Request(1, prompt.copy(), 4),
            Request(2, np.concatenate([prompt[:page], tail]), 4)]


@pytest.mark.parametrize("arch,n_layers,scenario", [
    ("llama32-1b", 2, "prefix_cow"),
    ("llama32-1b", 2, "preempt_spill"),
    ("zamba2-1.2b", 4, "preempt_spill"),
])
def test_donated_server_streams_match_isolated(arch, n_layers, scenario,
                                               tmp_path):
    """The decode and prefill programs take the cache donated, and the
    host edits between rounds (prefix hits, copy-on-write page copies,
    preemption, spill and page reload) keep working on the pool they hand
    back: every stream equals its request's isolated decode, and every
    paged round's output pool is the buffer it was given."""
    from repro.serve import SpillStore

    cfg, model, params = _tiny_model(arch, n_layers=n_layers)
    max_len = 48
    if scenario == "prefix_cow":
        page = 8
        reqs = _cow_requests(cfg, page)
        kw = dict(batch_slots=2, paged=True, page_size=page, num_pages=12,
                  prefix_cache=True)
    else:
        reqs = _requests(cfg, [10, 14], 16)
        kw = dict(batch_slots=2, paged=True, page_size=4, num_pages=10,
                  page_growth=True, inject="oop@tick2,oop@tick6",
                  spill_store=SpillStore(tmp_path / "spill"),
                  spill_threshold=0)
    server = BatchedServer(model, params, max_len=max_len, **kw)
    stats = server.run(reqs)
    for r in reqs:
        want = _isolated_decode(model, params, r.prompt, r.max_new, max_len)
        assert r.out == want, (r.rid, r.out, want)
    if scenario == "prefix_cow":
        assert stats["prefix"]["hits"] >= 2, stats["prefix"]
        assert stats["pages"]["cow_copies"] >= 1, stats["pages"]
    else:
        res = stats["resilience"]
        assert res["preemptions"] >= 1 and res["spills"] >= 1, res
        assert res["spill_restores"] == res["spills"], res
    kv = stats["kv"]
    assert kv["pool_rounds"] == stats["decode_steps"] + stats["prefill_waves"]
    assert kv["pool_in_place"] == kv["pool_rounds"] > 0, kv


# ---------------------------------------------------------------------------
# Fully-masked softmax guard (replaces the k_len >= 1 clamp)
# ---------------------------------------------------------------------------


def test_empty_row_attention_is_exact_zero_not_garbage_key():
    """Regression: rows with NO valid key (empty/frozen slot, k_len == 0)
    used to clamp in one garbage key; they must now produce exact zeros
    with no NaN — for both the dense and the paged cache layouts."""
    cfg, _, _ = _tiny_model()
    p = init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s, smax = 2, 1, 16
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(b, s, cfg.d_model)).astype(np.float32))
    pos = jnp.zeros((b, s), jnp.int32)
    # row 0 empty (len 0, frozen), row 1 has 3 cached keys and writes one
    kv = jnp.asarray(np.random.default_rng(3).normal(
        size=(2, b, smax, cfg.n_kv_heads, cfg.hd)).astype(np.float32))
    out, _ = attention_block(
        p, cfg, x, pos, kv_cache=kv,
        cache_len=jnp.asarray([0, 3], jnp.int32),
        seq_lens=jnp.asarray([0, 1], jnp.int32),
    )
    out = np.asarray(out)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[0], 0.0)
    assert np.abs(out[1]).max() > 0
    # paged layout, same contract
    pages = jnp.asarray(np.random.default_rng(4).normal(
        size=(1, 2, 6, 4, cfg.n_kv_heads, cfg.hd)).astype(np.float32))
    table = jnp.asarray([[5, 2, 0, 1], [3, 4, 1, 0]], jnp.int32)
    out_p, _ = attention_block(
        p, cfg, x, pos, kv_pages=pages, page_table=table,
        cache_len=jnp.asarray([0, 3], jnp.int32),
        seq_lens=jnp.asarray([0, 1], jnp.int32),
    )
    out_p = np.asarray(out_p)
    assert np.isfinite(out_p).all()
    np.testing.assert_array_equal(out_p[0], 0.0)


def test_decode_all_slots_empty_no_nan():
    """A decode step where EVERY slot is empty/inactive must stay finite
    end-to-end (the old clamp hid this; the guard must too — by design,
    not by accident)."""
    cfg, model, params = _tiny_model()
    cache = model.init_cache(2, 16)
    logits, cache2 = model.decode_step(
        params, jnp.zeros((2, 1), jnp.int32), cache,
        active=jnp.asarray([False, False]),
    )
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_array_equal(np.asarray(cache2["len"]),
                                  np.asarray(cache["len"]))
