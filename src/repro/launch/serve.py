"""Batched serving driver with SplitQuantV2 quantized weights.

    PYTHONPATH=src python -m repro.launch.serve --arch llama32-1b --reduced \
        --bits 4 --engine packed --batch 4 --prompt-len 16 --gen 8 \
        --paged --page-size 16 --prefill-chunk 32

Continuous batching: a request queue is packed into fixed batch slots. The
KV cache keeps a PER-SLOT fill length (``cache["len"]: (B,)``), so every
slot decodes at its own position against its own keys; finished sequences
are replaced between decode steps by **batched in-place prefill** waves
that write new prompts straight into the live cache (rows of ongoing
requests are frozen via per-row ``seq_lens``). Prompts are right-padded to
power-of-two buckets, so slot swaps compile once per bucket instead of once
per distinct prompt length, and the decode step never recompiles at all.

``--paged`` swaps the per-slot contiguous KV strips for the PAGED cache
(``repro.kvcache``): attention KV lives in a shared pool of fixed-size
pages, each request owns exactly the pages its prompt+generation needs, and
the scheduler admits by FREE-PAGE BUDGET instead of reserving
``batch × max_len`` up front — one long request no longer dictates the
memory bill for the whole batch. ``--prefill-chunk N`` additionally splits
long prompts into N-token waves interleaved with decode steps, so a giant
prompt doesn't stall ongoing decodes (works for dense caches too).

Sampling: greedy argmax by default; ``--temperature/--top-k/--top-p`` turn
on seeded stochastic sampling (host-side, reproducible via ``--seed``).
``BatchedServer.run(requests, on_token=...)`` streams tokens to the caller
as they decode.

``--prefix-cache`` (paged mode) turns on PREFIX SHARING: after a prompt is
fully prefilled, its full KV pages are indexed by a chain hash of their
token ids (``repro.kvcache.prefix``); a later request whose prompt starts
with the same tokens retains the matched pages read-only into its own page
table and prefills only the unmatched tail — fleets sharing a system
prompt stop paying for the same prefix pages and prefill compute N times.
Shared pages are copy-on-written before any write lands in one, and
reservation accounting is net of shared pages. ``--shared-prefix N``
prepends a common N-token prefix to every generated prompt (workload
shaping for smokes/benches).

``--engine`` selects how quantized weights execute:
  fake    dequantized dense weights (the paper's fake-quant evaluation)
  packed  6-bit packed storage streamed through the fused Pallas kernels
          with grouped QKV / gate+up launches (4 quantized matmul launches
          per block instead of 7) — the real deployment path
  planes  paper-faithful 3-plane storage through the fused k-plane kernel

``--speculate k`` (paged mode) turns on SPECULATIVE DECODING: a draft
model — by default the packed INT4 executable of the SAME weights
(``--draft-engine``/``--draft-bits``), the paper's accuracy result turned
into a latency win — proposes k tokens per request over its own paged KV
cache (``repro.spec``), and the target model scores all k+1 positions in
ONE batched forward (drafted tokens are just a prefill chunk whose logits
we keep). Accepted drafts are emitted in bulk; rejected ones rewind each
slot's ``cache["len"]`` (and, for recurrent families, restore + recompute
the boundary state) with no page leaked or double-written. Greedy decoding
is BIT-IDENTICAL to non-speculative serving; with sampling, standard
rejection sampling against the per-request seeded streams keeps each
emitted token an exact draw from the target distribution.

SERVING UNDER PRESSURE (``--page-growth`` / ``--preemption`` /
``--spec-floor`` / ``--inject`` / ``--max-wall-s``): on-demand page
growth admits requests with a prompt-only (+ ``--growth-headroom``)
reservation and grows their page lists per decode tick, so the same pool
admits MORE concurrent requests than full reservation — at the price of
possible mid-decode exhaustion. When the pool runs dry the scheduler
first evicts cached prefixes, then PREEMPTS a victim (lowest priority,
then youngest-by-emitted-tokens; the oldest live request is always
exempt, which makes forward progress provable — see
``runtime.resilience``): the victim's non-shared pages are released and
it is re-admitted later by replaying prompt + emitted tokens through the
ordinary prefill path, bit-identically for greedy streams. Speculative
requests degrade gracefully: under pool pressure, or when the trailing
acceptance rate sits below ``--spec-floor`` over ``--spec-window``
drafted tokens, a request decodes plainly for the round instead of
failing. SIGTERM (via ``PreemptionGuard``) and the ``--max-wall-s`` soft
deadline drain in-flight requests — finish the current wave, mark live
requests ``preempted`` with their partial streams, free every page. A
seeded fault injector (``--inject oop@tick7,fail@tick3``, see
``runtime.faultinject``) forces pool exhaustion / transient step
failures / latency at chosen decode ticks so chaos tests can assert the
recovery paths are exact.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache import (PagePoolGroup, PrefixIndex, copy_page, pages_for,
                           read_pages, write_pages)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import _RECURRENT_KEYS, reset_slots
from repro.obs import DEFAULT_CAP, JaxProfile, Observability, compile_counts
from repro.obs.trace import now as _now
from repro.runtime import sharding as shd
from repro.runtime.fault import PreemptionGuard, run_with_retries
from repro.runtime.faultinject import FaultInjector
from repro.runtime.resilience import (AcceptanceWindow, SchedulerStall,
                                      SlotDiag, pick_victim, replay_sequence)
from repro.spec import Drafter, SpecStats, Verifier
from repro.spec.policy import accept_greedy, accept_speculative, shaped_probs


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (prompt_len,)
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    fed: int = 0                # prompt tokens already prefilled (chunked)
    pages: list = dataclasses.field(default_factory=list)  # owned page ids
    kv_reserved_bytes: int = 0  # KV bytes reserved for this request
    start_len: int = 0          # prefix-cache hit: first position to prefill
    preloaded: bool = False     # recurrent state installed at admission
    indexed: bool = False       # prompt pages registered in the prefix index
    snaps: dict = dataclasses.field(default_factory=dict)  # boundary -> state
    rng: np.random.Generator | None = None  # per-request sampling stream
    dfed: int = 0               # prompt tokens prefilled into the DRAFT cache
    priority: int = 0           # victim policy: lower preempts first
    status: str = "ok"          # "ok" | "preempted" (drained with a partial
    #                             stream; mid-run preemptions restore to "ok")
    seq_no: int = -1            # admission order; the oldest live request is
    #                             growth-exempt (assigned once, survives replay)
    replay: np.ndarray | None = None  # preempted: tokens to re-prefill
    preemptions: int = 0        # times this request was preempted
    draft_on: bool = False      # drafting decision, frozen at (re)admission
    acc: "AcceptanceWindow | None" = None  # trailing draft acceptance
    spilled: bool = False       # pages live in the host spill store; restore
    #                             reloads them instead of replay recompute
    queued_t: float | None = None  # service submit time (tenant-queue entry
    #                                starts the TTFT clock, not admission)
    force: np.ndarray | None = None  # teacher forcing (eval): emit
    #                             force[len(out)] instead of sampling, while
    #                             the model still scores every position —
    #                             perplexity through the real serving path
    logits: list | None = None  # capture_logits=True: host (V,) logits row
    #                             behind each emitted token, append order ==
    #                             out order (the eval scorers read these)


def sample_token(
    logits: np.ndarray,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: np.random.Generator | None = None,
) -> int:
    """One token from a (V,) logits row. ``temperature <= 0`` is greedy
    argmax (the deterministic default the serving tests pin); otherwise
    temperature -> top-k filter -> top-p nucleus -> seeded draw. The
    shaping lives in ``spec.policy.shaped_probs`` — the SAME distribution
    the speculative rejection sampler verifies against."""
    if temperature <= 0.0:
        return int(np.argmax(np.asarray(logits)))
    if rng is None:
        rng = np.random.default_rng()
    probs = shaped_probs(np.asarray(logits), temperature=temperature,
                         top_k=top_k, top_p=top_p)
    return int(rng.choice(probs.size, p=probs))


def _bucket(n: int, minimum: int) -> int:
    """Next power of two >= max(n, minimum)."""
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


# divergence magnitudes span many decades (INT8 KL ~1e-5, INT2 KL ~10), so
# the probe histograms bucket by powers of ten, not the latency buckets
PROBE_BUCKETS = tuple(10.0 ** e for e in range(-8, 3))


class BatchedServer:
    """Fixed-slot continuous batching over a decode_step function.

    Slot-swap contract: every prefill wave is ONE batched call into the
    live cache — rows starting a fresh request are reset (``reset_slots``),
    rows mid-prompt continue at their own ``len``, ongoing/finished rows
    are frozen (``lengths == 0``) — and the per-slot cache length makes
    every subsequent step position each request correctly regardless of
    its neighbours.

    Paged mode: attention KV pages are reserved per request at admission
    (``ceil((prompt + gen - 1) / page_size)`` pages — deadlock-free: a
    request that is admitted can always finish) and freed at retirement;
    the scheduler admits while the free-page budget lasts. ``max_len``
    bounds one REQUEST (the page-table width), not the pool — the pool is
    ``num_pages`` and can be far below ``slots × max_len``.

    Prefix sharing (``prefix_cache=True``, paged only): admission matches
    the new prompt against the prefix index, retains the matched pages
    read-only, and reserves only the tail — ``start_len`` makes prefill
    begin past the shared prefix (positions, write offsets and masks all
    ride the per-row ``len`` contract). A request never scatters into a
    page with refcount > 1: the scheduler copy-on-writes first (fresh
    page, device copy, page-table swap — only a full-prompt page-boundary
    hit triggers it, to re-run the last token for logits). Recurrent
    families (zamba2) additionally need the ssm/conv state at the
    boundary: prefill waves are capped to end on page boundaries so every
    boundary's state is snapshotted into the index, and a hit installs the
    snapshot instead of resetting the slot. Requests admitted in the SAME
    wave cannot share with each other (the index only learns a prompt once
    it is fully prefilled).

    Chunked prefill: ``prefill_chunk > 0`` feeds prompts in chunk-sized
    waves; ``run`` alternates one prefill wave with one decode step so
    ongoing requests keep emitting tokens while a long prompt loads.
    """

    def __init__(self, model, params, batch_slots: int, max_len: int,
                 bucket_min: int = 8, *, paged: bool = False,
                 page_size: int = 16, num_pages: int | None = None,
                 prefix_cache: bool = False, prefix_state_budget: int = 0,
                 prefill_chunk: int = 0, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 speculate: int = 0, draft_params=None,
                 draft_num_pages: int | None = None,
                 page_growth: bool = False, growth_headroom: int = 0,
                 preemption: bool = True, spec_floor: float = 0.0,
                 spec_window: int = 16,
                 inject: "FaultInjector | str | None" = None,
                 guard: PreemptionGuard | None = None,
                 max_wall_s: float = 0.0,
                 spill_store=None, spill_threshold: int = 0,
                 slo=None, mesh=None,
                 obs: Observability | None = None,
                 trace_cap: int = DEFAULT_CAP,
                 profile: JaxProfile | None = None,
                 quality_probe: int = 0, probe_params=None,
                 capture_logits: bool = False):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.bucket_min = bucket_min
        self.paged = paged
        self.prefill_chunk = prefill_chunk
        self.sampling = {"temperature": temperature, "top_k": top_k,
                         "top_p": top_p}
        self._seed = seed
        self._on_token: Callable | None = None
        self.active: list[Request | None] = [None] * batch_slots
        self.buckets_used: list[int] = []
        # -- observability (repro.obs): default ON — registry + tracer +
        # timeline; Observability.disabled() keeps a REAL timeline so the
        # ``events`` compat property behaves identically either way
        if obs is None:
            obs = Observability(
                trace_cap=trace_cap,
                const_labels={"family": model.cfg.family},
            )
        self.obs = obs
        self.registry = obs.registry
        self.tracer = obs.tracer
        self.timeline = obs.timeline
        self.profile = profile
        self.prefill_tokens = 0     # tokens actually fed through prefill
        self.pages_allocated = 0    # fresh pages allocated (incl. COW copies)
        self.prefix_deferrals = 0   # admissions held back for cross-wave dedup
        # -- resilience (see module docstring + runtime.resilience) ---------
        self.page_growth = page_growth
        self.growth_headroom = growth_headroom
        self.preemption = preemption
        self.spec_floor = spec_floor
        self.spec_window = spec_window
        self.inject = (FaultInjector(inject, seed=seed,
                                     registry=self.registry)
                       if isinstance(inject, str) else inject)
        if self.inject is not None and self.inject.registry is None:
            self.inject.registry = self.registry
        self.guard = guard
        self.max_wall_s = max_wall_s
        self.preemptions = 0        # victim preemptions (pool pressure)
        self.replays = 0            # preempted requests re-admitted
        self.replay_tokens = 0      # tokens re-prefilled by those replays
        # -- spill tier (preempt-to-disk, see repro.serve.spill) ------------
        self.spill = spill_store
        self.spill_threshold = spill_threshold
        self.spills = 0             # preempted contexts spilled to the store
        self.spill_restores = 0     # re-admissions restored by page reload
        self.recompute_forwards = 0  # prefill waves that carried replay rows
        if spill_store is not None and not paged:
            raise ValueError("spill_store requires paged=True")
        # -- SLO loop (repro.serve.slo): the controller owns the chunk ------
        self.slo = slo
        self.slo_adjustments = 0
        if slo is not None:
            self.prefill_chunk = slo.chunk
            slo.spec_floor = slo.base_floor = spec_floor or slo.base_floor
            self.spec_floor = slo.spec_floor
        self.peak_concurrency = 0   # most slots simultaneously live
        self.drained = False        # run ended via SIGTERM / wall-clock drain
        self._seq_counter = 0       # admission order for the growth exemption
        self._pending: list[Request] = []
        if page_growth and not paged:
            raise ValueError("page_growth requires paged=True")
        if prefix_cache and not paged:
            raise ValueError("prefix_cache requires paged=True")
        if speculate and not paged:
            raise ValueError("speculate requires paged=True (draft KV and "
                             "verify rollback ride the paged cache)")
        if speculate and draft_params is None:
            raise ValueError("speculate requires draft_params (the draft "
                             "model's executable tree)")
        if speculate and speculate + 1 > max_len:
            raise ValueError(f"speculate={speculate} verify chunk exceeds "
                             f"max_len={max_len}")
        if speculate and (model.cfg.encdec or model.cfg.family == "vlm"):
            raise ValueError(
                f"{model.cfg.name}: speculative decoding covers token-only "
                "LM families (enc-dec / VLM verify_step is a follow-on)"
            )

        # -- mesh plan (GSPMD serving) ---------------------------------------
        # One MeshPlan binds this server run to one (data, model) mesh: DP
        # replica groups split the batch slots (and, in paged mode, the page
        # pool) while TP shards every matmul's output dim under the exact-TP
        # contract (bit-identical greedy streams — see runtime.sharding).
        self._plan = shd.MeshPlan(mesh) if mesh is not None else None
        if self._plan is not None:
            n_rep = self._plan.n_data
            if batch_slots % n_rep:
                raise ValueError(
                    f"batch_slots ({batch_slots}) must divide over the "
                    f"mesh's {n_rep} data replicas")
            self.params, self._param_shd = self._plan.put_params(params)
            params = self.params
        else:
            n_rep = 1
            self._param_shd = None
        self.n_replicas = n_rep
        self._slots_per_rep = batch_slots // n_rep

        if paged:
            self.page_size = page_size
            pages_per_row = pages_for(max_len, page_size)
            self.num_pages = num_pages or batch_slots * pages_per_row
            if self.num_pages % n_rep:
                raise ValueError(
                    f"num_pages ({self.num_pages}) must divide over the "
                    f"mesh's {n_rep} data replicas")
            self.cache = model.init_paged_cache(
                batch_slots, max_len, page_size=page_size,
                num_pages=self.num_pages,
            )
            # replica r owns global page ids [r*n, (r+1)*n): with the pool's
            # PAGE dim batch-sharded over `data`, a replica's pages — and all
            # its COW / copy_page / rewind traffic — stay on its own devices
            self.alloc = PagePoolGroup(self.num_pages, n_rep)
            self._table = np.zeros((batch_slots, pages_per_row), np.int32)
            self._table_dirty = False  # host table diverged from device copy
            pool_bytes = sum(
                v.nbytes for k, v in self.cache.items()
                if k in ("pages", "shared_pages")
            )
            self._page_bytes = pool_bytes // self.num_pages
            # one prefix index per DP replica, each bound to its own pool —
            # a replica's prefix hits retain pages its own devices hold
            self.prefixes = (
                [PrefixIndex(page_size, self.alloc.pools[r],
                             state_budget=prefix_state_budget)
                 for r in range(n_rep)]
                if prefix_cache else None
            )
            self.prefix = self.prefixes[0] if prefix_cache else None
            # recurrent leaves are part of a prefix (KV pages alone are
            # not): their boundary states ride the index as snapshots
            self._recurrent = [k for k in _RECURRENT_KEYS if k in self.cache]
            self._snap_boundaries = bool(self.prefix and self._recurrent)
        else:
            self.alloc = None
            self.prefix = None
            self.prefixes = None
            self._recurrent = []
            self._snap_boundaries = False
            self.cache = model.init_cache(batch_slots, max_len)
            kv_bytes = sum(
                v.nbytes for k, v in self.cache.items()
                if k in ("kv", "shared_kv")
            )
            # contiguous strips reserve max_len rows per slot up front
            self._kv_row_bytes = kv_bytes // batch_slots

        # canonical cache shardings: committed at init, pinned as every
        # jit's cache OUT sharding, and re-committed by _sync_table after
        # host-side cache edits — jitted-call input shardings stay
        # byte-stable so decode compiles exactly once
        if self._plan is not None:
            self._cache_shd = self._plan.cache_shardings(self.cache)
            self.cache = self._plan.put_cache(self.cache, self._cache_shd)
        else:
            self._cache_shd = None

        self.speculate = speculate
        if speculate:
            self.drafter = Drafter(
                model, draft_params, batch_slots, max_len,
                page_size=page_size, width=speculate + 1,
                num_pages=draft_num_pages, plan=self._plan,
                registry=self.registry,
            )
            self.verifier = Verifier(model, params, self._recurrent,
                                     plan=self._plan,
                                     cache_shd=self._cache_shd,
                                     registry=self.registry)
            self.spec = SpecStats(k=speculate)
        else:
            self.drafter = None
            self.verifier = None
            self.spec = None

        plan = self._plan
        if plan is None:
            self._decode = jax.jit(model.decode_step)
        else:
            # the hints context is entered INSIDE the traced body: the
            # exact-TP act_constraints (and the kernels' shard_map over the
            # serving mesh) are captured at trace time, like steps.py
            def _decode_fn(params, tokens, cache, active):
                with plan.hints():
                    return model.decode_step(params, tokens, cache,
                                             active=active)

            self._decode = jax.jit(_decode_fn,
                                   out_shardings=(None, self._cache_shd))

        def _prefill_fn(params, tokens, lengths, fresh, starts, cache):
            # fresh rows restart at ``starts`` (0, or past a shared prefix)
            cache = reset_slots(cache, fresh, starts)
            if plan is not None:
                with plan.hints():
                    return model.prefill(
                        params, {"tokens": tokens, "lengths": lengths}, cache
                    )
            return model.prefill(
                params, {"tokens": tokens, "lengths": lengths}, cache
            )

        if plan is None:
            self._prefill = jax.jit(_prefill_fn)
        else:
            self._prefill = jax.jit(_prefill_fn,
                                    out_shardings=(None, self._cache_shd))

        # -- quality observability (see module docstring) --------------------
        # capture_logits: the eval path asks for the host logits row behind
        # every emitted token; force (per request) teacher-forces the
        # emission. Neither touches the jitted functions.
        self.capture_logits = capture_logits
        # quality_probe=N: every N decode/verify ticks, replay each live
        # row's context through an fp-reference forward and record the
        # logit divergence (KL, top-1 agreement, max-abs-diff) between the
        # reference and the quantized logits THE SERVER JUST COMPUTED. The
        # probe owns a dedicated 1-slot dense cache and its own jit: it
        # never reads or writes the serving cache, never touches
        # self._prefill / self._decode (whose compile counts the stats
        # report), and only consumes host copies of serving logits — the
        # enabled-vs-disabled streams are bit-identical by construction.
        self.quality_probe = quality_probe
        self.probe_samples = 0
        self.probe_agreements = 0
        self._probe_tick = 0
        if quality_probe:
            if probe_params is None:
                raise ValueError("quality_probe requires probe_params "
                                 "(the fp reference weight tree)")
            self._probe_params = probe_params
            self._probe_cache = model.init_cache(1, max_len)

            def _probe_fn(params, tokens, lengths, cache):
                fresh = jnp.ones((tokens.shape[0],), bool)
                starts = jnp.zeros((tokens.shape[0],), jnp.int32)
                cache = reset_slots(cache, fresh, starts)
                return model.prefill(
                    params, {"tokens": tokens, "lengths": lengths}, cache
                )

            self._probe_prefill = jax.jit(_probe_fn)

    # -- sampling / streaming -----------------------------------------------

    def _pick_tokens(self, logits) -> Callable[[int], int]:
        """Per-slot token chooser from device logits (B, 1, V). Greedy mode
        argmaxes ON DEVICE and transfers B ints; stochastic sampling needs
        the full logits rows on the host (B x V, off the hot path).

        Each request draws from its OWN stream seeded by (server seed,
        rid): the sampled tokens depend only on the request and the model,
        not on which slot it landed in, what its neighbours were, or the
        order the scheduler admitted it.

        Eval hooks: ``capture_logits`` appends the host row behind each
        pick to the request's ``logits`` list, and a request's ``force``
        array teacher-forces the emitted token — both need the full rows
        on the host, so the device-argmax fast path only runs when
        neither is in play (serving streams stay untouched)."""
        eval_hooks = self.capture_logits or any(
            r is not None and r.force is not None for r in self.active)
        if self.sampling["temperature"] <= 0.0 and not eval_hooks:
            toks = np.asarray(jnp.argmax(logits[:, 0], -1))
            return lambda i: int(toks[i])
        rows = np.asarray(logits[:, 0])

        def pick(i: int) -> int:
            r = self.active[i]
            if self.capture_logits:
                if r.logits is None:
                    r.logits = []
                r.logits.append(rows[i].copy())
            if r.force is not None and len(r.out) < len(r.force):
                return int(r.force[len(r.out)])
            if self.sampling["temperature"] <= 0.0:
                return int(np.argmax(rows[i]))
            return sample_token(rows[i], **self.sampling, rng=r.rng)

        return pick

    def _emit(self, req: Request, tok: int):
        req.out.append(tok)
        req.done = len(req.out) >= req.max_new
        self.tracer.emit(req.rid)
        if self._on_token is not None:
            self._on_token(req, tok)

    # -- observability --------------------------------------------------------

    @property
    def events(self) -> list[str]:
        """Legacy event strings ("prefill" / "decode" / "verify" /
        "preempt:<rid>" ...), rendered from the structured timeline —
        the compat view over the new source of truth."""
        return self.timeline.legacy_events()

    def _tl(self, kind: str, **fields) -> None:
        """Emit one scheduler-timeline record, stamped with the live
        scheduler state every record shares (active slots; pool free /
        fragmentation in paged mode)."""
        fields["active"] = sum(1 for r in self.active if r is not None)
        if self.paged:
            fields["free_pages"] = self.alloc.free_pages
            fields["frag"] = round(self.alloc.fragmentation(), 4)
        self.timeline.emit(kind, **fields)

    def _span(self, i: int, r: Request, kind: str, t0: float, t1: float,
              out_before: int, **kw) -> None:
        """Attribute one wave's work to request ``r``: a tracer span whose
        ``emitted`` is exactly the tokens this wave appended to ``r.out``
        (so per-request span sums always reconcile with the stream), plus
        the per-replica token counter."""
        emitted = len(r.out) - out_before
        self.tracer.span(r.rid, kind, t0, t1, emitted=emitted, **kw)
        if emitted and self.registry.enabled:
            self.registry.counter(
                "serve_tokens_total", "tokens emitted, by replica",
            ).inc(emitted, replica=self._rep(i))

    # -- online divergence probe (quality observability) ---------------------

    def _probe_due(self) -> bool:
        """Tick the probe clock (one tick per decode/verify round) and
        decide whether this round is a probed one."""
        if not self.quality_probe:
            return False
        self._probe_tick += 1
        return self._probe_tick % self.quality_probe == 0

    def _probe_forward(self, seq: np.ndarray) -> np.ndarray:
        """fp-reference logits after ``seq``: one B=1 prefill over the
        probe's private dense cache, bucketed so the shadow jit compiles
        once per power-of-two length like the serving prefill."""
        lb = min(_bucket(len(seq), self.bucket_min), self.max_len)
        tokens = np.zeros((1, lb), np.int32)
        tokens[0, : len(seq)] = seq
        lengths = np.array([len(seq)], np.int32)
        logits, self._probe_cache = self._probe_prefill(
            self._probe_params, jnp.asarray(tokens), jnp.asarray(lengths),
            self._probe_cache,
        )
        return np.asarray(logits[0, 0])

    def _probe_row(self, r: Request, q_row: np.ndarray) -> None:
        """Compare the quantized serving distribution for request ``r``'s
        next token (``q_row``, already on the host) against the fp
        reference over the same context, and file the divergence into the
        registry/timeline. Host-side only — nothing here can perturb the
        serving streams."""
        seq = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        if len(seq) > self.max_len:
            return
        fp = self._probe_forward(seq).astype(np.float64)
        q = np.asarray(q_row, np.float64)
        m = fp.max()
        logp_fp = fp - (m + np.log(np.sum(np.exp(fp - m))))
        mq = q.max()
        logp_q = q - (mq + np.log(np.sum(np.exp(q - mq))))
        kl = float(np.sum(np.exp(logp_fp) * (logp_fp - logp_q)))
        agree = int(np.argmax(fp)) == int(np.argmax(q))
        mad = float(np.max(np.abs(fp - q)))
        self.probe_samples += 1
        self.probe_agreements += int(agree)
        if self.registry.enabled:
            reg = self.registry
            reg.histogram(
                "quality_probe_kl",
                "KL(fp || quantized) of next-token logits at probed "
                "decode positions", buckets=PROBE_BUCKETS,
            ).observe(max(kl, 0.0))
            reg.histogram(
                "quality_probe_max_abs_diff",
                "max |logit_fp - logit_quantized| at probed positions",
                buckets=PROBE_BUCKETS,
            ).observe(mad)
            reg.counter(
                "quality_probe_samples_total", "decode positions probed "
                "against the fp reference").inc()
            if agree:
                reg.counter(
                    "quality_probe_top1_agree_total",
                    "probed positions where fp and quantized argmax "
                    "agree").inc()
        self._tl("probe", rid=r.rid, kl=round(kl, 6), agree=agree,
                 max_abs_diff=round(mad, 6))

    # -- slot management ----------------------------------------------------

    def _rep(self, i: int) -> int:
        """DP replica owning batch slot ``i`` (0 on a single-replica run):
        the cache's slot dim is batch-sharded over ``data``, so contiguous
        slot blocks live on contiguous replica device groups."""
        return i // self._slots_per_rep

    def _prefix_of(self, i: int) -> PrefixIndex | None:
        """Slot ``i``'s replica-local prefix index (None when disabled)."""
        return self.prefixes[self._rep(i)] if self.prefixes else None

    def _put(self, arr):
        """Host batch array -> device; slot-leading arrays shard over the
        data axes under a mesh plan so jitted input shardings never vary."""
        if self._plan is None:
            return jnp.asarray(arr)
        return self._plan.put_batch(arr)

    def _sync_table(self):
        """Re-upload the page table only when admission/retirement changed
        it — steady-state decode keeps the device copy (it rides through
        every jitted call unchanged in the cache pytree). Under a mesh plan
        the whole cache is re-committed to its canonical shardings: host
        edits (COW page copies, snapshot installs, rewinds) leave eager
        result shardings behind, and device_put on an already-canonical
        leaf is a no-op."""
        if self.paged and self._table_dirty:
            self.cache["page_table"] = jnp.asarray(self._table)
            self._table_dirty = False
        if self._plan is not None:
            self.cache = self._plan.put_cache(self.cache, self._cache_shd)

    def _seq(self, r: Request) -> np.ndarray:
        """The token sequence the prefill path feeds for ``r``: its prompt,
        or — after a preemption — the replay sequence (prompt + emitted
        tokens except the last, see ``resilience.replay_sequence``)."""
        return r.replay if r.replay is not None else r.prompt

    def _need_rows(self, r: Request) -> int:
        """KV rows ``r`` still needs END-TO-END from its current sequence:
        prefill writes ``len(seq)`` rows, decode one more per remaining
        token except the last. For a fresh request this is the classic
        ``prompt + max_new - 1``; for a replay it already nets out the
        rows the emitted tokens no longer need."""
        return len(self._seq(r)) + (r.max_new - len(r.out)) - 1

    def _call(self, seam: str, fn: Callable):
        """Run one device step through the fault boundary. With no
        injector installed this is a direct call (the hot path pays
        nothing). Under injection, the seam's slow/fail hooks fire first
        and transient failures retry via ``run_with_retries`` — safe
        because every step is a pure jitted function over an immutable
        cache pytree (re-running cannot double-apply a write), with
        ``OutOfPages`` excluded (deterministic resource condition: the
        scheduler's relief path owns it, not the retry loop).

        With a live registry the step additionally runs under the
        per-seam ``StepTimer`` (``block_until_ready`` + wall clock into
        ``serve_step_seconds{seam=...}``) — pure observation: blocking
        changes when the host sees values, never what they are. A
        ``NullRegistry`` run skips the wrapper entirely."""
        if self.obs.step_timer.enabled:
            inner = fn
            fn = lambda: self.obs.step_timer.run(seam, inner)
        if self.inject is None:
            return fn()

        def step():
            self.inject.on_step(seam)
            return fn()

        return run_with_retries(step, max_retries=3, base_delay_s=0.0,
                                retriable=(RuntimeError,))

    def _draftable(self, r: Request) -> bool:
        """Drafting decision, frozen into ``r.draft_on`` at (re)admission:
        speculation needs at least one draftable step — ``kk = min(k,
        max_new - emitted - 1)`` positive for some future round. Fresh
        requests need ``max_new >= 3``; a replayed request re-decides from
        its emitted count (a nearly-finished victim re-admits as a plain
        verify-wave rider and never re-touches the draft cache)."""
        if self.drafter is None:
            return False
        return r.max_new - (len(r.out) or 1) >= 2

    def _common_prefix_pages(self, a: np.ndarray, b: np.ndarray) -> int:
        """Leading FULL pages on which two prompts are token-identical."""
        ps = self.page_size
        n = 0
        for j in range(min(len(a), len(b)) // ps):
            if not np.array_equal(a[j * ps:(j + 1) * ps],
                                  b[j * ps:(j + 1) * ps]):
                break
            n += 1
        return n

    def _select_for_slots(self, pending: list[Request],
                          free: list[int]) -> list[tuple[int, Request]]:
        """Pair free slots (in index order) with pending requests (in queue
        order), DEFERRING any request whose prompt shares more full pages
        with a not-yet-indexed request on its TARGET REPLICA (already
        active there, or chosen earlier this wave for it) than that
        replica's prefix index can currently serve: admitting it now would
        prefill the common prefix twice, because an index only learns a
        prompt once it is fully prefilled. Serializing just those requests
        turns same-wave duplicates into ordinary cache hits one wave later
        — the deferral resolves as soon as the overlapping request
        finishes prefilling (it is driven by the same run loop), so no
        deadlock. Prefix indexes are replica-local, so only same-replica
        duplicates defer; on a single replica this reduces exactly to the
        old single-index selection."""
        if self.prefixes is None:
            return list(zip(free, pending))
        by_rep: dict[int, list[int]] = {}
        for i in free:
            by_rep.setdefault(self._rep(i), []).append(i)
        # nothing mid-prefill to duplicate against: admit without probing —
        # the steady blocked-on-pool retry path (every active already
        # indexed) never re-hashes prompts
        unindexed: dict[int, list[Request]] = {r: [] for r in by_rep}
        for i, r in enumerate(self.active):
            if r is not None and not r.indexed and self._rep(i) in by_rep:
                unindexed[self._rep(i)].append(r)
        out: list[tuple[int, Request]] = []
        for req in pending:
            if not by_rep:
                break
            rep = min(by_rep, key=lambda r: by_rep[r][0])
            slot = by_rep[rep][0]
            others = unindexed[rep]
            if others:
                overlap = max(
                    self._common_prefix_pages(self._seq(req), self._seq(o))
                    for o in others
                )
                if overlap:
                    matched, _, _ = self.prefixes[rep].match(
                        self._seq(req), need_state=bool(self._recurrent),
                        record=False
                    )
                    if overlap * self.page_size > matched:
                        self.prefix_deferrals += 1
                        continue
            out.append((slot, req))
            unindexed[rep].append(req)
            by_rep[rep].pop(0)
            if not by_rep[rep]:
                del by_rep[rep]
        return out

    def _fill_slots(self, pending: list[Request]) -> int:
        """Admit waiting requests into free slots, then run one prefill
        wave. Returns the number of requests admitted (0 when the free-page
        budget is exhausted — callers wait for retirements — or when every
        pending candidate is deferred for cross-wave prefix dedup)."""
        free = [i for i in range(self.slots) if self.active[i] is None]
        if not free or not pending:
            return 0
        picked = self._select_for_slots(pending, free)
        if not picked:
            return 0
        # validate BEFORE mutating active/pending: a rejected request must
        # not strand its wave-mates admitted-but-never-prefilled
        for _, r in picked:
            if r.rid < 0:
                # the per-request sampling stream seeds from (seed, rid):
                # SeedSequence rejects negatives, and failing AFTER pages
                # are reserved would strand them assigned-but-unadmitted
                raise ValueError(f"request rid must be >= 0, got {r.rid}")
            if len(r.prompt) == 0:
                # lengths==0 means "frozen slot": an empty prompt would
                # skip the slot reset and decode the previous occupant
                raise ValueError(f"request {r.rid}: empty prompt")
            if r.max_new < 1:
                # max_new == 0 would under-reserve (prompt - 1 rows) while
                # prefill still writes the full prompt — in paged mode the
                # tail would scatter into a page owned by a live neighbour
                raise ValueError(f"request {r.rid}: max_new must be >= 1")
            # prefill writes len(seq) KV rows, decode the rest; this bound
            # is ALSO the deadlock-freedom anchor of on-demand growth: a
            # lone request's end-to-end need always fits the pool
            need = self._need_rows(r)
            if need > self.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt {len(self._seq(r))} + gen "
                    f"{r.max_new} needs {need} cache rows > "
                    f"max_len={self.max_len}"
                )
            if (self.paged
                    and pages_for(need, self.page_size)
                    > self.alloc.per_replica):
                raise ValueError(
                    f"request {r.rid}: needs "
                    f"{pages_for(need, self.page_size)} pages > per-replica "
                    f"pool size {self.alloc.per_replica}"
                )
        admitted = 0
        for i, req in picked:
            if self.paged:
                if not self._admit_paged(i, req):
                    break  # budget exhausted: the rest wait for retirements
            else:
                req.kv_reserved_bytes = self._kv_row_bytes
            if req.rng is None:
                # NOT reset on re-admission: a preempted request's sampling
                # stream continues where it stopped, so sampled streams
                # survive preemption exactly like greedy ones
                req.rng = np.random.default_rng([self._seed, req.rid])
            if req.seq_no < 0:
                # admission order, assigned ONCE: a replayed request keeps
                # its original seq_no, so re-admission restores (not
                # resets) its growth-exemption seniority
                req.seq_no = self._seq_counter
                self._seq_counter += 1
            if req.spilled:
                # preempt-to-disk re-admission: reload page contents from
                # the host store — no replay prefill recompute happens
                self._restore_spill(i, req)
            elif req.replay is not None:
                self.replays += 1
                self.replay_tokens += len(req.replay) - req.start_len
                self.tracer.replay(req.rid,
                                   len(req.replay) - req.start_len)
                self._tl("replay", rid=req.rid,
                         tokens=len(req.replay) - req.start_len)
                self.registry.counter(
                    "resilience_replays_total",
                    "preempted requests re-admitted via replay",
                ).inc(replica=self._rep(i))
            for qi, p in enumerate(pending):  # identity removal: Request
                if p is req:                  # __eq__ compares ndarrays
                    del pending[qi]
                    break
            self.active[i] = req
            req.status = "ok"
            self.tracer.admitted(req.rid, replica=self._rep(i),
                                 prefix_hit_tokens=req.start_len,
                                 pages=len(req.pages))
            req.draft_on = self._draftable(req)
            if req.draft_on:
                # draft high-water: one row less than the target's — the
                # drafter never ingests the final emitted token (absolute
                # positions, so replay does not change it)
                self.drafter.admit(i, len(req.prompt) + req.max_new - 2)
                if self.spec_floor > 0.0 and req.acc is None:
                    req.acc = AcceptanceWindow(self.spec_floor,
                                               self.spec_window)
            admitted += 1
        if admitted:
            self._prefill_wave()
        return admitted

    def _admit_paged(self, i: int, req: Request) -> bool:
        """Reserve pages for ``req`` in slot ``i``; False when the pool
        cannot host it yet (even after evicting cached prefixes).

        With the prefix cache on, the prompt is matched against the index
        first: matched pages are RETAINED (read-only, refcount + 1) into
        the slot's page table, only the unmatched tail is allocated fresh,
        and ``start_len``/``fed`` begin past the shared prefix. A
        full-prompt match on a page boundary rolls back one token (its
        logits must be recomputed to sample the first output) and
        copy-on-writes the boundary page, so the shared copy is never
        scattered into. Recurrent families additionally install the
        boundary's state snapshot in place of the slot reset.

        On-demand growth (``page_growth=True``) reserves only the pages
        the SEQUENCE (+ ``growth_headroom`` tokens) needs — the rest grow
        per decode tick via :meth:`_ensure_rows` — so the same pool
        admits more concurrent requests than full reservation."""
        seq = self._seq(req)
        rep = self._rep(i)
        # a spilled request restores by OVERWRITING its pages with store
        # contents, so it must own every page exclusively: never retain
        # shared prefix pages for it
        prefix = None if req.spilled else self._prefix_of(i)
        np_need = pages_for(self._need_rows(req), self.page_size)
        if self.page_growth:
            goal = max(
                pages_for(min(self._need_rows(req),
                              len(seq) + self.growth_headroom),
                          self.page_size),
                pages_for(len(seq), self.page_size),  # always hold the seq
            )
        else:
            goal = np_need
        shared_tok, shared_pages, state = 0, [], None
        if prefix is not None:
            # dry-run probe: stats count and LRU move only when admission
            # actually commits (this path retries every scheduler step
            # while blocked on the pool)
            shared_tok, shared_pages, state = prefix.match(
                seq, need_state=bool(self._recurrent), record=False
            )
        m = len(shared_pages)
        rollback = m > 0 and shared_tok == len(seq)
        # fresh pages = unmatched tail (+1 when the boundary page is COWed)
        fresh_needed = goal - m + (1 if rollback else 0)
        if m:
            # retain BEFORE any eviction: matched pages must stay live even
            # if eviction drops their index entries
            self.alloc.retain(shared_pages)
        if not self.alloc.can_alloc(fresh_needed, rep):
            if prefix is None or not prefix.evict_for(fresh_needed):
                if m:
                    self.alloc.free(shared_pages)  # undo; retry after retire
                return False
        tail = self.alloc.alloc(goal - m, rep)
        if prefix is not None:
            prefix.record(seq, shared_tok)  # admission commits
        req.pages = shared_pages + tail
        req.start_len = shared_tok - (1 if rollback else 0)
        req.fed = req.start_len
        self._table[i, : len(req.pages)] = req.pages
        self._table_dirty = True
        self.pages_allocated += goal - m
        req.kv_reserved_bytes = (goal - m) * self._page_bytes
        if rollback:
            # the re-run token writes into the last SHARED page: make this
            # slot its exclusive writer first
            self._cow(i, req, req.start_len // self.page_size)
        if state is not None:
            # recurrent prefix: install the boundary snapshot instead of
            # resetting the slot (the wave treats the row as mid-prompt)
            self._install_state(i, state, req.start_len)
            req.preloaded = True
        return True

    def _cow(self, i: int, req: Request, logical_page: int) -> None:
        """Copy-on-write slot ``i``'s ``logical_page`` if it is shared:
        fresh page, device copy of the contents, page-table swap. No-op for
        pages this request already exclusively owns."""
        old = int(self._table[i, logical_page])
        new, copied = self.alloc.cow(old)
        if not copied:
            return
        for key in ("pages", "shared_pages"):
            if key in self.cache:
                self.cache[key] = copy_page(self.cache[key], old, new)
        req.pages[req.pages.index(old)] = new
        self._table[i, logical_page] = new
        self._table_dirty = True
        self.pages_allocated += 1
        req.kv_reserved_bytes += self._page_bytes

    def _cow_guard(self, i: int, req: Request, start: int, n: int) -> None:
        """Enforce the no-shared-writer invariant for a write of ``n``
        tokens at logical positions ``[start, start + n)``: any touched
        page still shared gets copy-on-written before the wave runs. After
        admission this never fires (the boundary COW already ran) — it is
        the structural guarantee, not a hot path."""
        if self.prefixes is None or n <= 0:
            return
        for lp in range(start // self.page_size,
                        (start + n - 1) // self.page_size + 1):
            self._cow(i, req, lp)

    def _install_state(self, i: int, state: dict, start_len: int) -> None:
        """Write a cached recurrent-state snapshot (and the matching fill
        length) into slot ``i``'s cache rows. Admission-path host update —
        off the jitted hot loop."""
        for k, v in state.items():
            self.cache[k] = self.cache[k].at[:, i].set(jnp.asarray(v))
        self.cache["len"] = self.cache["len"].at[i].set(
            jnp.int32(start_len)
        )

    def _index_prompt(self, i: int, req: Request) -> None:
        """Register a fully prefilled prompt's full pages in slot ``i``'s
        replica-local prefix index (with any recurrent boundary snapshots
        captured en route). A replayed sequence indexes like a prompt —
        its full pages are as reusable (and a future replay of the same
        request hits them)."""
        prefix = self._prefix_of(i)
        if prefix is None or req.indexed:
            return
        req.indexed = True
        prefix.insert(self._seq(req), req.pages, states=req.snaps or None)
        req.snaps = {}

    def _retire(self, i: int, req: Request, done: list[Request]):
        done.append(req)
        self.active[i] = None
        if self.paged:
            self.alloc.free(req.pages)
            self._table[i] = 0  # cosmetic: stale ids are unreachable anyway
            self._table_dirty = True
        if self.drafter is not None:
            self.drafter.release(i)  # idempotent; usually already released
        if self.spill is not None and req.spilled:
            # defensive: an active request was restored (spilled cleared),
            # but never leave a retired rid's file behind
            self.spill.drop(req.rid)
            req.spilled = False
        self.tracer.retire(req.rid, req.status, registry=self.registry)
        self.registry.counter(
            "serve_requests_total", "requests retired, by final status",
        ).inc(status=req.status, replica=self._rep(i))

    # -- preemption / on-demand growth (see runtime.resilience) -------------

    def _preempt(self, i: int, req: Request) -> None:
        """Evict ``req`` from slot ``i`` mid-flight: release its pages
        (shared prefix pages are never victim-released — they only lose
        this owner's reference, see ``PageAllocator.free``), invalidate
        its draft state, and requeue it at the FRONT of the pending queue
        with a replay sequence that restores it exactly.

        With a spill store attached, an eligible victim's page contents
        are snapshotted to the host FIRST (before the pages are freed):
        re-admission then restores by page reload instead of replaying
        the sequence through prefill. The replay sequence is still built
        either way — it is the length/readiness contract the scheduler
        reasons with, and the recompute fallback if the store is gone."""
        req.spilled = self._maybe_spill(i, req)
        req.replay = replay_sequence(req.prompt, req.out)
        req.fed = 0
        req.dfed = 0
        req.start_len = 0
        req.preloaded = False
        req.indexed = False
        req.snaps = {}
        req.preemptions += 1
        self.preemptions += 1
        self.alloc.free(req.pages)
        req.pages = []
        self._table[i] = 0
        self._table_dirty = True
        if self.drafter is not None:
            self.drafter.release(i)
        self.active[i] = None
        self._pending.insert(0, req)
        self.tracer.preempted(req.rid)
        self._tl("preempt", rid=req.rid, emitted=len(req.out))
        self.registry.counter(
            "resilience_preemptions_total",
            "victim preemptions on pool pressure",
        ).inc(replica=self._rep(i))
        # structural guarantee, not a hot path: preemption is the one op
        # that frees pages other parties may still reference
        self.alloc.audit()
        if self.prefixes is not None:
            for p in self.prefixes:
                p.audit()

    def _maybe_spill(self, i: int, req: Request) -> bool:
        """Snapshot slot ``i``'s KV page contents (and recurrent state
        rows) into the host spill store, if ``req`` is eligible: a spill
        store is attached, the request is fully prefilled and decoding
        (mid-prefill victims have nothing worth saving — their replay IS
        the remaining prefill), and the context has at least
        ``spill_threshold`` rows (short contexts replay cheaply). Must run
        BEFORE the allocator frees the pages."""
        if self.spill is None or not req.out:
            return False
        if req.fed < len(self._seq(req)):
            return False
        # rows the cache holds for a caught-up decoder == len(replay):
        # prompt + emitted[:-1] (the final token is re-fed, not stored)
        rows = len(req.prompt) + len(req.out) - 1
        if rows < self.spill_threshold:
            return False
        ids = req.pages[: pages_for(rows, self.page_size)]
        payload = {"rows": np.int32(rows)}
        for key in ("pages", "shared_pages"):
            if key in self.cache:
                payload[f"pool.{key}"] = np.asarray(
                    read_pages(self.cache[key], ids))
        for key in self._recurrent:
            payload[f"state.{key}"] = np.asarray(self.cache[key][:, i])
        self.spill.spill(req.rid, payload)
        self.spills += 1
        self._tl("spill", rid=req.rid, rows=rows, pages=len(ids))
        self.registry.counter(
            "resilience_spills_total",
            "preempted contexts spilled to the host store",
        ).inc(replica=self._rep(i))
        return True

    def _restore_spill(self, i: int, req: Request) -> None:
        """Reload a spilled context into slot ``i``'s freshly allocated
        pages: page contents scatter back by physical id, recurrent state
        rows reinstall, and the slot's fill length jumps straight to the
        stored row count — the request is decode-ready without a single
        replay prefill forward (the next decode step re-feeds ``out[-1]``
        exactly as it would after any other wave)."""
        payload = self.spill.restore(req.rid)
        rows = int(payload["rows"])
        ids = req.pages[: pages_for(rows, self.page_size)]
        for key in ("pages", "shared_pages"):
            if key in self.cache:
                self.cache[key] = write_pages(self.cache[key], ids,
                                              payload[f"pool.{key}"])
        for key in self._recurrent:
            self.cache[key] = self.cache[key].at[:, i].set(
                jnp.asarray(payload[f"state.{key}"]))
        self.cache["len"] = self.cache["len"].at[i].set(jnp.int32(rows))
        req.fed = rows  # fully "prefilled": no wave will pick this row up
        # the restored sequence is never re-walked by a prefill wave, so
        # it can never be indexed — mark it so dedup does not wait on it
        req.indexed = True
        req.spilled = False
        self.spill.drop(req.rid)
        self.spill_restores += 1
        self._tl("restore", rid=req.rid, rows=rows, pages=len(ids))
        self.registry.counter(
            "resilience_spill_restores_total",
            "spilled contexts restored by page reload",
        ).inc(replica=self._rep(i))

    def _preempt_one(self, rep: int = 0) -> Request | None:
        """Preempt the policy victim WITHIN replica ``rep`` (lowest
        priority, then youngest, then latest-admitted; the replica's
        oldest live request is always exempt — the deadlock-freedom
        anchor holds per page pool, since preempting a neighbour in
        another replica would relieve nothing). Returns the victim, or
        None when only the exempt request remains."""
        live = [(i, r) for i, r in enumerate(self.active)
                if r is not None and self._rep(i) == rep]
        if len(live) <= 1:
            return None
        exempt = min(r.seq_no for _, r in live)
        pick = pick_victim(live, exempt)
        if pick is None:
            return None
        vi, victim = pick
        self._preempt(vi, victim)
        return victim

    def _ensure_rows(self, i: int, req: Request, rows: int, *,
                     preempt: bool = True) -> bool:
        """Grow slot ``i``'s page list to cover ``rows`` KV rows.

        Relief order on exhaustion: prefix-cache eviction first (free
        capacity, no one loses work), then victim preemption. Returns
        False when the request cannot proceed THIS tick — it was itself
        chosen as the victim, or relief is exhausted/disabled
        (``preempt=False`` is the degradation probe: speculative headroom
        is not worth preempting a neighbour for). A False from a
        non-probe call just skips the row for one tick; retirement or
        relief unblocks it later, and greedy streams are invariant to the
        skipped tick.

        The injector's forced ``oop`` fires here (non-probe calls only)
        and preempts a victim even when the pool could serve the need —
        that is what makes chaos-test preemptions land at exact ticks."""
        need = pages_for(rows, self.page_size) - len(req.pages)
        rep = self._rep(i)
        prefix = self._prefix_of(i)
        if (preempt and self.inject is not None
                and self.inject.take("oop")):
            if not self.preemption:
                return False  # behave like unrelieved exhaustion: skip
            self._preempt_one(rep)
            if self.active[i] is not req:
                return False  # the requester itself was the chosen victim
        if need <= 0:
            return True
        while not self.alloc.can_alloc(need, rep):
            if prefix is not None and prefix.evict_for(need):
                break
            if not (preempt and self.preemption):
                return False
            if self._preempt_one(rep) is None:
                return False  # only the exempt oldest remains
            if self.active[i] is not req:
                return False
        grown = self.alloc.alloc(need, rep)
        self._table[i, len(req.pages): len(req.pages) + need] = grown
        req.pages.extend(grown)
        self._table_dirty = True
        self.pages_allocated += need
        req.kv_reserved_bytes += need * self._page_bytes
        return True

    def _draft_prefill_wave(self) -> bool:
        """Mirror prefill into the DRAFT cache: the drafter scores
        continuations of the same prompt, so it must ingest the prompt too
        (always from position 0 — a target-side prefix hit shares no pages
        with the draft pool). Runs on the same wave cadence as the target
        prefill; logits are discarded."""
        if self.drafter is None:
            return False
        rows = [(i, r) for i, r in enumerate(self.active)
                if r is not None and r.draft_on
                and r.dfed < len(self._seq(r))]
        if not rows:
            return False
        chunk = self.prefill_chunk or self.max_len
        sizes = {i: min(chunk, len(self._seq(r)) - r.dfed) for i, r in rows}
        lb = min(_bucket(max(sizes.values()), self.bucket_min), self.max_len)
        tokens = np.zeros((self.slots, lb), np.int32)
        lengths = np.zeros((self.slots,), np.int32)
        fresh = np.zeros((self.slots,), bool)
        fed_after: dict[int, int] = {}
        for i, r in rows:
            c = sizes[i]
            tokens[i, :c] = self._seq(r)[r.dfed : r.dfed + c]
            lengths[i] = c
            fresh[i] = r.dfed == 0
            r.dfed += c
            fed_after[i] = r.dfed
        self.drafter.prefill_wave(tokens, lengths, fresh, fed_after)
        self._tl("draft_prefill", rows=len(rows))
        return True

    def _prefill_wave(self) -> bool:
        """ONE batched prefill advancing every mid-prompt row by one chunk
        (the whole remaining prompt when ``prefill_chunk == 0``). Rows whose
        prompt completes get their first token sampled from this wave's
        logits at their own last real position."""
        drafted = self._draft_prefill_wave()
        rows = [(i, r) for i, r in enumerate(self.active)
                if r is not None and r.fed < len(self._seq(r))]
        if not rows:
            return drafted
        if any(r.replay is not None for _, r in rows):
            # replay-restore recompute (spill-restored requests never
            # enter a wave — the spill tier's whole point)
            self.recompute_forwards += 1
        chunk = self.prefill_chunk or self.max_len
        sizes = {}
        for i, r in rows:
            c = min(chunk, len(self._seq(r)) - r.fed)
            if self._snap_boundaries:
                # recurrent prefix caching: cap the wave at the next page
                # boundary so its state can be snapshotted for the index
                c = min(c, (r.fed // self.page_size + 1) * self.page_size
                        - r.fed)
            sizes[i] = c
        lb = min(_bucket(max(sizes.values()), self.bucket_min), self.max_len)
        self.buckets_used.append(lb)
        tokens = np.zeros((self.slots, lb), np.int32)
        lengths = np.zeros((self.slots,), np.int32)
        fresh = np.zeros((self.slots,), bool)
        starts = np.zeros((self.slots,), np.int32)
        for i, r in rows:
            c = sizes[i]
            tokens[i, :c] = self._seq(r)[r.fed : r.fed + c]
            lengths[i] = c
            # first wave of a request resets the slot — unless its state
            # was preloaded from the prefix index at admission
            fresh[i] = r.fed == r.start_len and not r.preloaded
            starts[i] = r.start_len
            if self.paged:
                self._cow_guard(i, r, r.fed, c)
            r.fed += c
            self.prefill_tokens += c
        self._sync_table()

        def _wave():
            return self._prefill(
                self.params, self._put(tokens), self._put(lengths),
                self._put(fresh), self._put(starts), self.cache,
            )

        t0 = _now()
        logits, self.cache = self._call("prefill", _wave)
        t1 = _now()
        self._tl("prefill", bucket=lb, rows=len(rows),
                 tokens=int(sum(sizes.values())))
        if self._snap_boundaries:
            for i, r in rows:
                if (not r.indexed and r.fed > 0
                        and r.fed % self.page_size == 0
                        and r.fed not in r.snaps):
                    r.snaps[r.fed] = {
                        k: np.asarray(self.cache[k][:, i])
                        for k in self._recurrent
                    }
        pick = self._pick_tokens(logits)
        for i, r in rows:
            before = len(r.out)
            if r.fed == len(self._seq(r)):
                self._index_prompt(i, r)
                if not r.out:
                    # replayed requests skip this: their first token(s)
                    # were emitted before preemption — the replay tail's
                    # logits would re-derive out[-1], which the next
                    # decode step re-feeds instead
                    self._emit(r, pick(i))
            self._span(i, r, "prefill", t0, t1, before, fed=sizes[i])
        return True

    def step(self) -> bool:
        """One decode step for all decode-ready slots; finished, empty and
        mid-prefill slots are masked out (no cache write, no length
        advance). In growth mode each ready row first secures the page its
        write lands in (:meth:`_ensure_rows`) — a row whose growth fails,
        or that gets preempted by a NEIGHBOUR'S growth, sits the tick out
        (greedy streams are invariant to the skipped tick)."""
        ready = [(i, r) for i, r in enumerate(self.active)
                 if (r is not None and not r.done and r.out
                     and r.fed == len(self._seq(r)))]
        grown: dict[int, bool] = {}
        if self.paged:
            for i, r in ready:
                if self.active[i] is not r:
                    continue  # preempted by an earlier row's growth
                # decode writes ONE row at len(prompt) + len(out) - 1
                grown[i] = self._ensure_rows(i, r,
                                             len(r.prompt) + len(r.out))
        tokens = np.zeros((self.slots, 1), np.int32)
        active = np.zeros((self.slots,), bool)
        for i, r in ready:
            if self.active[i] is not r:
                continue  # a LATER row's growth preempted this one
            if self.paged and not grown.get(i, False):
                continue
            tokens[i, 0] = r.out[-1]
            active[i] = True
            if self.paged:
                # COW if the write page is somehow still shared
                # (post-admission invariant: it never is)
                self._cow_guard(i, r, len(r.prompt) + len(r.out) - 1, 1)
        if not active.any():
            return False
        self._sync_table()

        def _step():
            return self._decode(
                self.params, self._put(tokens), self.cache,
                active=self._put(active),
            )

        t0 = _now()
        logits, self.cache = self._call("decode", _step)
        t1 = _now()
        self._tl("decode", rows=int(active.sum()))
        if self._probe_due():
            rows_host = np.asarray(logits[:, 0])
            for i, r in enumerate(self.active):
                if active[i]:
                    self._probe_row(r, rows_host[i])
        pick = self._pick_tokens(logits)
        for i, r in enumerate(self.active):
            if active[i]:
                before = len(r.out)
                self._emit(r, pick(i))
                self._span(i, r, "decode", t0, t1, before)
        return True

    def _spec_ready(self, i: int, r: Request | None) -> bool:
        """Decode-ready for a speculative round: target prompt fully
        prefilled AND (for drafting requests) the draft cache too — a
        prefix-cache hit can finish the target's prefill first, in which
        case the request waits a wave for its drafter rather than decode
        un-drafted."""
        if r is None or r.done or not r.out or r.fed < len(self._seq(r)):
            return False
        if r.draft_on and r.dfed < len(self._seq(r)):
            return False
        return True

    def _spec_round(self) -> bool:
        """One draft -> verify -> accept/rollback round for every
        decode-ready slot (spec mode's replacement for :meth:`step`).

        Each drafting slot proposes ``kk = min(k, remaining - 1)`` tokens
        (clamped so the verify chunk NEVER writes past the request's
        standard page reservation — speculation needs no extra pages);
        slots out of draft budget ride the same verify wave as plain
        single-token rows, so the target model runs exactly ONE forward
        per round regardless of the mix, and ``decode_step`` is never
        traced in spec mode."""
        rows = [(i, r) for i, r in enumerate(self.active)
                if self._spec_ready(i, r)]
        if not rows:
            return False
        greedy = self.sampling["temperature"] <= 0.0
        deg0 = self.spec.degraded_rounds
        # capacity + degradation phase BEFORE any drafting: decide each
        # row's draft budget under pool pressure / acceptance history
        kks: dict[int, int] = {}
        for i, r in rows:
            if self.active[i] is not r:
                continue  # preempted by an earlier row's growth
            kk = (min(self.speculate, r.max_new - len(r.out) - 1)
                  if r.draft_on else 0)
            if kk > 0 and r.acc is not None and r.acc.degraded():
                # persistent drafter divergence: decode plainly this round;
                # aging the window lets drafting re-probe later
                r.acc.age()
                kk = 0
                self.spec.degraded_rounds += 1
            base_rows = len(r.prompt) + len(r.out)  # plain width-1 write
            if self.paged and kk > 0:
                if not self._ensure_rows(i, r, base_rows + kk,
                                         preempt=False):
                    # pool pressure: speculative HEADROOM is not worth
                    # preempting a neighbour — fall back to plain decode
                    # for this round
                    kk = 0
                    self.spec.degraded_rounds += 1
            if self.paged and not self._ensure_rows(i, r, base_rows + kk):
                continue  # preempted or blocked: sits this round out
            kks[i] = kk
        rows = [(i, r) for i, r in rows
                if self.active[i] is r and i in kks]
        if not rows:
            return False
        jobs = []
        for i, r in rows:
            if kks[i] > 0:
                jobs.append((
                    i,
                    np.concatenate([r.prompt,
                                    np.asarray(r.out, np.int32)]),
                    kks[i],
                ))
        drafts: dict[int, list[int]] = {i: [] for i, _ in rows}
        qdists: dict[int, np.ndarray] = {}
        if jobs:
            d, q = self.drafter.draft_round(
                jobs, sampling=self.sampling,
                rngs={i: self.active[i].rng for i, _, _ in jobs},
            )
            drafts.update(d)
            qdists.update(q)
        # one verify forward scores every row's chunk at once
        width = self.speculate + 1
        tokens = np.zeros((self.slots, width), np.int32)
        lengths = np.zeros((self.slots,), np.int32)
        base = np.zeros((self.slots,), np.int32)
        for i, r in rows:
            di = drafts[i]
            base[i] = len(r.prompt) + len(r.out) - 1
            tokens[i, 0] = r.out[-1]
            tokens[i, 1 : 1 + len(di)] = di
            lengths[i] = 1 + len(di)
            if self.paged:
                self._cow_guard(i, r, int(base[i]), 1 + len(di))
        self._sync_table()

        probe_now = self._probe_due()

        def _score():
            return self.verifier.score(self.cache, tokens, lengths,
                                       greedy=greedy,
                                       keep_logits0=probe_now)

        t0 = _now()
        scores, self.cache, snap = self._call("verify", _score)
        t1 = _now()
        self._tl("verify", rows=len(rows), drafting=len(jobs),
                 k=self.speculate,
                 degraded=self.spec.degraded_rounds - deg0)
        if probe_now:
            # position 0 of the verify chunk is the target distribution
            # after the last emitted token — the same quantity step()
            # probes in plain mode
            logits0 = self.verifier.last_logits0
            for i, r in rows:
                self._probe_row(r, logits0[i])
        self.spec.rounds += 1
        self.spec.target_forwards += 1
        # host-side acceptance per request, then one batched rollback
        new_lens = base + lengths  # post-verify lens
        rejected = np.zeros((self.slots,), bool)
        verdicts: dict[int, int] = {}
        emits: dict[int, int] = {}
        for i, r in rows:
            di = drafts[i]
            if greedy:
                m, tok = accept_greedy(di, scores[i])
            else:
                p = np.stack([
                    shaped_probs(scores[i, j], **self.sampling)
                    for j in range(len(di) + 1)
                ])
                m, tok = accept_speculative(di, qdists.get(i), p, r.rng)
            self.spec.drafted += len(di)
            self.spec.accepted += m
            if len(di) and self.registry.enabled:
                self.registry.counter(
                    "spec_drafted_total", "draft tokens proposed",
                ).inc(len(di), replica=self._rep(i))
                if m:
                    self.registry.counter(
                        "spec_accepted_total",
                        "draft tokens that survived verification",
                    ).inc(m, replica=self._rep(i))
            if r.acc is not None and len(di):
                r.acc.record(len(di), m)
            if kks[i] > 0:
                verdicts[i] = m
            if m < len(di):  # rejected suffix: un-write it
                rejected[i] = True
                new_lens[i] = base[i] + m + 1
            emits[i] = tok
        if rejected.any():
            self.cache = self.verifier.rollback(
                self.cache, snap, base, new_lens, rejected, tokens
            )
            if self._recurrent:
                self.spec.recompute_forwards += 1
                self.spec.target_forwards += 1
        if verdicts:
            self.drafter.finish_round(verdicts)
        for i, r in rows:
            before = len(r.out)
            for t in drafts[i][: verdicts.get(i, 0)]:
                self._emit(r, t)
                self.spec.emitted += 1
            self._emit(r, emits[i])
            self.spec.emitted += 1
            self._span(i, r, "verify", t0, t1, before,
                       drafted=len(drafts[i]),
                       accepted=verdicts.get(i, 0))
            if r.draft_on and r.max_new - len(r.out) - 1 <= 0:
                # out of draft budget: the drafter is done with this slot
                # one round before the target retires — release its pages
                self.drafter.release(i)
        return True

    def _stall(self) -> SchedulerStall:
        """Build the diagnostic stall exception from live-slot state."""
        diags = []
        for i, r in enumerate(self.active):
            if r is None:
                continue
            pend = 0
            if self.paged:
                # end-of-decode row need, valid mid-flight (``_need_rows``
                # is admission-time: it folds emitted tokens into the
                # replay sequence, which a live slot hasn't built)
                end_rows = len(r.prompt) + r.max_new - 1
                pend = max(pages_for(end_rows, self.page_size)
                           - len(r.pages), 0)
            diags.append(SlotDiag(
                slot=i, rid=r.rid, seq_len=len(self._seq(r)), fed=r.fed,
                emitted=len(r.out), max_new=r.max_new,
                pages_held=len(r.pages), pages_pending=pend,
            ))
        return SchedulerStall(
            diags, self.alloc.free_pages if self.paged else None,
            recent=self.timeline.tail(8))

    def _slo_tick(self) -> None:
        """Close the SLO loop once per decode tick: hand the tracer's
        token-granular TTFT/TPOT observations to the controller and apply
        whatever it decides — a new chunked-prefill budget (greedy streams
        are chunk-invariant, so retuning live never changes tokens) and/or
        a raised speculative acceptance floor (live requests' trailing
        windows pick it up immediately)."""
        if self.slo is None:
            return
        for kind, seconds in self.tracer.drain_observations():
            self.slo.observe(kind, seconds)
        chunk, floor = self.slo.tick()
        if chunk != self.prefill_chunk:
            self.prefill_chunk = chunk
            self.slo_adjustments += 1
            self._tl("slo", chunk=chunk, floor=round(floor, 4))
            if self.registry.enabled:
                self.registry.gauge(
                    "slo_prefill_chunk",
                    "SLO-tuned chunked-prefill budget",
                ).set(chunk)
        if floor != self.spec_floor:
            self.spec_floor = floor
            self.slo_adjustments += 1
            for r in self.active:
                if r is not None and r.acc is not None:
                    r.acc.floor = floor

    def _drain_due(self, t0: float) -> bool:
        if self.guard is not None and self.guard.requested:
            return True
        return bool(self.max_wall_s) and time.time() - t0 > self.max_wall_s

    def _drain(self, done: list[Request]) -> None:
        """Graceful shutdown: the current wave already finished (checked
        at the loop top), so live requests retire with their partial
        streams (tokens were streamed via ``on_token`` as they decoded)
        under ``status='preempted'``; nothing new is admitted; every page
        is freed — a drained server must leak nothing."""
        self.drained = True
        for i, r in enumerate(self.active):
            if r is None:
                continue
            if not r.done:  # a finished row retires normally, status "ok"
                r.status = "preempted"
                r.done = True
            self._retire(i, r, done)
        for r in self._pending:
            r.status = "preempted"
            if r.spilled and self.spill is not None:
                # a spilled-but-never-restored context: its file would
                # orphan (zero spill files after a drain is an invariant)
                self.spill.drop(r.rid)
                r.spilled = False
        self._tl("drain", unserved=len(self._pending))

    def run(self, requests: list[Request],
            on_token: Callable[[Request, int], None] | None = None, *,
            feed: Callable[[], list[Request]] | None = None,
            idle_wait_s: float = 0.002) -> dict:
        """Serve ``requests`` to completion. ``on_token(request, token)``
        streams each decoded token to the caller as it is sampled.

        ``feed`` turns the batch loop into a SERVICE loop: it is polled
        every scheduler iteration for newly admitted requests (the asyncio
        front-end hands it ``FairScheduler.drain``), and an idle server
        waits ``idle_wait_s`` instead of exiting — the run then ends only
        through the drain path (SIGTERM guard or ``max_wall_s``)."""
        if self.speculate and any(r.force is not None for r in requests):
            raise ValueError(
                "teacher forcing (Request.force) is incompatible with "
                "speculative decoding: drafts would verify against the "
                "model's own continuation, not the forced one")
        self._on_token = on_token
        self._pending = list(requests)
        for r in self._pending:
            self.tracer.queued(r.rid, r.queued_t)
        done: list[Request] = []
        steps = 0
        t0 = time.time()
        try:
            while True:
                # decode-step counter = the chaos tick clock AND the
                # timeline/profiler tick clock
                self.timeline.set_tick(steps)
                if self.inject is not None:
                    self.inject.set_tick(steps)
                if self.profile is not None:
                    self.profile.on_tick(steps)
                if feed is not None:
                    for r in feed():
                        self._pending.append(r)
                        self.tracer.queued(r.rid, r.queued_t)
                if self._drain_due(t0):
                    self._drain(done)
                    break
                # retire finished slots — including requests whose single
                # token came straight from the previous prefill wave
                for i, r in enumerate(self.active):
                    if r is not None and r.done:
                        self._retire(i, r, done)
                if self._pending and any(s is None for s in self.active):
                    if self._fill_slots(self._pending):
                        self.peak_concurrency = max(
                            self.peak_concurrency,
                            sum(1 for r in self.active if r is not None),
                        )
                        continue  # retire prefill-finished, refill more
                self.peak_concurrency = max(
                    self.peak_concurrency,
                    sum(1 for r in self.active if r is not None),
                )
                # interleave: one chunk of prompt feeding, then one decode
                # step — a long prompt never stalls ongoing decodes
                fed = self._prefill_wave()
                stepped = (self._spec_round() if self.speculate
                           else self.step())
                if stepped:
                    steps += 1
                    self._slo_tick()
                if fed or stepped:
                    continue
                if any(r is not None and r.done for r in self.active):
                    continue  # retire at loop top
                if any(r is not None for r in self.active):
                    raise self._stall()
                if self._pending:
                    continue  # slots all free: next _fill_slots admits
                if feed is not None:
                    # service mode: idle is not done — wait for traffic
                    # until the guard/wall-clock drain says stop
                    time.sleep(idle_wait_s)
                    continue
                break
        finally:
            self._on_token = None
            if self.profile is not None:
                self.profile.stop()
        dt = time.time() - t0
        return self._build_stats(done, steps, dt)

    def _build_stats(self, done: list[Request], steps: int,
                     dt: float) -> dict:
        """THE stats builder: one registry-backed assembly of the
        end-of-run stats dict (CLI, bench and tests all read this shape)
        that simultaneously files the same numbers into the metrics
        registry — the dict and ``Registry.snapshot()`` can never
        disagree because they are built from one pass."""
        toks = sum(len(r.out) for r in done)
        cc = compile_counts(prefill=self._prefill, decode=self._decode)
        stats = {
            "requests": len(done), "tokens": toks, "seconds": dt,
            "tok_per_s": toks / max(dt, 1e-9), "decode_steps": steps,
            "prefill_waves": len(self.buckets_used),
            "prefill_buckets": sorted(set(self.buckets_used)),
            "prefill_compiles": cc["prefill"],
            "decode_compiles": cc["decode"],
            "prefill_tokens": self.prefill_tokens,
        }
        stats["resilience"] = {
            "page_growth": self.page_growth,
            "preemptions": self.preemptions,
            "replays": self.replays,
            "replay_tokens": self.replay_tokens,
            "degraded_rounds": (self.spec.degraded_rounds
                                if self.spec else 0),
            "peak_concurrency": self.peak_concurrency,
            "drained": self.drained,
            "preempted_requests": sum(1 for r in done
                                      if r.status == "preempted"),
            "unserved": len(self._pending),
            "spills": self.spills,
            "spill_restores": self.spill_restores,
            "recompute_forwards": self.recompute_forwards,
        }
        if self.spill is not None:
            stats["resilience"]["spill_store"] = self.spill.stats()
        if self.slo is not None:
            stats["slo"] = {
                "adjustments": self.slo_adjustments,
                "chunk": self.prefill_chunk,
                "spec_floor": self.spec_floor,
                "ticks": self.slo.ticks,
                "history": list(self.slo.history)[-32:],
            }
        if self.inject is not None:
            stats["resilience"]["injected"] = self.inject.summary()
        if self.paged:
            self.alloc.audit()  # end-of-run structural check
        if done:
            reserved = [r.kv_reserved_bytes for r in done]
            stats["kv_bytes_reserved_per_request"] = {
                "mean": int(np.mean(reserved)), "max": int(max(reserved)),
            }
        if self.paged:
            cached = (sum(p.pages_held for p in self.prefixes)
                      if self.prefixes else 0)
            stats["pages"] = {
                **self.alloc.stats(),
                "page_size": self.page_size,
                "pages_allocated": self.pages_allocated,
                "prefix_cached": cached,
                # pages held past retirement are LEAKED unless the prefix
                # cache deliberately holds them (drop_prefix_cache releases
                # those and must return the pool to zero in use)
                "leaked": self.alloc.in_use - cached,
            }
            if self.prefixes is not None:
                stats["prefix"] = self._prefix_stats()
                stats["prefix"]["admission_deferrals"] = self.prefix_deferrals
        if self._plan is not None:
            stats["mesh"] = {
                "data": self._plan.n_data,
                "model": self._plan.n_model,
                "devices": self._plan.n_data * self._plan.n_model,
            }
            if self.paged:
                # peak KV bytes each DP replica's page pool committed —
                # the per-device memory bill the mesh run actually pays
                stats["mesh"]["kv_reserved_bytes_per_replica"] = [
                    a.peak_in_use * self._page_bytes
                    for a in self.alloc.pools
                ]
        if self.speculate:
            self.spec.draft_forwards = self.drafter.forwards
            stats["spec"] = {
                **self.spec.summary(),
                "verify_compiles": self.verifier.compiles,
                "draft_compiles": self.drafter.compiles(),
                # the draft pool must drain like the target pool: a draft
                # page alive after every request retired is a real leak
                "draft_pages_leaked": self.drafter.alloc.in_use,
            }
        if self.quality_probe:
            stats["probe"] = {
                "every": self.quality_probe,
                "samples": self.probe_samples,
                "top1_agreements": self.probe_agreements,
                "top1_agreement_rate": (
                    self.probe_agreements / max(self.probe_samples, 1)
                ),
            }
        self._export_metrics(stats, cc)
        stats["obs"] = {
            "trace_events": self.timeline.seq,
            "trace_dropped": self.timeline.dropped,
            "requests": self.tracer.summary(),
            "step_time": self.obs.step_timer.summary(),
        }
        return stats

    def _export_metrics(self, stats: dict, cc: dict) -> None:
        """File the end-of-run scheduler/pool/prefix/spec state into the
        registry as gauges (event-shaped metrics — tokens, requests,
        preemptions, faults — were already counted live where they
        happened). No-ops wholesale under a ``NullRegistry``."""
        reg = self.registry
        if not reg.enabled:
            return
        g = reg.gauge
        g("serve_decode_ticks", "decode/verify rounds run").set(
            stats["decode_steps"])
        g("serve_prefill_waves", "batched prefill waves run").set(
            stats["prefill_waves"])
        g("serve_prefill_tokens", "tokens fed through prefill").set(
            stats["prefill_tokens"])
        g("serve_tok_per_s", "end-of-run decode throughput").set(
            stats["tok_per_s"])
        compiles = dict(cc)
        if self.speculate:
            compiles["verify"] = self.verifier.compiles
            for k, v in self.drafter.compiles().items():
                compiles[f"draft_{k}"] = v
        for step, n in compiles.items():
            g("serve_jit_compiles",
              "compilation-cache size per jitted step").set(n, step=step)
        res = stats["resilience"]
        g("resilience_peak_concurrency",
          "most slots simultaneously live").set(res["peak_concurrency"])
        g("resilience_degraded_rounds",
          "spec rounds decoded plainly under pressure").set(
            res["degraded_rounds"])
        g("resilience_drained", "1 when the run ended by drain").set(
            int(res["drained"]))
        if self.paged:
            for r, a in enumerate(self.alloc.pools):
                ps = a.stats()
                g("kv_pages_in_use", "pool pages held, per replica").set(
                    ps["in_use"], replica=r)
                g("kv_pages_free", "pool pages free, per replica").set(
                    ps["free"], replica=r)
                g("kv_pages_peak", "peak pool pages held").set(
                    ps["peak_in_use"], replica=r)
                g("kv_pool_fragmentation",
                  "free-list discontiguity, 0..1").set(
                    ps["fragmentation"], replica=r)
                g("kv_cow_copies", "copy-on-write page copies").set(
                    ps["cow_copies"], replica=r)
            g("kv_pages_allocated",
              "fresh pages allocated (incl. COW copies)").set(
                self.pages_allocated)
            g("kv_pages_leaked",
              "pages held past retirement, net of prefix cache").set(
                stats["pages"]["leaked"])
        if self.prefixes is not None:
            for r, p in enumerate(self.prefixes):
                ps = p.stats()
                g("prefix_hits", "prefix-cache hits, per replica").set(
                    ps["hits"], replica=r)
                g("prefix_misses", "prefix-cache misses").set(
                    ps["misses"], replica=r)
                g("prefix_hit_tokens",
                  "prompt tokens served from cached prefixes").set(
                    ps["hit_tokens"], replica=r)
                g("prefix_entries", "live prefix-index entries").set(
                    ps["entries"], replica=r)
                g("prefix_pages_held",
                  "pool pages the index keeps alive").set(
                    ps["pages_held"], replica=r)
        if self._plan is not None:
            g("mesh_data_replicas", "DP replica groups").set(
                self._plan.n_data)
            g("mesh_model_shards", "TP shards per replica").set(
                self._plan.n_model)
        if self.speculate:
            sp = stats["spec"]
            g("spec_acceptance_rate",
              "accepted / drafted over the run").set(sp["acceptance_rate"])
            g("spec_emitted_per_target_forward",
              "speculative figure of merit").set(
                sp["emitted_per_target_forward"])
        g("obs_trace_events", "timeline records ever emitted").set(
            self.timeline.seq)
        g("obs_trace_dropped",
          "timeline records dropped by the ring buffer").set(
            self.timeline.dropped)

    def _prefix_stats(self) -> dict:
        """Aggregate prefix-index stats: the single index's dict on one
        replica (unchanged keys for existing callers), summed counters plus
        a per-replica breakdown under DP."""
        if len(self.prefixes) == 1:
            return self.prefixes[0].stats()
        per = [p.stats() for p in self.prefixes]
        out = {k: sum(s[k] for s in per) for k in per[0]}
        out["per_replica"] = per
        return out

    def drop_prefix_cache(self) -> None:
        """Release every page the prefix indexes hold (cache teardown).
        With no live requests, every replica's pool must return to zero
        pages in use — anything left is a real leak."""
        if self.prefixes is not None:
            for p in self.prefixes:
                p.release_all()


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32-1b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="use the reduced config (--no-reduced for full)")
    ap.add_argument("--bits", type=int, default=0,
                    help="0 = fp; 2/4/8 = SplitQuantV2 linear quant")
    ap.add_argument("--split", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="outlier-splitting quantization (--no-split "
                         "for the plain linear baseline)")
    ap.add_argument("--engine", default="packed",
                    choices=("fake", "packed", "planes"),
                    help="quantized execution path (see module docstring)")
    ap.add_argument("--no-group", action="store_true",
                    help="disable fused QKV / gate+up kernel launches")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--prompt-lens", default=None,
                    help="comma-separated heterogeneous prompt lengths "
                         "cycled over requests (overrides --prompt-len), "
                         "e.g. 4,16,23")
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="paged KV cache: per-request page reservations "
                         "from a shared pool instead of batch x max_len "
                         "contiguous strips")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page pool size (0 = batch * pages-per-row, "
                         "i.e. dense-equivalent capacity)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="share common prompt prefixes via page refcounts "
                         "(paged mode): matched full pages are retained "
                         "read-only, only the tail is prefilled")
    ap.add_argument("--prefix-state-budget", type=int, default=0,
                    help="byte cap on recurrent boundary-state snapshots "
                         "held by the prefix index (0 = unbounded); over "
                         "budget, LRU entries lose their snapshot but keep "
                         "their KV pages")
    ap.add_argument("--speculate", type=int, default=0,
                    help="speculative decoding: draft k tokens per request "
                         "with the quantized draft model and verify them "
                         "in one target forward (paged mode; 0 = off)")
    ap.add_argument("--draft-engine", default="packed",
                    choices=("fake", "packed", "planes"),
                    help="execution path for the draft model (built from "
                         "the same weights; packed INT4 is the point)")
    ap.add_argument("--draft-bits", type=int, default=4,
                    help="draft-model quantization bits (SplitQuantV2)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common N-token prefix to every "
                         "generated prompt (shared-prompt workload "
                         "shaping for smokes/benches)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split prompts into N-token prefill waves "
                         "interleaved with decode steps (0 = whole prompt)")
    ap.add_argument("--page-growth", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="admit with a prompt-only (+headroom) page "
                         "reservation and grow per decode tick (paged "
                         "mode): more admitted concurrency, preemption "
                         "handles mid-decode exhaustion")
    ap.add_argument("--growth-headroom", type=int, default=0,
                    help="extra tokens reserved beyond the prompt at "
                         "admission in growth mode")
    ap.add_argument("--preemption", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="preempt+replay victims on pool exhaustion; "
                         "--no-preemption skips ticks instead and may "
                         "stall (SchedulerStall)")
    ap.add_argument("--spec-floor", type=float, default=0.0,
                    help="trailing draft acceptance-rate floor below "
                         "which a request decodes plainly for the round "
                         "(0 = never degrade)")
    ap.add_argument("--spec-window", type=int, default=16,
                    help="drafted tokens in the acceptance window")
    ap.add_argument("--inject", default="",
                    help="fault plan, e.g. oop@tick7,fail@tick3,slow@tick5 "
                         "(see repro.runtime.faultinject); with greedy "
                         "sampling the CLI re-runs the workload cleanly "
                         "and FAILS unless streams match bit-exactly")
    ap.add_argument("--spill-dir", default="",
                    help="preempt-to-disk tier: spill eligible preempted "
                         "contexts' KV pages to .npz files under this "
                         "directory and restore by page reload instead of "
                         "replay recompute (paged mode; empty = off)")
    ap.add_argument("--spill-threshold", type=int, default=0,
                    help="minimum cache rows (prompt + emitted - 1) a "
                         "preempted context must hold to spill; shorter "
                         "contexts replay through prefill instead")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="time-to-first-token target: when the trailing "
                         "median exceeds it (and TPOT is healthy) the SLO "
                         "controller GROWS the chunked-prefill budget "
                         "(0 = no target)")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help="inter-token latency target: violations SHRINK "
                         "the chunked-prefill budget (decode interleaves "
                         "more) and raise the spec degradation floor "
                         "(0 = no target)")
    ap.add_argument("--slo-chunk-min", type=int, default=8,
                    help="smallest chunked-prefill budget the SLO "
                         "controller may tune down to")
    ap.add_argument("--max-wall-s", type=float, default=0.0,
                    help="soft deadline: drain in-flight requests (partial "
                         "streams, status=preempted, zero leaks) and exit "
                         "cleanly after S seconds (0 = off)")
    ap.add_argument("--mesh", default="",
                    help="serve on a DxM (data x model) device mesh, e.g. "
                         "2x2: D data-parallel replica groups split the "
                         "batch slots and page pool, M-way tensor "
                         "parallelism shards every matmul's output dim "
                         "(exact-TP: greedy streams stay bit-identical to "
                         "the single-device path). Empty = no mesh.")
    ap.add_argument("--obs", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="telemetry registry + per-request tracing "
                         "(--no-obs swaps in the no-op registry; the "
                         "scheduler timeline stays on either way)")
    ap.add_argument("--metrics-out", default="",
                    help="write the end-of-run metrics snapshot to this "
                         "path in Prometheus text format")
    ap.add_argument("--trace-out", default="",
                    help="write the scheduler timeline to this path as "
                         "JSONL (meta head line + one record per event)")
    ap.add_argument("--trace-cap", type=int, default=DEFAULT_CAP,
                    help="ring-buffer cap on timeline records (0 = "
                         "unbounded); the run FAILS if records are "
                         "dropped, so raise this rather than letting a "
                         "long smoke wrap")
    ap.add_argument("--jax-profile", default="",
                    help="capture a jax.profiler trace into this "
                         "directory, gated around --profile-ticks decode "
                         "ticks")
    ap.add_argument("--profile-ticks", type=int, default=8,
                    help="decode ticks the --jax-profile trace spans")
    ap.add_argument("--quality-probe", type=int, default=0,
                    help="every N decode ticks, replay each live row's "
                         "context through an fp-reference forward and "
                         "record quantized-vs-fp logit divergence (KL, "
                         "top-1 agreement, max-abs-diff) into the "
                         "registry; greedy streams are bit-identical "
                         "probe-on vs probe-off (0 = off)")
    ap.add_argument("--quant-report", default="",
                    help="write the ranked per-layer quantization-quality "
                         "report (SQNR base vs split, clipping, outlier "
                         "mass — worst layer first) to this JSON path and "
                         "file its gauges into the metrics registry")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax (default); > 0 samples")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k most likely tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_engine(args):
    """Build the serving engine a parsed CLI namespace describes:
    ``(cfg, model, params, draft_params, w_bytes, mesh)``. Shared by this
    CLI and the service front-end (``repro.serve.app``), so both launch
    the exact same quantized execution path."""
    from repro.configs import get_config
    from repro.core import QuantPolicy, restructure
    from repro.engine import decode_weight_bytes, weight_bytes
    from repro.models import build_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    w_bytes = decode_weight_bytes(params, tie_embeddings=cfg.tie_embeddings)
    # fp reference hooks are captured BEFORE quantization rebinds params:
    # the probe needs the unquantized tree, and the quant report measures
    # the fp weights the quantizer is about to compress
    probe_params = params if getattr(args, "quality_probe", 0) else None
    if getattr(args, "quant_report", ""):
        from repro.core import build_quant_report
        from repro.obs.metrics import global_registry
        t0 = time.time()
        rep = build_quant_report(params, QuantPolicy(
            bits=args.bits or 4, split=args.split,
            packed=args.engine == "packed",
        ))
        rep.record(global_registry())
        rep.save(args.quant_report)
        s = rep.summary()
        print(f"[serve] quant report -> {args.quant_report} "
              f"({s['layers']} layers, mean SQNR gain "
              f"{s['mean_sqnr_gain_db']:+.2f} dB, worst layer "
              f"{s['worst_layer']} at {s['worst_layer_sqnr_split_db']:.2f} "
              f"dB, {time.time() - t0:.1f}s)")
    draft_params = None
    if args.speculate:
        # the drafter quantizes the SAME weights the target serves —
        # self-speculation is the paper's accuracy claim cashed in as
        # serving latency (built before the target tree replaces params)
        t0 = time.time()
        qd = restructure(params, QuantPolicy(
            bits=args.draft_bits, split=args.split,
            packed=args.draft_engine == "packed",
        ))
        if args.draft_engine == "fake":
            draft_params = qd.materialize()
        else:
            draft_params = qd.as_executable(group=not args.no_group)
        print(f"[serve] draft model: SplitQuantV2 INT{args.draft_bits} "
              f"({args.draft_engine} engine), {time.time()-t0:.1f}s, "
              f"{weight_bytes(draft_params)/1e6:.2f} MB weights")
    if args.bits:
        t0 = time.time()
        qm = restructure(params, QuantPolicy(
            bits=args.bits, split=args.split,
            packed=args.engine == "packed",
        ))
        if args.engine == "fake":
            params = qm.materialize()
        else:
            params = qm.as_executable(group=not args.no_group)
        w_bytes = decode_weight_bytes(params,
                                      tie_embeddings=cfg.tie_embeddings)
        print(f"[serve] SplitQuantV2 INT{args.bits} preprocessing "
              f"({args.engine} engine): {time.time()-t0:.1f}s, "
              f"{weight_bytes(params)/1e6:.2f} MB weights, "
              f"{w_bytes/1e6:.2f} MB read per decoded token")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        try:
            d, m = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh must be DxM (e.g. 2x2), got "
                             f"{args.mesh!r}")
        if d * m > jax.device_count():
            raise SystemExit(
                f"--mesh {d}x{m} needs {d * m} devices, have "
                f"{jax.device_count()} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N for CPU runs)")
        mesh = make_mesh((d, m), ("data", "model"))
        print(f"[serve] mesh: {d} data replica(s) x {m} model shard(s) "
              f"over {d * m} {jax.devices()[0].platform} device(s)")
    return cfg, model, params, draft_params, w_bytes, mesh, probe_params


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    (cfg, model, params, draft_params, w_bytes, mesh,
     probe_params) = build_engine(args)

    if args.prompt_lens:
        plens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        plens = [args.prompt_len]

    def make_reqs():
        # deterministic workload: the --inject self-check rebuilds the
        # identical request list for its clean reference run
        rng = np.random.default_rng(args.seed)
        common = rng.integers(0, cfg.vocab_size, args.shared_prefix,
                              dtype=np.int32)
        return [
            Request(i, np.concatenate([
                common,
                rng.integers(0, cfg.vocab_size, plens[i % len(plens)],
                             dtype=np.int32),
            ]), args.gen)
            for i in range(args.requests)
        ]

    max_len = args.shared_prefix + max(plens) + args.gen + 8
    slo_on = args.slo_ttft_ms > 0 or args.slo_tpot_ms > 0

    def make_slo():
        if not slo_on:
            return None
        from repro.serve import SLOController
        return SLOController(
            ttft_ms=args.slo_ttft_ms, tpot_ms=args.slo_tpot_ms,
            chunk=args.prefill_chunk or max_len,
            chunk_min=args.slo_chunk_min, chunk_max=max_len,
            spec_floor=args.spec_floor,
        )

    def make_spill():
        if not args.spill_dir:
            return None
        from repro.serve import SpillStore
        return SpillStore(args.spill_dir)

    def make_server(*, inject=None, guard=None, max_wall_s=0.0, obs=None,
                    profile=None):
        return BatchedServer(
            model, params, args.batch, max_len,
            paged=args.paged, page_size=args.page_size,
            num_pages=args.num_pages or None,
            prefix_cache=args.prefix_cache,
            prefix_state_budget=args.prefix_state_budget,
            prefill_chunk=args.prefill_chunk,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed,
            speculate=args.speculate, draft_params=draft_params,
            page_growth=args.page_growth,
            growth_headroom=args.growth_headroom,
            preemption=args.preemption, spec_floor=args.spec_floor,
            spec_window=args.spec_window, inject=inject, guard=guard,
            max_wall_s=max_wall_s,
            spill_store=make_spill(), spill_threshold=args.spill_threshold,
            slo=make_slo(), mesh=mesh, obs=obs,
            trace_cap=args.trace_cap, profile=profile,
            quality_probe=args.quality_probe, probe_params=probe_params,
        )

    greedy = args.temperature <= 0.0
    ref_out = None
    if args.inject and greedy:
        # clean reference first: the injected run must reproduce these
        # streams bit-exactly despite forced preemptions/faults. It runs
        # with telemetry DISABLED, so the stream comparison below also
        # certifies that the enabled registry never perturbs serving.
        ref_reqs = make_reqs()
        make_server(obs=Observability.disabled()).run(ref_reqs)
        ref_out = {r.rid: list(r.out) for r in ref_reqs}

    if args.obs:
        obs = Observability(
            trace_cap=args.trace_cap,
            const_labels={"family": cfg.family,
                          "engine": args.engine if args.bits else "fp"},
        )
    else:
        obs = Observability.disabled(trace_cap=args.trace_cap)
    profile = (JaxProfile(args.jax_profile, ticks=args.profile_ticks)
               if args.jax_profile else None)

    guard = PreemptionGuard().install()
    try:
        reqs = make_reqs()
        server = make_server(inject=args.inject or None, guard=guard,
                             max_wall_s=args.max_wall_s, obs=obs,
                             profile=profile)
        stats = server.run(reqs)
    finally:
        guard.uninstall()
    # decode reads every weight once per step: bytes/token on one chip
    stats["weight_bytes_per_token"] = w_bytes
    stats["engine"] = args.engine if args.bits else "fp"
    print(f"[serve] {stats}")
    # telemetry artifacts are written BEFORE the FAIL gates so a failing
    # smoke still leaves its metrics/trace behind for diagnosis
    if args.metrics_out:
        obs.dump_metrics(args.metrics_out)
        print(f"[serve] metrics -> {args.metrics_out}")
    if args.trace_out:
        n = obs.dump_trace(args.trace_out)
        print(f"[serve] timeline -> {args.trace_out} ({n} records)")
    req_sum = server.tracer.summary()
    if req_sum.get("ttft_s"):
        print(f"[serve] ttft p50={req_sum['ttft_s']['p50'] * 1e3:.1f}ms "
              f"p95={req_sum['ttft_s']['p95'] * 1e3:.1f}ms | "
              f"tpot p50={req_sum.get('tpot_s', {}).get('p50', 0) * 1e3:.1f}"
              f"ms | queue p50="
              f"{req_sum.get('queue_wait_s', {}).get('p50', 0) * 1e3:.1f}ms")
    if args.quality_probe:
        pr = stats["probe"]
        print(f"[serve] quality probe: {pr['samples']} positions probed "
              f"(every {pr['every']} ticks), top-1 agreement "
              f"{pr['top1_agreement_rate']:.3f}")
        if stats["decode_steps"] >= args.quality_probe and not pr["samples"]:
            print("[serve] FAIL: probe enabled but zero positions probed")
            return 1
    if server.timeline.dropped:
        print(f"[serve] FAIL: {server.timeline.dropped} timeline records "
              f"dropped (ring cap {server.timeline.cap}; raise "
              f"--trace-cap)")
        return 1
    if mesh is not None and args.paged:
        per = stats["pages"].get("per_replica", [stats["pages"]])
        for r, ps in enumerate(per):
            kv = stats["mesh"]["kv_reserved_bytes_per_replica"][r]
            print(f"[serve] replica {r}: pages in_use={ps['in_use']} "
                  f"peak={ps['peak_in_use']} cow_copies={ps['cow_copies']} "
                  f"peak_kv_reserved={kv / 1e6:.3f} MB")
    drained = stats["resilience"]["drained"]
    if drained:
        res = stats["resilience"]
        print(f"[serve] drained cleanly: {stats['requests']} retired "
              f"({res['preempted_requests']} partial), "
              f"{res['unserved']} unserved")
    if not drained and stats["requests"] != len(reqs):
        print(f"[serve] FAIL: served {stats['requests']}/{len(reqs)}")
        return 1
    if stats["decode_compiles"] > 1:
        print(f"[serve] FAIL: decode compiled "
              f"{stats['decode_compiles']}x (must be at most once)")
        return 1
    if args.paged and stats["pages"]["leaked"]:
        print(f"[serve] FAIL: {stats['pages']['leaked']} KV pages leaked")
        return 1
    if args.spill_dir:
        store = stats["resilience"]["spill_store"]
        print(f"[serve] spill tier: {store['spills']} spills, "
              f"{store['restores']} restores, "
              f"{stats['resilience']['recompute_forwards']} recompute "
              f"forwards, {store['bytes_written'] / 1e6:.2f} MB written")
        if store["orphans"]:
            print(f"[serve] FAIL: {store['orphans']} orphaned spill "
                  f"file(s) left in {args.spill_dir}")
            return 1
    if slo_on:
        slo = stats["slo"]
        print(f"[serve] slo: {slo['adjustments']} adjustment(s), final "
              f"chunk={slo['chunk']} floor={slo['spec_floor']:.2f} over "
              f"{slo['ticks']} tick(s)")
    if ref_out is not None and not drained:
        got = {r.rid: list(r.out) for r in reqs}
        if got != ref_out:
            bad = sorted(rid for rid in ref_out
                         if got.get(rid) != ref_out[rid])
            print(f"[serve] FAIL: injected-run streams diverge from the "
                  f"clean run for rids {bad}")
            return 1
        if "oop" in args.inject and not stats["resilience"]["preemptions"]:
            print("[serve] FAIL: oop injection fired no preemption "
                  "(tick beyond the run, or nothing preemptible)")
            return 1
        print(f"[serve] chaos OK: streams bit-identical across "
              f"{stats['resilience']['preemptions']} preemption(s) / "
              f"{stats['resilience']['replays']} replay(s)")
    if args.prefix_cache:
        if (args.shared_prefix and args.requests > 1
                and stats["prefix"]["hits"] == 0):
            print("[serve] FAIL: no prefix-cache hits on a shared-prefix "
                  "workload")
            return 1
        server.drop_prefix_cache()
        if server.alloc.in_use:
            print(f"[serve] FAIL: {server.alloc.in_use} pages still in use "
                  "after prefix-cache drop")
            return 1
    if args.speculate:
        sp = stats["spec"]
        if sp["drafted"] and sp["acceptance_rate"] <= 0:
            print("[serve] FAIL: speculation accepted zero draft tokens")
            return 1
        if sp["draft_pages_leaked"]:
            print(f"[serve] FAIL: {sp['draft_pages_leaked']} DRAFT KV "
                  "pages leaked")
            return 1
        if sp["verify_compiles"] > 1:
            print(f"[serve] FAIL: verify compiled {sp['verify_compiles']}x "
                  "(fixed k+1 chunk must compile at most once)")
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
