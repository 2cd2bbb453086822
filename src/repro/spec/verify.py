"""Target-side verification: one batched multi-token forward + rollback.

The verifier turns k drafted tokens into one target forward: row ``i``
feeds ``[last_emitted, d_1 .. d_kk]`` as a prefill-style chunk (per-row
``lengths`` — the chunked-prefill scatter contract), and
``model.verify_step`` returns the logits at EVERY fed position, i.e. the
target distribution after the context, after draft 1, ..., after draft
kk. Acceptance happens host-side (``spec.policy``); what lives here is
the cache bookkeeping that makes rejection safe:

* positional KV: the verify forward wrote all ``kk + 1`` positions, but a
  rejection means only ``m + 1`` of them are real. Un-writing is a LENGTH
  update, not a data wipe — ``kvcache.paged.rewind`` pulls the per-slot
  ``cache["len"]`` back to ``base + m + 1`` and the rejected positions
  become unreachable exactly like stale KV in a recycled slot (attention
  masks ``k >= len``; the next wave overwrites them — every touched page
  is exclusively owned, the scheduler's COW guard ran before the write).

* recurrent state (zamba2 ssm/conv rows): state cannot be length-masked —
  after the verify forward it has absorbed the rejected drafts. The
  verifier snapshots the recurrent leaves before scoring (free: jax
  arrays are immutable, a snapshot is a reference), and on rejection
  restores the slot's rows, rewinds ``len`` to ``base``, and re-runs the
  ACCEPTED tokens (``m + 1 <= kk + 1``, same jitted verify fn — no new
  compile) to rebuild state; the KV re-writes are idempotent. Slots whose
  drafts all survived keep their post-verify state untouched.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache.paged import restore_rows, rewind


@dataclasses.dataclass
class SpecStats:
    """Speculation counters for one server run."""
    k: int = 0
    rounds: int = 0
    drafted: int = 0            # draft tokens proposed
    accepted: int = 0           # draft tokens that survived verification
    emitted: int = 0            # decode-path tokens emitted by spec rounds
    target_forwards: int = 0    # verify + recompute forwards (target model)
    recompute_forwards: int = 0  # recurrent-state rebuilds after rejection
    draft_forwards: int = 0     # drafter forwards (catch-up + decode steps)
    degraded_rounds: int = 0    # per-request rounds decoded plainly instead
    #                             of drafting (pool pressure or acceptance
    #                             below the configured floor)

    def summary(self) -> dict:
        fwd = max(self.target_forwards, 1)
        return {
            "k": self.k,
            "rounds": self.rounds,
            "drafted": self.drafted,
            "accepted": self.accepted,
            "degraded_rounds": self.degraded_rounds,
            "acceptance_rate": self.accepted / max(self.drafted, 1),
            "emitted": self.emitted,
            "target_forwards": self.target_forwards,
            "recompute_forwards": self.recompute_forwards,
            "draft_forwards": self.draft_forwards,
            # the speculative figure of merit: > 1.0 means each target
            # forward emitted more than one token on average
            "emitted_per_target_forward": self.emitted / fwd,
            "target_forwards_per_token": (
                self.target_forwards / max(self.emitted, 1)
            ),
        }


class Verifier:
    """Jitted multi-token scoring + leakage-free rollback for one cache."""

    def __init__(self, model, params, recurrent_keys: list[str], plan=None,
                 cache_shd=None, registry=None):
        self.params = params
        self._recurrent = list(recurrent_keys)
        self._plan = plan
        self._cache_shd = cache_shd
        self.registry = registry  # optional obs registry (set by the server)
        self.last_logits0 = None  # host (B, V) position-0 logits rows, kept
        #                           only when fetch() is asked (quality probe)

        # private closure: jit caches are keyed by the wrapped function, so
        # wrapping model.verify_step directly would share a compile count
        # with the drafter's catch-up chunk and muddy the compile stats.
        # Under a mesh plan the exact-TP hints are entered inside the trace
        # and the cache output is pinned to its canonical shardings, so the
        # rollback's nested re-verify never registers a second signature.
        def _verify_fn(params, tokens, lengths, cache):
            if plan is not None:
                with plan.hints():
                    return model.verify_step(params, tokens, lengths, cache)
            return model.verify_step(params, tokens, lengths, cache)

        if plan is not None and cache_shd is not None:
            self._verify = jax.jit(_verify_fn,
                                   out_shardings=(None, cache_shd))
        else:
            self._verify = jax.jit(_verify_fn)

    def _put(self, arr):
        if self._plan is None:
            return jnp.asarray(arr)
        return self._plan.put_batch(arr)

    @property
    def compiles(self) -> int:
        return self._verify._cache_size()

    def forward(self, cache: dict, tokens: np.ndarray,
                lengths: np.ndarray):
        """Dispatch the verify forward. Returns ``(logits, new_cache,
        snapshot)``: the ``(B, S, V)`` logits stay on the device (see
        :meth:`fetch`), and the snapshot holds the pre-verify recurrent
        leaves for :meth:`rollback` (empty for attention-only
        families)."""
        snap = {k: cache[k] for k in self._recurrent}
        logits, cache = self._verify(
            self.params, self._put(tokens), self._put(lengths), cache
        )
        if self.registry is not None:
            self.registry.counter(
                "spec_verify_forwards_total",
                "target-model verify forwards (incl. rollback recompute)",
            ).inc()
        return logits, cache, snap

    def fetch(self, logits, greedy: bool = False,
              keep_logits0: bool = False) -> np.ndarray:
        """The verify scores on the host.

        The full ``(B, S, V)`` logits for sampling, but greedy acceptance
        only compares token ids: with ``greedy`` the argmax runs ON DEVICE
        and only ``(B, S)`` ints cross to the host — the verify-wave
        analogue of the serve path's device-argmax decode (full-vocab rows
        at production V would otherwise dominate the round).

        ``keep_logits0`` stashes the position-0 logits rows (the target
        distribution after the last emitted token) on
        ``self.last_logits0`` for the serve path's quality probe — a
        host transfer off the already-computed forward, never an extra
        trace or device call, so greedy streams and compile counts are
        untouched."""
        if keep_logits0:
            self.last_logits0 = np.asarray(logits[:, 0])
        return np.asarray(jnp.argmax(logits, -1) if greedy else logits)

    def rollback(
        self,
        cache: dict,
        snap: dict,
        base: np.ndarray,       # (B,) pre-verify cache lens
        new_lens: np.ndarray,   # (B,) post-acceptance lens (base + m + 1)
        rejected: np.ndarray,   # (B,) bool: slot kept fewer tokens than fed
        tokens: np.ndarray,     # (B, S) the verify wave's token rows
    ) -> dict:
        """Rewind rejected slots so the cache holds exactly the accepted
        sequence. Attention KV rewinds by length; recurrent families
        restore the snapshot and recompute the accepted chunk."""
        if not rejected.any():
            return cache
        if self._recurrent:
            sel = jnp.asarray(rejected)
            cache = restore_rows(cache, snap, sel, self._recurrent)
            # rewind to base, then re-feed the accepted tokens (the first
            # new_lens - base columns of the verify rows) to rebuild state
            cache["len"] = rewind(cache["len"], sel, jnp.asarray(base))
            if self._cache_shd is not None:
                # eager restore/rewind results may carry drifted shardings
                cache = jax.tree.map(jax.device_put, cache, self._cache_shd)
            relens = np.where(rejected, new_lens - base, 0).astype(np.int32)
            _, cache = self._verify(
                self.params, self._put(tokens), self._put(relens), cache
            )
            if self.registry is not None:
                self.registry.counter(
                    "spec_verify_forwards_total",
                    "target-model verify forwards (incl. rollback "
                    "recompute)",
                ).inc()
        else:
            cache = dict(cache)
            cache["len"] = rewind(
                cache["len"], jnp.asarray(rejected), jnp.asarray(new_lens)
            )
        return cache
