"""Block-shape dispatch for the quantized matmul kernels.

Two layers, cheapest first:

1. **Heuristic defaults** keyed on (M, K, N, bits): MXU-aligned block
   shapes chosen per problem shape (decode M is tiny -> small bm; big K ->
   big bk to amortize grid overhead; bn capped by a VMEM budget for the
   fp32 accumulator + unpacked weight tile).
2. **Measured cache**: an optional JSON file (``SPLITQ_TUNE_CACHE`` env var
   or an explicit path) mapping ``"MxKxN@bits"`` -> ``[bm, bn, bk]``.
   ``autotune()`` times the candidate blocks for a concrete call and records
   the winner, so serving picks measured shapes on the next run — levanter-
   style config plumbing: the cache is plain data, reviewable and shippable.

M/K/N in a key are the matmul one device runs; on a serving mesh that is
the whole weight (kernels gather it, see ``repro.kernels.ops``). Entries of
the schema-2 format (keys ending in a ``/dS`` shard count) were tuned for
the column-packed layout and are dropped at load time; every hit is checked
against the kernel contracts below before it is used.

All outputs satisfy the kernel contracts: bm % 8 == 0 (fp32 sublane; 16 for
bf16 activations), bn % 128 == 0 (lane), bk covers whole packing slabs
(a multiple of 128, or the whole K of a single-slab tensor — see
``repro.core.quantize.pack_codes``), and for grouped launches bn divides
the group's N alignment so every output block belongs to exactly one
member.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import re
from typing import Callable, Iterable

from repro.core.quantize import PACK_SLAB, packed_rows

ENV_CACHE = "SPLITQ_TUNE_CACHE"

# Scoped-VMEM budget per kernel instance. Mosaic's default scoped limit on
# a v5e is 16 MiB (its out-of-VMEM error names the limit); a quarter stays
# free for the compiler's own scratch.
VMEM_BUDGET = 12 * 1024 * 1024

BN_CANDIDATES = (512, 256, 128)
BK_CANDIDATES = (512, 256, 128)


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    bm: int
    bn: int
    bk: int

    def astuple(self) -> tuple[int, int, int]:
        return (self.bm, self.bn, self.bk)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _vmem_bytes(bm: int, bn: int, bk: int, bits: int) -> int:
    """Scoped VMEM the packed kernel allocates for one block shape: the
    double-buffered x, code, cluster-id and output tiles (activations
    counted as fp32, the worst case) plus the fp32 accumulator. This is
    the sum Mosaic reports when it runs out; the unpacked weight tile is
    not in it (the compiler streams it through vregs and its own scratch)."""
    tiles = bm * bk * 4 + bk * bn * (bits + 2) // 8 + bm * bn * 4
    return 2 * tiles + bm * bn * 4


def heuristic_block(
    m: int, k: int, n: int, bits: int, *, max_bn: int | None = None,
    bf16_acts: bool = False,
) -> tuple[int, int, int]:
    """MXU-aligned default block shape for a (M, K) x (K, N) int-b matmul."""
    sublane = 16 if bf16_acts else 8
    bm = 128 if m >= 128 else _round_up(max(m, 1), sublane)
    bn = next((c for c in BN_CANDIDATES if n >= c), 128)
    if max_bn is not None:
        bn = min(bn, max_bn)
    kp = packed_rows(k)
    if kp < PACK_SLAB:
        bk = kp  # a single partial slab: the tile is the whole K
    else:
        bk = min(next((c for c in BK_CANDIDATES if k >= 4 * c), 128), kp)
    while _vmem_bytes(bm, bn, bk, bits) > VMEM_BUDGET and bn > 128:
        bn //= 2
    while _vmem_bytes(bm, bn, bk, bits) > VMEM_BUDGET and bk > 128:
        bk //= 2
    return (bm, bn, bk)


def _bk_valid(bk: int, k: int) -> bool:
    """bk tiles whole packing slabs of a K-row weight."""
    kp = packed_rows(k)
    return bk == kp if kp < PACK_SLAB else bk % 128 == 0


def candidate_blocks(
    m: int, k: int, n: int, bits: int, *, max_bn: int | None = None,
    bf16_acts: bool = False,
) -> list[tuple[int, int, int]]:
    """Small, valid candidate set around the heuristic for measurement."""
    base = heuristic_block(m, k, n, bits, max_bn=max_bn, bf16_acts=bf16_acts)
    out = {base}
    bm = base[0]
    # a single-slab K has one valid bk, the heuristic's
    bks = BK_CANDIDATES if packed_rows(k) >= PACK_SLAB else ()
    for bn in BN_CANDIDATES:
        if max_bn is not None and bn > max_bn:
            continue
        for bk in bks:
            if _vmem_bytes(bm, bn, bk, bits) <= VMEM_BUDGET:
                out.add((bm, bn, bk))
    return sorted(out)


# ---------------------------------------------------------------------------
# Measured cache
# ---------------------------------------------------------------------------


def cache_key(m: int, k: int, n: int, bits: int,
              bf16_acts: bool = False) -> str:
    # activation dtype changes both the sublane constraint and the measured
    # winner, so bf16 entries get their own namespace
    return f"{m}x{k}x{n}@{bits}" + ("+bf16" if bf16_acts else "")


_KEY_RE = re.compile(r"^\d+x\d+x\d+@\d+(\+bf16)?$")


def _valid_block_entry(v) -> bool:
    """A cache entry must be a 3-int [bm, bn, bk] list."""
    return (
        isinstance(v, (list, tuple)) and len(v) == 3
        and all(isinstance(x, int) and not isinstance(x, bool) for x in v)
    )


class TuneCache:
    """JSON-backed (M, K, N, bits) -> block mapping."""

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = pathlib.Path(path) if path else None
        self.table: dict[str, tuple[int, int, int]] = {}
        if self.path and self.path.exists():
            try:
                raw = json.loads(self.path.read_text())
                # validate per entry at LOAD time: a hand-edited 2-element
                # (or non-int) entry must degrade to the heuristic here,
                # not raise inside choose_block on the serving hot path.
                # Schema-2 keys (a /dS shard suffix) were tuned for the
                # column-packed layout, so they are dropped too.
                self.table = {k: tuple(v)
                              for k, v in raw.get("blocks", raw).items()
                              if _valid_block_entry(v)
                              and isinstance(k, str) and _KEY_RE.match(k)}
            except (json.JSONDecodeError, OSError, AttributeError, TypeError):
                # corrupt/truncated cache must not take down the hot path —
                # heuristics cover every shape
                self.table = {}

    def get(self, m: int, k: int, n: int, bits: int, bf16_acts: bool = False):
        return self.table.get(cache_key(m, k, n, bits, bf16_acts))

    def put(self, m: int, k: int, n: int, bits: int,
            block: tuple[int, int, int], bf16_acts: bool = False):
        self.table[cache_key(m, k, n, bits, bf16_acts)] = tuple(block)

    def save(self, path: str | os.PathLike | None = None):
        p = pathlib.Path(path) if path else self.path
        if p is None:
            raise ValueError("no cache path configured")
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(
            {"schema": 3, "blocks": {k: list(v) for k, v in
                                     sorted(self.table.items())}},
            indent=2,
        ))


_cache: TuneCache | None = None


def get_cache() -> TuneCache:
    global _cache
    if _cache is None:
        _cache = TuneCache(os.environ.get(ENV_CACHE) or None)
    return _cache


def reset_cache():
    global _cache
    _cache = None


def choose_block(
    m: int, k: int, n: int, bits: int, *, max_bn: int | None = None,
    bf16_acts: bool = False,
) -> tuple[int, int, int]:
    """Dispatch: measured cache hit if valid for this call, else heuristic."""
    hit = get_cache().get(m, k, n, bits, bf16_acts)
    if hit is not None and _valid_block_entry(hit):
        bm, bn, bk = hit
        sublane = 16 if bf16_acts else 8
        ok = bm % sublane == 0 and bn % 128 == 0 and _bk_valid(bk, k)
        # re-check the VMEM budget on every hit: an entry tuned on another
        # machine (or hand-edited) may exceed this build's working set
        ok = ok and _vmem_bytes(bm, bn, bk, bits) <= VMEM_BUDGET
        if max_bn is not None:
            ok = ok and bn <= max_bn and max_bn % bn == 0
        if ok:
            _count("tune_cache_hits_total",
                   "choose_block served from the measured cache")
            return (bm, bn, bk)
    _count("tune_cache_misses_total",
           "choose_block fell back to the heuristic")
    return heuristic_block(m, k, n, bits, max_bn=max_bn, bf16_acts=bf16_acts)


def _count(name: str, help: str) -> None:
    """Bump a counter in the process-global obs registry. choose_block has
    no server handle in scope (it runs inside kernel dispatch), so tuning
    visibility rides the global registry, which every exporter merges."""
    from repro.obs.metrics import global_registry

    global_registry().counter(name, help).inc()


def autotune(
    run: Callable[[tuple[int, int, int]], object],
    m: int, k: int, n: int, bits: int,
    *, candidates: Iterable[tuple[int, int, int]] | None = None,
    iters: int = 3, max_bn: int | None = None, bf16_acts: bool = False,
) -> tuple[tuple[int, int, int], dict[str, float]]:
    """Time ``run(block)`` over the candidate set; record the winner.

    Timing goes through ``repro.obs.profile.timeit`` — the repo's one
    benchmark clock (warmup excludes compile, every iteration blocks on
    its own output, MEDIAN of ``iters`` so one GC pause can't crown the
    wrong block). Returns (best_block, {block_str: seconds}).
    """
    from repro.obs.profile import timeit

    cands = list(candidates or candidate_blocks(
        m, k, n, bits, max_bn=max_bn, bf16_acts=bf16_acts))
    timings: dict[str, float] = {}
    best, best_t = None, float("inf")
    last_err: Exception | None = None
    for block in cands:
        try:
            dt = timeit(run, block, iters=iters, warmup=1)
        except Exception as e:  # invalid block for this backend/shape
            last_err = e
            continue
        _count("autotune_trials_total", "candidate blocks measured")
        timings["x".join(map(str, block))] = dt
        if dt < best_t:
            best, best_t = block, dt
    if best is None:
        # EVERY candidate failed: that is a kernel/shape problem, not a
        # tuning outcome — don't record an untimed "winner" silently.
        raise RuntimeError(
            f"autotune: all {len(cands)} candidate blocks failed for "
            f"{cache_key(m, k, n, bits, bf16_acts)}"
        ) from last_err
    get_cache().put(m, k, n, bits, best, bf16_acts)
    _count("autotune_winners_total", "measured winners recorded")
    return best, timings
