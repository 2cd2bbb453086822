"""Kernel + engine micro-bench: wall time of the quantized-matmul execution
paths on CPU (interpret-mode Pallas is NOT representative of TPU — the point
here is (a) the paths run, (b) relative cost of the XLA-fused jnp variants,
and (c) weight-bytes accounting per path, which IS the TPU-relevant number
for decode (weight-bandwidth-bound).

Also emits ``BENCH_quant_engine.json`` at the repo root — a persistent
perf-trajectory record (tokens/s per engine, weight-bytes/token per path,
kernel wall times, launches/block) that this and future PRs append to
compare against.
"""
from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qlinear import (
    splitq_linear_3pass,
    splitq_linear_fused,
    splitq_linear_packed,
)
from repro.core.split import split_quantize, split_quantize_packed
from repro.obs.profile import timeit

BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / (
    "BENCH_quant_engine.json"
)


def _time(f, *args, iters=5):
    # the shared benchmark clock (warmup + block_until_ready + median):
    # bench rows and autotune winners are measured the same way
    return timeit(f, *args, iters=iters, warmup=1)


def _serve_stats(engine: str, gen: int = 4,
                 prompt_lens: tuple[int, ...] = (8, 8),
                 shared_prefix: int = 0, speculate: int = 0,
                 batch_slots: int = 2, mesh_shape=None, **server_kw) -> dict:
    """Tiny end-to-end serve run per engine path (reduced llama, CPU).

    ``server_kw`` forwards to BatchedServer — e.g. ``paged=True,
    page_size=8, num_pages=...`` for the paged KV cache, or
    ``prefill_chunk=N`` for chunked prefill. ``shared_prefix`` prepends a
    common token prefix to every prompt (the production system-prompt
    pattern the prefix cache exists for). ``engine="fp"`` serves the
    unquantized weights; ``speculate=k`` adds a packed-INT4 drafter of the
    same weights (the self-speculation pairing: cheap quantized drafts,
    full-precision verification)."""
    from repro.configs import get_config
    from repro.core import QuantPolicy, restructure
    from repro.engine import decode_weight_bytes
    from repro.kernels import ops
    from repro.launch.serve import BatchedServer, Request
    from repro.models import build_model

    cfg = get_config("llama32-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    draft_params = None
    if speculate:
        draft_params = restructure(
            params, QuantPolicy(bits=4, packed=True)
        ).as_executable(group=True)
    if engine != "fp":
        qm = restructure(params,
                         QuantPolicy(bits=4, packed=engine == "packed"))
        if engine == "fake":
            params = qm.materialize()
        else:
            params = qm.as_executable(group=True)
    common = np.random.default_rng(99).integers(
        0, cfg.vocab_size, shared_prefix, dtype=np.int32)
    mesh = None
    if mesh_shape is not None:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(mesh_shape, ("data", "model"))
    with ops.count_launches() as launches:
        server = BatchedServer(
            model, params, batch_slots=batch_slots,
            max_len=shared_prefix + max(prompt_lens) + gen + 8,
            speculate=speculate, draft_params=draft_params, mesh=mesh,
            **server_kw)
        reqs = [
            Request(i, np.concatenate([common, np.random.default_rng(i)
                    .integers(0, cfg.vocab_size, ln, dtype=np.int32)]), gen)
            for i, ln in enumerate(prompt_lens)
        ]
        stats = server.run(reqs)
    stats["weight_bytes_per_token"] = decode_weight_bytes(
        params, tie_embeddings=cfg.tie_embeddings)
    stats["quant_kernel_launches_traced"] = dict(launches)
    return stats


def run() -> list[tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    k, n, m = 1024, 1024, 16
    w = jnp.asarray(rng.normal(0, 0.02, (k, n)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    sq = split_quantize(w, 4)
    psq = split_quantize_packed(w, 4)

    rows = []
    t3 = _time(jax.jit(splitq_linear_3pass), x, sq)
    tf = _time(jax.jit(splitq_linear_fused), x, sq)
    tp = _time(jax.jit(splitq_linear_packed), x, psq)
    rows.append(("kernel/3pass_us", t3 * 1e6, "paper deployment: 3 matmuls"))
    rows.append(("kernel/fused_us", tf * 1e6, "fused sum-then-matmul"))
    rows.append(("kernel/packed_us", tp * 1e6, "6-bit packed layout"))
    # weight bytes per layer read at decode (the TPU-side figure of merit)
    bytes_3plane = float(3 * k * n // 2)
    bytes_packed = float(k * n // 2 + k * n // 4)
    rows.append(("kernel/bytes_3plane", bytes_3plane, "12 bit/weight (paper)"))
    rows.append(("kernel/bytes_packed", bytes_packed,
                 "6 bit/weight (ours) = 2x less HBM traffic at decode"))

    # engine end-to-end: fake-quant vs packed-kernel serving
    serve = {eng: _serve_stats(eng) for eng in ("fake", "packed")}
    for eng, st in serve.items():
        rows.append((f"engine/{eng}_tok_per_s", st["tok_per_s"],
                     f"{st['tokens']} tokens end-to-end (reduced llama)"))
        rows.append((f"engine/{eng}_weight_bytes_per_token",
                     float(st["weight_bytes_per_token"]),
                     "decode reads every weight once per token"))

    # slot-swap continuous batching: heterogeneous prompts, requests > slots
    # (multi-wave), packed engine — per-slot cache lengths + bucketing
    slotswap = _serve_stats("packed", prompt_lens=(4, 16, 23, 5))
    serve["slotswap_packed"] = slotswap
    rows.append(("engine/slotswap_tok_per_s", slotswap["tok_per_s"],
                 f"{slotswap['tokens']} tokens, prompts 4/16/23/5 through "
                 f"2 slots ({slotswap['prefill_waves']} prefill waves)"))
    rows.append(("engine/slotswap_decode_compiles",
                 float(slotswap["decode_compiles"]),
                 "decode must compile exactly once across slot swaps"))
    rows.append(("engine/slotswap_prefill_compiles",
                 float(slotswap["prefill_compiles"]),
                 f"pow2 buckets {slotswap['prefill_buckets']} "
                 "bound prefill recompiles"))

    # paged KV cache vs contiguous strips: same heterogeneous workload, one
    # long prompt chunk-prefilled, pool smaller than batch x max_len — the
    # memory win is MEASURED per request, not asserted
    paged = _serve_stats("packed", prompt_lens=(4, 16, 23, 5),
                         paged=True, page_size=8, num_pages=8,
                         prefill_chunk=8)
    serve["paged_packed"] = paged
    rows.append(("serve/paged_tok_per_s", paged["tok_per_s"],
                 f"{paged['tokens']} tokens, paged KV (page=8, pool=8 < "
                 f"dense 10), chunked prefill, "
                 f"{paged['prefill_waves']} waves"))
    rows.append(("serve/paged_decode_compiles",
                 float(paged["decode_compiles"]),
                 "paged decode must also compile exactly once"))
    rows.append(("serve/paged_pages_leaked",
                 float(paged["pages"]["leaked"]),
                 "pages still in use after all requests retired"))
    dense_res = slotswap["kv_bytes_reserved_per_request"]
    paged_res = paged["kv_bytes_reserved_per_request"]
    rows.append(("serve/paged_kv_bytes_per_request_mean",
                 float(paged_res["mean"]),
                 f"vs {dense_res['mean']} contiguous: each request reserves "
                 "only the pages its prompt+gen needs"))
    rows.append(("serve/paged_vs_contiguous_kv_reserve_ratio",
                 dense_res["mean"] / max(paged_res["mean"], 1),
                 "contiguous reserves batch x max_len regardless of length"))

    # observability: operational latency percentiles + the tick-time
    # breakdown, read from the paged run's telemetry (stats["obs"] is the
    # tracer/StepTimer view — the bench no longer reaches into server
    # internals for timing). CPU interpret wall times: trajectory, not
    # absolute truth.
    obs = paged["obs"]
    ttft = obs["requests"].get("ttft_s", {})
    tpot = obs["requests"].get("tpot_s", {})
    rows.append(("serve/obs_ttft_ms_p50", ttft.get("p50", 0.0) * 1e3,
                 f"time to first token, p95={ttft.get('p95', 0.0) * 1e3:.0f}"
                 f"ms over {obs['requests'].get('requests', 0)} requests"))
    rows.append(("serve/obs_tpot_ms_p50", tpot.get("p50", 0.0) * 1e3,
                 "steady-state ms per output token (paged run)"))
    for seam, st in sorted(obs["step_time"].items()):
        rows.append((f"serve/obs_tick_{seam}_ms_mean", st["mean_s"] * 1e3,
                     f"{st['count']} {seam} steps, "
                     f"{st['total_s'] * 1e3:.0f}ms total (block_until_ready"
                     " host wall)"))
    rows.append(("serve/obs_trace_dropped", float(obs["trace_dropped"]),
                 "timeline ring-buffer drops (must be 0 in smokes)"))

    # prefix sharing: the SAME common-system-prompt workload (24-token
    # shared prefix = 3 full pages, heterogeneous tails) with and without
    # the prefix cache — reserved pages and prefill tokens must drop
    paged_kw = dict(prompt_lens=(4, 16, 23, 5), shared_prefix=24,
                    paged=True, page_size=8, num_pages=16)
    unshared = _serve_stats("packed", **paged_kw)
    shared = _serve_stats("packed", **paged_kw, prefix_cache=True)
    serve["prefix_unshared"] = unshared
    serve["prefix_shared"] = shared
    rows.append(("serve/prefix_pages_allocated",
                 float(shared["pages"]["pages_allocated"]),
                 f"vs {unshared['pages']['pages_allocated']} unshared: "
                 "matched prefix pages are retained, not re-reserved"))
    rows.append(("serve/prefix_prefill_tokens",
                 float(shared["prefill_tokens"]),
                 f"vs {unshared['prefill_tokens']} unshared: the shared "
                 "prefix is not recomputed"))
    rows.append(("serve/prefix_hit_tokens",
                 float(shared["prefix"]["hit_tokens"]),
                 f"{shared['prefix']['hits']} hits, "
                 f"{shared['pages']['cow_copies']} copy-on-writes"))
    rows.append(("serve/prefix_kv_bytes_per_request_mean",
                 float(shared["kv_bytes_reserved_per_request"]["mean"]),
                 f"vs {unshared['kv_bytes_reserved_per_request']['mean']} "
                 "unshared (reservations net of shared pages)"))
    rows.append(("serve/prefix_pages_leaked",
                 float(shared["pages"]["leaked"]),
                 "pages neither owned nor cached after retirement"))

    # speculative decoding: fp target + packed INT4 drafter (the paper's
    # accuracy result cashed in as serving latency) vs the SAME workload
    # decoded plainly — accepted tokens per target forward is the win
    spec_kw = dict(gen=12, prompt_lens=(6, 14), paged=True, page_size=8,
                   num_pages=16)
    spec_base = _serve_stats("fp", **spec_kw)
    serve["spec_baseline_fp"] = spec_base
    for k in (2, 4):
        st = _serve_stats("fp", **spec_kw, speculate=k)
        serve[f"spec_k{k}_fp"] = st
        sp = st["spec"]
        rows.append((f"serve/spec_k{k}_emitted_per_target_forward",
                     sp["emitted_per_target_forward"],
                     f"{sp['emitted']} tokens / {sp['target_forwards']} "
                     f"target forwards (accept rate "
                     f"{sp['acceptance_rate']:.2f})"))
        rows.append((f"serve/spec_k{k}_target_forwards_per_token",
                     sp["target_forwards_per_token"],
                     f"vs 1 decode forward/token non-speculative (k={k})"))
        rows.append((f"serve/spec_k{k}_tok_per_s", st["tok_per_s"],
                     f"vs {spec_base['tok_per_s']:.1f} baseline (CPU "
                     "interpret wall time: not TPU-representative; the "
                     "forwards/token column is)"))
        rows.append((f"serve/spec_k{k}_pages_leaked",
                     float(st["pages"]["leaked"]
                           + sp["draft_pages_leaked"]),
                     "target + draft pools after rollback-heavy serving"))

    # serving under pressure: prompt-only reservation with on-demand page
    # growth vs full end-to-end reservation on the SAME 6-page pool — the
    # overcommit admits strictly more concurrent requests, repaid with
    # victim preemption + exact replay instead of admission stalls
    pressure_kw = dict(gen=8, prompt_lens=(8, 8, 8, 8), batch_slots=4,
                       paged=True, page_size=8, num_pages=6)
    full = _serve_stats("packed", **pressure_kw)
    grow = _serve_stats("packed", **pressure_kw, page_growth=True)
    serve["pressure_full"] = full
    serve["pressure_growth"] = grow
    fres, gres = full["resilience"], grow["resilience"]
    rows.append(("serve/pressure_full_peak_concurrency",
                 float(fres["peak_concurrency"]),
                 "full reservation: 2 pages/request up front on 6 pages"))
    rows.append(("serve/pressure_growth_peak_concurrency",
                 float(gres["peak_concurrency"]),
                 "prompt-only reservation + per-tick growth, same pool "
                 "(must admit strictly more than full reservation)"))
    rows.append(("serve/pressure_growth_preemptions",
                 float(gres["preemptions"]),
                 f"victims preempted to honor the overcommit "
                 f"({gres['replay_tokens']} tokens replayed exactly)"))
    rows.append(("serve/pressure_pages_leaked",
                 float(full["pages"]["leaked"] + grow["pages"]["leaked"]),
                 "both pools after pressure serving"))

    # mesh-sharded serving: 2 DP replicas split the admission queue and the
    # page pool into replica-local ranges, 2-way exact TP shards every
    # packed matmul's output dim. Bit-identity to the single-device streams
    # is pinned by tests/test_sharded_serving.py; the record here is the
    # per-replica KV memory bill and the compile discipline on the mesh path
    if jax.device_count() >= 4:
        sharded = _serve_stats("packed", gen=8,
                               prompt_lens=(12, 12, 12, 12), batch_slots=4,
                               shared_prefix=16, paged=True, page_size=8,
                               prefix_cache=True, mesh_shape=(2, 2))
        serve["shard_2x2_packed"] = sharded
        rows.append(("serve/shard_tok_per_s", sharded["tok_per_s"],
                     f"{sharded['tokens']} tokens on a 2x2 (data x model) "
                     "mesh, paged KV + prefix cache"))
        rows.append(("serve/shard_decode_compiles",
                     float(sharded["decode_compiles"]),
                     "sharded decode must also compile exactly once"))
        for r, kv in enumerate(
                sharded["mesh"]["kv_reserved_bytes_per_replica"]):
            rows.append((f"serve/shard_kv_reserved_bytes_replica{r}",
                         float(kv),
                         "peak KV pages reserved by this DP replica's "
                         "range of the pool (device-local bytes)"))
        rows.append(("serve/shard_pages_leaked",
                     float(sharded["pages"]["leaked"]),
                     "pool state after sharded serving"))
    else:
        rows.append(("serve/shard_skipped", 1.0,
                     "mesh rows need >= 4 devices (set XLA_FLAGS="
                     "--xla_force_host_platform_device_count=8)"))

    # quantized-storage bytes/token: packed (6 bit/wt) vs 3-plane (12 bit/wt)
    from repro.configs import get_config
    from repro.core import QuantPolicy, restructure
    from repro.models import build_model

    cfg = get_config("llama32-1b").reduced()
    params0 = build_model(cfg).init(jax.random.PRNGKey(0))

    # serving as a service: (a) the SLO loop retunes the chunked-prefill
    # budget against an inter-token target, (b) deficit round-robin
    # admission protects a light tenant queued behind a heavy one. Both
    # comparisons are measured on WARM servers (a first run compiles every
    # bucket) so interpret-mode compile time doesn't drown the scheduling
    # signal, and gaps are token-granular on_token wall stamps — the p99
    # inter-token gap is exactly the decoder stall a long prefill wave
    # causes, which the SLO controller exists to shrink.
    from repro.launch.serve import BatchedServer, Request
    from repro.serve import FairScheduler, SLOController

    model_s = build_model(cfg)

    def _gap_p99(stamps: dict) -> float:
        gaps = sorted(b - a for ts in stamps.values()
                      for a, b in zip(ts, ts[1:]))
        if not gaps:
            return 0.0
        return gaps[min(int(0.99 * len(gaps)), len(gaps) - 1)]

    def _mixed_reqs(base):
        # long-prompt arrivals mid-decode are the stall the SLO loop
        # exists for: the 256-token prefill must be COMPUTE-bound (O(S^2)
        # attention) so one fixed-chunk wave genuinely blocks the live
        # decoder — short prompts here are overhead-bound and show
        # nothing. Four long arrivals put several stall gaps in the
        # distribution, so p99 reads a stall, not a one-off host hiccup.
        rng = np.random.default_rng(5)
        lens_gens = ((6, 30), (256, 4)) * 4
        return [Request(base + i,
                        rng.integers(0, cfg.vocab_size, ln, dtype=np.int32),
                        gen)
                for i, (ln, gen) in enumerate(lens_gens)]

    def _timed_run(slo):
        server = BatchedServer(model_s, params0, batch_slots=2,
                               max_len=256 + 30 + 8, paged=True, page_size=8,
                               num_pages=80, prefill_chunk=256, slo=slo)
        server.run(_mixed_reqs(0))  # warm every bucket the run will touch
        stamps: dict[int, list[float]] = {}

        def on_token(r, tok):
            stamps.setdefault(r.rid, []).append(time.monotonic())

        stats = server.run(_mixed_reqs(1000), on_token=on_token)
        return stats, _gap_p99(stamps)

    fixed_stats, p99_fixed = _timed_run(None)
    slo_stats, p99_slo = _timed_run(
        SLOController(tpot_ms=0.05, chunk=256, chunk_min=8, chunk_max=256))
    rows.append(("serve/service_tpot_ms_p99_fixed", p99_fixed * 1e3,
                 "p99 inter-token gap, fixed 256-token prefill chunk: a "
                 "long prompt stalls the live decoder a whole wave"))
    rows.append(("serve/service_tpot_ms_p99_slo", p99_slo * 1e3,
                 f"vs {p99_fixed * 1e3:.0f}ms fixed: the SLO loop shrank "
                 f"the chunk to {slo_stats['slo']['chunk']} (must be "
                 "strictly lower)"))
    rows.append(("serve/service_slo_adjustments",
                 float(slo_stats["slo"]["adjustments"]),
                 "budget moves the controller made (must be > 0: the "
                 "loop demonstrably acts)"))
    serve["service_slo"] = {
        "fixed_tpot_p99_s": p99_fixed, "slo_tpot_p99_s": p99_slo,
        "final_chunk": slo_stats["slo"]["chunk"],
        "adjustments": slo_stats["slo"]["adjustments"],
        "history": slo_stats["slo"]["history"],
        "pages_leaked": (fixed_stats["pages"]["leaked"]
                         + slo_stats["pages"]["leaked"]),
    }

    def _fair_reqs(base):
        rng = np.random.default_rng(9)
        heavy = [Request(base + i,
                         rng.integers(0, cfg.vocab_size, 12, dtype=np.int32),
                         8) for i in range(8)]
        light = [Request(base + 100 + i,
                         rng.integers(0, cfg.vocab_size, 12, dtype=np.int32),
                         8) for i in range(2)]
        return heavy, light

    def _light_ttft(use_drr):
        """Median light-tenant TTFT when 2 light requests are submitted
        BEHIND 8 heavy ones: FIFO serves them last; DRR (weight 3)
        releases them in the first round."""
        server = BatchedServer(model_s, params0, batch_slots=2,
                               max_len=12 + 8 + 8, paged=True, page_size=8,
                               num_pages=24)

        def ordered(base):
            heavy, light = _fair_reqs(base)
            if not use_drr:
                return heavy + light  # submission order
            fair = FairScheduler(quantum=20.0)
            for r in heavy:
                fair.submit("heavy", r, weight=1.0)
            for r in light:
                fair.submit("light", r, weight=3.0)
            out = []
            while fair.backlog:
                out += fair.drain(1)
            return out

        server.run(ordered(0))       # warm
        server.run(ordered(2000))    # measured (fresh rids -> fresh traces)
        ttfts = sorted(d["ttft_s"] for d in server.tracer.requests()
                       if d["rid"] >= 2100)
        return ttfts[len(ttfts) // 2]

    ttft_fifo = _light_ttft(False)
    ttft_fair = _light_ttft(True)
    rows.append(("serve/service_ttft_ms_light_fifo", ttft_fifo * 1e3,
                 "light tenant's TTFT p50 queued behind 8 heavy requests, "
                 "plain FIFO admission"))
    rows.append(("serve/service_ttft_ms_light_fair", ttft_fair * 1e3,
                 f"vs {ttft_fifo * 1e3:.0f}ms FIFO: weighted DRR releases "
                 "the light tenant in round one (must be strictly lower)"))
    serve["service_fairness"] = {
        "light_ttft_s_fifo": ttft_fifo, "light_ttft_s_fair": ttft_fair,
    }
    q_packed = restructure(params0, QuantPolicy(bits=4, packed=True))
    q_planes = restructure(params0, QuantPolicy(bits=4, packed=False))
    b_packed = q_packed.size_bytes()["quantized"]
    b_planes = q_planes.size_bytes()["quantized"]
    rows.append(("engine/packed_vs_3plane_bytes_ratio", b_planes / b_packed,
                 "quantized weight bytes/token: must be ~2x (6 vs 12 bit)"))

    record = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "problem": {"m": m, "k": k, "n": n, "bits": 4},
        "kernel_wall_us": {"3pass": t3 * 1e6, "fused": tf * 1e6,
                           "packed": tp * 1e6},
        "weight_bytes_per_layer": {
            "3plane": bytes_3plane, "packed": bytes_packed,
            "packed_vs_3plane_ratio": bytes_3plane / bytes_packed,
        },
        "serve": serve,
        "weight_bytes_per_token_quantized": {
            "packed": b_packed, "3plane": b_planes,
            "packed_vs_3plane_ratio": b_planes / b_packed,
        },
        "note": "CPU interpret-mode wall times are not TPU-representative; "
                "bytes/token accounting is.",
    }
    # append to the persistent perf trajectory (one entry per run)
    runs = []
    if BENCH_PATH.exists():
        try:
            prev = json.loads(BENCH_PATH.read_text())
            runs = prev.get("runs", [prev] if "serve" in prev else [])
        except (json.JSONDecodeError, OSError):
            runs = []
    runs.append(record)
    BENCH_PATH.write_text(json.dumps({"schema": 2, "runs": runs}, indent=2))
    rows.append(("engine/bench_json_written", float(len(runs)),
                 f"{BENCH_PATH.name} ({len(runs)} run(s) recorded)"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r)
