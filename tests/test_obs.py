"""Observability subsystem: metrics registry, per-request tracing, and
the structured scheduler timeline.

The contracts under test (the telemetry tentpole):

* telemetry is PURE OBSERVATION — greedy streams and compile counts are
  bit-identical between a live registry and ``Observability.disabled()``
  (the no-op registry), plain and speculative, both cache families,
* the registry's live counters agree with the stats dict (they are built
  from the same events, so they can never diverge),
* span invariants: per-request spans are time-ordered, their emitted
  counts sum to exactly ``len(out)``, TTFT <= total latency, and a
  preempted-and-restored request carries a ``replay`` span,
* per-replica metric series sum to the aggregate under DP (subprocess,
  2x2 mesh on 8 fake devices),
* the timeline is a ring: dropped records are counted, exported as a
  metric, and fail the serve CLI loudly (nonzero exit),
* the Prometheus exposition round-trips through ``parse_prometheus``.
"""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from serve_helpers import REPO_ROOT, subprocess_env

from repro.configs import get_config
from repro.core import QuantPolicy, restructure
from repro.launch.serve import BatchedServer, Request
from repro.models import build_model
from repro.obs import (
    DEFAULT_TIME_BUCKETS,
    NullRegistry,
    Observability,
    Registry,
    Timeline,
    global_registry,
    parse_prometheus,
    read_jsonl,
    reset_global_registry,
)


def _tiny_model(arch="llama32-1b", n_layers=2, seed=0):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _requests(cfg, lens, gen, seed0=100):
    return [
        Request(i, np.random.default_rng(seed0 + i).integers(
            0, cfg.vocab_size, ln, dtype=np.int32), gen)
        for i, ln in enumerate(lens)
    ]


# ---------------------------------------------------------------------------
# Registry unit pins
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = Registry()
    c = reg.counter("reqs_total", "h")
    c.inc(replica=0)
    c.inc(2, replica=1)
    assert c.value(replica=0) == 1 and c.value(replica=1) == 2
    assert reg.total("reqs_total") == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("depth").set(4)
    reg.gauge("depth").set(7)  # get-or-create returns the same family
    assert reg.value("depth") == 7
    h = reg.histogram("lat_seconds", "h")
    for v in (2e-4, 2e-4, 1.0):
        h.observe(v)
    assert h.quantile(0.5) <= 1e-3  # two of three sit in the 200us bucket
    assert h.quantile(1.0) >= 1.0
    # a name registered as one kind cannot be re-registered as another
    with pytest.raises(TypeError):
        reg.counter("depth")


def test_prometheus_roundtrip_and_const_labels():
    reg = Registry(const_labels={"family": "dense", "engine": "packed"})
    reg.counter("serve_tokens_total", "emitted").inc(5, replica=0)
    reg.counter("serve_tokens_total").inc(3, replica=1)
    reg.histogram("serve_ttft_seconds", "ttft").observe(0.01, replica=0)
    text = reg.to_prometheus(include_global=False)
    snap = parse_prometheus(text)
    toks = snap["serve_tokens_total"]
    assert sum(v for _, v in toks) == 8
    # const labels stamped onto every series
    assert all(lbl["family"] == "dense" and lbl["engine"] == "packed"
               for lbl, _ in toks)
    assert {lbl["replica"] for lbl, _ in toks} == {"0", "1"}
    # histogram exports the cumulative +Inf bucket and _sum/_count
    inf = [v for lbl, v in snap["serve_ttft_seconds_bucket"]
           if lbl["le"] == "+Inf"]
    assert inf == [1.0]
    assert snap["serve_ttft_seconds_count"][0][1] == 1
    # strict parser: garbage must raise, not be skipped
    with pytest.raises(ValueError):
        parse_prometheus("this is { not a metric\n")


def test_null_registry_is_inert():
    reg = NullRegistry()
    assert not reg.enabled
    reg.counter("a_total").inc(5)
    reg.gauge("b").set(1)
    reg.histogram("c_seconds").observe(0.5)
    assert reg.snapshot()["metrics"] == {}
    assert reg.to_prometheus() == ""


def test_global_registry_merged_into_exports():
    reset_global_registry()
    try:
        global_registry().counter("tune_cache_hits_total", "h").inc(4)
        reg = Registry(const_labels={"engine": "packed"})
        reg.counter("serve_tokens_total").inc(2)
        snap = reg.snapshot()
        assert snap["metrics"]["tune_cache_hits_total"]["series"][0][
            "value"] == 4
        parsed = parse_prometheus(reg.to_prometheus())
        assert parsed["tune_cache_hits_total"][0][1] == 4
        # the global registry itself does not re-merge (no recursion)
        assert "serve_tokens_total" not in global_registry().snapshot()[
            "metrics"]
    finally:
        reset_global_registry()


# ---------------------------------------------------------------------------
# Timeline unit pins
# ---------------------------------------------------------------------------


def test_timeline_ring_drops_and_legacy_rendering(tmp_path):
    tl = Timeline(cap=3)
    tl.set_tick(0)
    tl.emit("prefill", rows=2)
    tl.emit("admission", rid=7)        # timeline-only detail
    tl.emit("decode", rows=2)
    tl.emit("preempt", rid=3)
    tl.emit("replay", rid=3, tokens=9)
    assert len(tl) == 3 and tl.seq == 5 and tl.dropped == 2
    # legacy strings render only the kinds the old list held
    assert tl.legacy_events() == ["decode", "preempt:3", "replay:3"]
    p = tmp_path / "t.jsonl"
    assert tl.to_jsonl(p) == 3
    meta, recs = read_jsonl(p)
    assert meta["events"] == 5 and meta["dropped"] == 2 and meta["cap"] == 3
    assert [r["kind"] for r in recs] == ["decode", "preempt", "replay"]
    assert [r["seq"] for r in recs] == [2, 3, 4]  # monotone survives drops
    with pytest.raises(ValueError):
        Timeline(cap=-1)
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "decode"}\n')
    with pytest.raises(ValueError, match="meta"):
        read_jsonl(bad)


# ---------------------------------------------------------------------------
# Bit-identity: telemetry is pure observation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,n_layers", [("llama32-1b", 2),
                                           ("zamba2-1.2b", 4)])
@pytest.mark.parametrize("speculate", [0, 3])
def test_streams_bit_identical_with_and_without_registry(arch, n_layers,
                                                         speculate):
    """The tentpole's acceptance pin: the SAME workload served with a live
    registry and with the no-op registry produces identical greedy streams,
    identical compile counts, and identical legacy event strings."""
    cfg, model, params = _tiny_model(arch, n_layers=n_layers)
    draft = (restructure(params, QuantPolicy(bits=4, packed=True))
             .as_executable(group=True) if speculate else None)
    kw = dict(batch_slots=2, max_len=32, paged=True, page_size=4,
              num_pages=24, speculate=speculate, draft_params=draft)
    lens, gen = [6, 11, 4, 9], 5

    def serve(obs):
        reqs = _requests(cfg, lens, gen)
        server = BatchedServer(model, params, obs=obs, **kw)
        stats = server.run(reqs)
        return ({r.rid: r.out for r in reqs}, stats["decode_compiles"],
                stats["prefill_compiles"], server.events, server)

    on = serve(None)                          # default: live registry
    off = serve(Observability.disabled())     # no-op registry + tracer
    assert on[4].registry.enabled and not off[4].registry.enabled
    assert on[0] == off[0], (arch, speculate)
    assert on[1:4] == off[1:4], (arch, speculate)
    # the disabled bundle still keeps the REAL timeline (events compat)
    assert off[3] and off[3] == on[3]
    assert off[4].tracer.requests() == []


def test_registry_counters_match_stats_dict():
    """Live counters and the stats dict are built from the same events:
    totals must agree exactly."""
    cfg, model, params = _tiny_model()
    reqs = _requests(cfg, [6, 11, 4], 5)
    server = BatchedServer(model, params, batch_slots=2, max_len=32,
                           paged=True, page_size=4, num_pages=24,
                           prefix_cache=True)
    stats = server.run(reqs)
    reg = server.registry
    assert reg.total("serve_tokens_total") == stats["tokens"]
    assert reg.total("serve_requests_total") == stats["requests"]
    assert reg.value("serve_requests_total", status="ok", replica=0) == 3
    assert reg.value("serve_jit_compiles",
                     step="decode") == stats["decode_compiles"]
    assert reg.value("serve_decode_ticks") == stats["decode_steps"]
    assert reg.value("kv_pages_leaked") == stats["pages"]["leaked"] == 0
    assert reg.value("prefix_hits", replica=0) == stats["prefix"]["hits"]
    assert reg.value("obs_trace_events") == server.timeline.seq > 0
    # the step timer saw every jitted seam the run exercised
    st = stats["obs"]["step_time"]
    assert set(st) >= {"prefill", "decode"}
    assert all(v["count"] > 0 and v["total_s"] >= 0 for v in st.values())
    hist = reg.histogram("serve_step_seconds")
    assert sum(h.count for _, h in hist.series()) == sum(
        v["count"] for v in st.values())


def test_spec_counters_match_spec_stats():
    cfg, model, params = _tiny_model()
    draft = restructure(params, QuantPolicy(bits=4, packed=True)
                        ).as_executable(group=True)
    reqs = _requests(cfg, [6, 11, 4, 9], 6)
    server = BatchedServer(model, params, batch_slots=2, max_len=32,
                           paged=True, page_size=4, num_pages=24,
                           speculate=3, draft_params=draft)
    stats = server.run(reqs)
    sp, reg = stats["spec"], server.registry
    assert reg.total("spec_drafted_total") == sp["drafted"] > 0
    assert reg.total("spec_accepted_total") == sp["accepted"] > 0
    assert reg.total("spec_verify_forwards_total") == sp["target_forwards"]
    assert reg.total("spec_draft_forwards_total") == sp["draft_forwards"]
    assert reg.value("spec_acceptance_rate") == sp["acceptance_rate"]


# ---------------------------------------------------------------------------
# Span invariants
# ---------------------------------------------------------------------------


def _check_span_invariants(server, reqs):
    for r in reqs:
        tr = server.tracer.request(r.rid)
        assert tr is not None, r.rid
        spans = tr["spans"]
        kinds = [s["kind"] for s in spans]
        assert kinds[0] == "queued" and kinds[-1] == "retired", kinds
        # spans are time-ordered with monotone start AND end times
        for a, b in zip(spans, spans[1:]):
            assert b["t0"] >= a["t0"] and b["t1"] >= a["t1"], (r.rid, kinds)
        for s in spans:
            assert s["t1"] >= s["t0"], s
        # every emitted token is attributed to exactly one span
        assert sum(s.get("emitted", 0) for s in spans) == len(r.out), (
            r.rid, spans)
        assert tr["emitted"] == len(r.out)
        if r.out:
            assert tr["ttft_s"] <= tr["latency_s"], tr
            assert tr["queue_wait_s"] <= tr["ttft_s"], tr
        if tr.get("tpot_s") is not None and len(r.out) > 1:
            assert tr["tpot_s"] >= 0


@pytest.mark.parametrize("speculate", [0, 3])
def test_span_invariants_plain_and_speculative(speculate):
    cfg, model, params = _tiny_model()
    draft = (restructure(params, QuantPolicy(bits=4, packed=True))
             .as_executable(group=True) if speculate else None)
    reqs = _requests(cfg, [6, 11, 4, 9], 5)
    server = BatchedServer(model, params, batch_slots=2, max_len=32,
                           paged=True, page_size=4, num_pages=24,
                           speculate=speculate, draft_params=draft)
    stats = server.run(reqs)
    _check_span_invariants(server, reqs)
    summ = stats["obs"]["requests"]
    assert summ["requests"] == len(reqs)
    assert summ["ttft_s"]["p50"] <= summ["latency_s"]["max"]
    if speculate:
        # verify spans carry the draft/accept attribution
        vs = [s for r in reqs for s in server.tracer.request(r.rid)["spans"]
              if s["kind"] == "verify"]
        assert vs and any(s.get("accepted", 0) > 0 for s in vs)


def test_preempted_request_carries_replay_span():
    """Page pressure under growth forces preemption: the victim's trace
    must show preempt -> replay -> (re)prefill, its replay tokens must be
    counted, and the live resilience counters must match the stats."""
    cfg, model, params = _tiny_model()
    reqs = _requests(cfg, [8, 8, 8, 8], 8)
    server = BatchedServer(model, params, batch_slots=4, max_len=16,
                           paged=True, page_size=8, num_pages=6,
                           page_growth=True)
    stats = server.run(reqs)
    res = stats["resilience"]
    assert res["preemptions"] > 0 and res["replays"] > 0
    reg = server.registry
    assert reg.total("resilience_preemptions_total") == res["preemptions"]
    assert reg.total("resilience_replays_total") == res["replays"]
    _check_span_invariants(server, reqs)
    victims = [server.tracer.request(r.rid) for r in reqs]
    victims = [t for t in victims if t["preemptions"] > 0]
    assert victims
    for t in victims:
        kinds = [s["kind"] for s in t["spans"]]
        i = kinds.index("preempt")
        assert "replay" in kinds[i:], kinds
        j = i + kinds[i:].index("replay")
        assert "prefill" in kinds[j:], kinds  # the restore really re-fed
        assert t["replay_tokens"] > 0
    # timeline carries the same story as structured records
    assert len(server.timeline.records("preempt")) == res["preemptions"]
    assert len(server.timeline.records("replay")) == res["replays"]


def test_server_trace_cap_ring_drops_counted():
    cfg, model, params = _tiny_model()
    reqs = _requests(cfg, [6, 9], 6)
    server = BatchedServer(model, params, batch_slots=2, max_len=24,
                           trace_cap=2)
    stats = server.run(reqs)
    assert server.timeline.dropped > 0
    assert stats["obs"]["trace_dropped"] == server.timeline.dropped
    assert server.registry.value(
        "obs_trace_dropped") == server.timeline.dropped


def test_serve_cli_fails_loudly_on_trace_drops(monkeypatch):
    """--trace-cap small enough to wrap the ring must exit nonzero: a
    truncated timeline silently read as complete is an observability
    bug."""
    import repro.launch.serve as serve_mod

    tiny = get_config("llama32-1b").reduced()
    tiny = dataclasses.replace(tiny, n_layers=2)

    class _Proxy:
        def reduced(self):
            return tiny

        def __getattr__(self, item):
            return getattr(tiny, item)

    monkeypatch.setattr("repro.configs.get_config", lambda name: _Proxy())
    argv = ["--no-reduced", "--no-split", "--bits", "4", "--engine", "fake",
            "--batch", "2", "--requests", "2", "--prompt-len", "4",
            "--gen", "6"]
    assert serve_mod.main(argv + ["--trace-cap", "2"]) != 0


def test_serve_cli_fails_when_jax_profile_cannot_start(monkeypatch, tmp_path):
    """--jax-profile whose trace cannot start fails the run: a run asked
    to profile must not finish looking profiled with no trace written."""
    import repro.launch.serve as serve_mod

    tiny = dataclasses.replace(get_config("llama32-1b").reduced(),
                               n_layers=2)
    monkeypatch.setattr("repro.configs.get_config", lambda name: tiny)

    def no_profiler(logdir, *a, **k):
        raise RuntimeError(f"profiler unavailable for {logdir}")

    monkeypatch.setattr(jax.profiler, "start_trace", no_profiler)
    argv = ["--no-reduced", "--bits", "0", "--batch", "2", "--requests",
            "2", "--prompt-len", "4", "--gen", "3",
            "--jax-profile", str(tmp_path / "trace")]
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        serve_mod.main(argv)


# ---------------------------------------------------------------------------
# Per-replica series sum to the aggregate (2x2 mesh, subprocess)
# ---------------------------------------------------------------------------


_MESH_METRICS = """
    import os
    assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]
    import jax, numpy as np
    from repro.configs import get_config
    from repro.core import QuantPolicy, restructure
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import BatchedServer, Request
    from repro.models import build_model
    from repro.obs import parse_prometheus

    cfg = get_config("llama32-1b").reduced()
    model = build_model(cfg)
    fp = model.init(jax.random.PRNGKey(0))
    params = restructure(fp, QuantPolicy(bits=4, split=True, packed=True)
                         ).as_executable(group=True)
    rng = np.random.default_rng(0)
    common = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    reqs = [Request(i, np.concatenate([
        common, rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)]), 6)
        for i in range(6)]
    mesh = make_mesh((2, 2), ("data", "model"))
    srv = BatchedServer(model, params, 4, 48, paged=True, page_size=8,
                        prefix_cache=True, mesh=mesh)
    stats = srv.run(reqs)
    reg = srv.registry
    assert stats["requests"] == 6

    # per-replica token/request series sum to the aggregate, and BOTH
    # replicas actually served (the DP split is real)
    per = [reg.value("serve_tokens_total", replica=r) for r in (0, 1)]
    assert sum(per) == reg.total("serve_tokens_total") == stats["tokens"]
    assert all(v > 0 for v in per), per
    assert reg.total("serve_requests_total") == 6

    # pool gauges per replica mirror the per-replica pool stats
    for r, ps in enumerate(stats["pages"]["per_replica"]):
        assert reg.value("kv_pages_peak", replica=r) == ps["peak_in_use"]
        assert reg.value("kv_pages_in_use", replica=r) == ps["in_use"]

    # prefix counters: replica series sum to the aggregated stats dict
    hits = sum(reg.value("prefix_hits", replica=r) for r in (0, 1))
    assert hits == stats["prefix"]["hits"] > 0
    assert reg.value("mesh_data_replicas") == 2
    assert reg.value("mesh_model_shards") == 2

    # the whole mesh run's exposition round-trips
    snap = parse_prometheus(reg.to_prometheus())
    for name in ("serve_tokens_total", "kv_pages_peak", "prefix_hits",
                 "serve_ttft_seconds_bucket", "mesh_data_replicas"):
        assert name in snap, name
    tok = {lbl["replica"]: v for lbl, v in snap["serve_tokens_total"]}
    assert tok == {"0": float(per[0]), "1": float(per[1])}
    print("OK mesh-metrics")
"""


def test_per_replica_metrics_sum_to_aggregate_2x2():
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH_METRICS)],
        capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
        env=subprocess_env(8),
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "OK mesh-metrics" in r.stdout


# ---------------------------------------------------------------------------
# Shared timing helper + autotune counters
# ---------------------------------------------------------------------------


def test_timeit_is_the_shared_clock():
    """kernel_bench and the autotuner must both delegate to
    ``obs.profile.timeit`` (one warmup discipline, median-of-k)."""
    from repro.obs.profile import timeit

    calls = []
    assert timeit(lambda: calls.append(1), iters=3, warmup=2) >= 0.0
    assert len(calls) == 5  # 2 warmup + 3 timed

    import inspect

    from benchmarks import kernel_bench
    from repro.engine import autotune

    assert "timeit" in inspect.getsource(kernel_bench._time)
    assert "timeit" in inspect.getsource(autotune.autotune)


def test_autotune_counters_ride_global_registry():
    from repro.engine.autotune import autotune, choose_block, get_cache

    reset_global_registry()
    try:
        choose_block(8, 256, 256, 4)  # cold cache: a miss
        g = global_registry()
        assert g.value("tune_cache_misses_total") == 1
        best, timings = autotune(lambda blk: None, 8, 256, 256, 4,
                                 candidates=[(8, 128, 128), (8, 256, 128)],
                                 iters=1)
        assert g.value("autotune_trials_total") == 2
        assert g.value("autotune_winners_total") == 1
        assert get_cache().get(8, 256, 256, 4) == best
        choose_block(8, 256, 256, 4)  # now served from the cache
        assert g.value("tune_cache_hits_total") == 1
    finally:
        reset_global_registry()
        from repro.engine.autotune import reset_cache
        reset_cache()


def test_step_timer_disabled_is_passthrough():
    """The step timer only files the step times it is handed (the loop's
    spans measure them); disabled, it records nothing."""
    from repro.obs import StepTimer

    on = StepTimer(Registry())
    off = StepTimer(NullRegistry())
    assert on.enabled and not off.enabled
    off.observe("decode", 0.25)
    assert off.summary() == {}
    on.observe("decode", 0.25)
    on.observe("decode", 0.75)
    s = on.summary()
    assert s["decode"] == {"total_s": 1.0, "count": 2, "mean_s": 0.5}
    hist = on.registry.histogram("serve_step_seconds")
    assert sum(h.count for _, h in hist.series()) == 2


def test_default_time_buckets_cover_serving_latencies():
    assert DEFAULT_TIME_BUCKETS[0] == pytest.approx(1e-4)
    assert DEFAULT_TIME_BUCKETS[-1] > 50  # ~52s: slow CI mesh runs fit
    assert list(DEFAULT_TIME_BUCKETS) == sorted(DEFAULT_TIME_BUCKETS)
