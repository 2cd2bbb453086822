"""One run of one cell: set-up, window, metrics, the check, the result.

The result is one JSON object, printed as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, in a traced
run ``breakdown``, and last ``checked`` (each number compared, with its
limit), which also ends standard error.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time

from harness import check, config, peaks, serve, traffic
from harness import trace as trace_mod
from harness.context import Context

OUT = config.BENCH / "out"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def spec() -> dict:
    with open(config.BENCH.parent / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, name: str, traced: bool) -> list[dict]:
    group = bench["per_layer" if traced else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def reader(metric: str):
    path = config.BENCH / "metrics" / f"{metric}.py"
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def _device_info(require_tpu: bool, chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"need {chips} TPU chip(s), JAX found "
                         f"{len(devs)} {d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def _memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


def run(name: str, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True, override: dict | None = None,
        fault=None, compile_events=None, control: bool = False) -> dict:
    """One run of workload ``name``. ``override`` may replace the loaded
    ``bench``/``conf``/``cell``/``mix`` dicts (tests); ``fault(built)``
    breaks the timed path after warm-up (tests); ``control`` also reads
    the control and judges it by the run's own predicate
    (bench/calibrate.py)."""
    import jax

    override = override or {}
    bench = override.get("bench") or spec()
    wl = workload(bench, name)
    conf = override.get("conf") or config.load_json("configs", wl["config"])
    mix = override.get("mix") or config.load_json("traffic", wl["traffic"])
    cell = override.get("cell") or config.load_json("cells", name)
    device = _device_info(require_tpu, wl["chips"])
    peak = peaks.peaks(device["kind"]) if require_tpu else \
        override.get("peak", peaks.PEAKS["TPU v5 lite"])
    sizes = config.model_sizes(conf)

    built = serve.build(sizes, cell, mix, seed, observed=traced)
    widths = serve.warm_up(built)
    plan = traffic.schedule(mix, seed, seconds, sizes["vocab_size"])
    if fault is not None:
        fault(built)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (quantize {built.quantize_s:.3f} s), "
        f"prefill widths {widths}, {len(plan)} requests planned")

    trace_dir = None
    if traced:
        trace_dir = str(OUT / "trace" / f"{name}.{seed}")
        os.makedirs(trace_dir, exist_ok=True)
    win = serve.drive(built, plan, seconds, trace_dir=trace_dir,
                      compile_events=compile_events)
    device["memory_peak_bytes"] = _memory_peak()
    log(f"window {win.t_close - win.t_open:.3f} s: {len(win.records)} "
        f"requests sent, feed late by at most {win.late_s * 1e3:.1f} ms, "
        f"{win.compiles} compile events inside the window")
    if traced:
        log(f"profiler start {win.profiler_s[0]:.3f} s inside the window, "
            f"stop {win.profiler_s[1]:.3f} s after it")

    ctx = Context(sizes, cell, mix, peak, win, setup_s, built.quantize_s,
                  timeline=built.server.timeline.records())
    breakdown = None
    if traced:
        ctx.tracer = built.server.tracer.requests()
        if win.traced is not None:
            ev = trace_mod.extract(trace_dir)
            t0, t1, anchor = win.traced
            anc = [s for n, s, _ in ev["host"] if n == "bench.anchor"]
            ctx.trace_offset_ns = (anc[0] - anchor * 1e9) if anc else 0.0
            ctx.trace = trace_mod.Trace(ev, ctx.to_ns(t0), ctx.to_ns(t1))
            trace_mod.save(ev, os.path.join(trace_dir, "events.json.gz"))
            shutil.rmtree(os.path.join(trace_dir, "plugins"))
            device["busy_s"] = ctx.trace.busy_s()
            device["window_s"] = ctx.trace.window_s
            breakdown = {
                "device_ops": [[n, s] for n, s in ctx.trace.top_ops(10)],
                "idle_gaps": sorted(
                    ([n, s] for n, s in ctx.trace.idle_by_host().items()),
                    key=lambda kv: -kv[1])[:10],
            }
    metrics = {}
    for m in metrics_for(bench, name, traced):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    _dump(name, seed, ctx, metrics)
    attempted = len(win.records)
    failed = sum(1 for r in win.records.values()
                 if r.request.status not in ("ok", "preempted"))

    # -- the check: program state freed first, then the reference -----------
    picked = check.sample(win.records, seed, cell["check"]["sample_tokens"])
    del built, ctx, win
    gc.collect()
    jax.clear_caches()
    ref = check.load_reference(conf["reference"])
    t_ref = time.perf_counter()
    got = check.compare(ref, ref.RefConfig.from_model(sizes),
                        serve.weights_key(seed), picked, control=control,
                        pad_to=cell["server"]["max_len"])
    log(f"reference over {got['requests']} requests, {got['tokens']} "
        f"served tokens, {time.perf_counter() - t_ref:.3f} s")
    limit = cell["check"]["widest_gap"]["limit"]
    out = {"correct": check.verdict(got["widest_gap"], got["tokens"], limit),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        # the control in the program's place, judged as a run is
        out["control_correct"] = check.verdict(got["control_widest_gap"],
                                               got["tokens"], limit)
        out["control_widest_gap"] = got["control_widest_gap"]
    out["checked"] = {
        "widest_gap": {"value": got["widest_gap"], "limit": limit},
        "served_tokens_compared": {"value": got["tokens"], "limit": 1}}
    return out


def _dump(name: str, seed: int, ctx: Context, metrics: dict) -> None:
    """Per-run percentiles, feed lateness and compiles, kept under
    bench/out/runs and logged."""
    from harness import stats

    ttft, gaps = ctx.ttfts_s(), ctx.token_gaps_s()
    detail = {
        "metrics": {k: v["value"] for k, v in metrics.items()},
        "ttft_s": {q: stats.percentile(ttft, q) for q in (50, 70, 90, 95)},
        "itl_s": {q: stats.percentile(gaps, q) for q in (50, 90, 95, 99)},
        "requests": len(ttft), "gaps": len(gaps),
        "late_s": ctx.window.late_s, "compiles": ctx.window.compiles,
        "window_s": ctx.window.t_close - ctx.window.t_open,
    }
    os.makedirs(OUT / "runs", exist_ok=True)
    with open(OUT / "runs" / f"{name}.{seed}.json", "w") as f:
        json.dump(detail, f)
    log("detail " + json.dumps(detail))


def compare_lines(checked: dict) -> list[str]:
    return [f"[check] {k} {v['value']!r} limit {v['limit']!r}"
            for k, v in checked.items()]

