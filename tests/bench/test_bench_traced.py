"""A traced run serves as an untraced one does: request spans are recorded,
the registry (whose StepTimer blocks on every jitted call) stays off."""
import json
import time

import tiny
from harness import cell

SPEC = dict(tiny.BENCH_SPEC, per_layer=[
    {"name": "queue_wait_p70_ms", "unit": "ms"},
    {"name": "decode_rows_mean", "unit": "rows"}])
SEED = 2**31 + 9


def test_traced_run_keeps_the_registry_off_and_reads_spans():
    seen = []
    res = cell.run("tiny.chat", SEED, 2.0, True, t_start=time.perf_counter(),
                   require_tpu=False, override=tiny.override(bench=SPEC),
                   fault=lambda b: seen.append(b.server.obs))
    obs = seen[0]
    assert not obs.registry.enabled and not obs.step_timer.enabled
    assert obs.tracer.enabled
    assert res["correct"] is True
    assert set(res["metrics"]) == {"queue_wait_p70_ms", "decode_rows_mean"}
    assert res["device"]["window_s"] > 0
    # in one run a request's queue wait ends before its first token
    detail = json.loads(
        (cell.OUT / "runs" / f"tiny.chat.{SEED}.json").read_text())
    wait = res["metrics"]["queue_wait_p70_ms"]["value"]
    assert 0 <= wait <= detail["ttft_s"]["70"] * 1e3


class _IdleServer:
    """Runs the feed until its wall limit; serves nothing."""

    max_wall_s = 0.0
    active: list = []

    def _fill_slots(self):
        pass

    _prefill_wave = step = _pick_tokens = _fill_slots

    def run(self, requests, on_token, *, feed, idle_wait_s):
        t0 = time.monotonic()
        while time.monotonic() - t0 < self.max_wall_s:
            feed()
            time.sleep(0.005)
        return {}


def test_profiler_covers_the_windows_end_and_stops_after_it(tmp_path):
    from harness import serve, traffic

    mix = dict(tiny.MIX)
    built = serve.Built(None, _IdleServer(), {}, mix, {}, 0.0, 0)
    plan = traffic.schedule(mix, 1, 0.8, 100)
    w = serve.drive(built, plan, 0.8, trace_dir=str(tmp_path))
    t0, t1, _ = w.traced
    assert t0 >= w.t_open + 0.8 * (1 - serve.TRACE_SHARE)
    # the stop, which stalls the caller, comes once the window has closed
    assert t1 >= w.t_close
    assert w.profiler_s[1] > 0
