"""The program's own spans against what the benchmark reads.

A traced run of the tiny cell on the CPU: the program's wave and step
records (``span`` fields of the timeline's ``prefill``/``decode`` records)
hold what the benchmark's wrappers rebuild from outside (``Window.waves``,
``Window.steps``), and the profiler trace shows each wave, step and phase
on the same boundaries. Then the two readers of those records, on small
hand-built timelines.
"""
import types

import pytest

import tiny
from harness import cell, config, serve, traffic
from harness import trace as tr
from harness.context import Context

SEED = 2**31 + 23
SECONDS = 3.0
PHASES = ["prepare", "dispatch", "fetch", "emit"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace")
    sizes = config.model_sizes(tiny.CONF)
    built = serve.build(sizes, tiny.CELL, tiny.MIX, SEED, observed=True)
    serve.warm_up(built)
    plan = traffic.schedule(tiny.MIX, SEED, SECONDS, sizes["vocab_size"])
    win = serve.drive(built, plan, SECONDS, trace_dir=str(out))
    events = tr.extract(str(out))
    ctx = Context(sizes, tiny.CELL, tiny.MIX, {}, win, 0.0, 0.0,
                  timeline=built.server.timeline.records())
    return types.SimpleNamespace(win=win, ctx=ctx, events=events)


def _rounds(ctx, kind):
    """The program's ``kind`` records inside the window, in order."""
    return [r for r in ctx.timeline if r["kind"] == kind
            and ctx.in_window(r["span"]["t0"])]


def test_program_records_hold_what_the_wrappers_rebuild(traced):
    win, ctx = traced.win, traced.ctx
    steps, waves = _rounds(ctx, "decode"), _rounds(ctx, "prefill")
    assert steps and waves
    assert [r["span"]["positions"] for r in steps] == [
        pos for _, _, pos in win.steps]
    assert [[tuple(x) for x in r["span"]["rows"]] for r in waves] == [
        rows for _, _, rows in win.waves]
    # each program span lies inside the wrapper around its method
    for recs, outer in ((steps, win.steps), (waves, win.waves)):
        for r, (t0, t1, _) in zip(recs, outer):
            assert t0 <= r["span"]["t0"] <= r["span"]["t1"] <= t1


def test_profiler_spans_match_the_timeline_one_for_one(traced):
    win, ctx, ev = traced.win, traced.ctx, traced.events
    anchor = [s for n, s, _ in ev["host"] if n == "bench.anchor"]
    assert anchor
    offset = anchor[0] - win.traced[2] * 1e9
    lo = win.traced[0]
    matched = 0
    for kind in ("prefill", "decode"):
        recs = [r for r in _rounds(ctx, kind) if r["span"]["t0"] >= lo]
        for name, key in [(f"host.serve.{kind}", None)] + [
                (f"host.serve.{kind}.{p}", p) for p in PHASES]:
            got = sorted((s, d) for n, s, d in ev["host"] if n == name)
            want = [r["span"]["phases"][key] if key else
                    [r["span"]["t0"], r["span"]["t1"]] for r in recs]
            assert len(got) == len(want), (name, len(got), len(want))
            for (s, d), (a, b) in zip(got, want):
                assert abs(s - (a * 1e9 + offset)) < 0.5e6, name
                assert abs(d - (b - a) * 1e9) < 0.5e6, name
                matched += 1
    assert matched >= 10
    # the loop top is named too
    names = {n for n, _, _ in ev["host"]}
    assert {"host.serve.feed", "host.serve.retire"} <= names


def test_readers_report_the_traced_run(traced):
    for name in ("prefill_wave_p50_ms", "decode_host_gap_p50_ms"):
        v = cell.reader(name)(traced.ctx)
        assert v is not None and v > 0, name


# -- the readers on hand-built timelines -------------------------------------

def _ctx(records, t_open=0.0, t_close=100.0):
    win = types.SimpleNamespace(t_open=t_open, t_close=t_close)
    return Context({}, {}, {}, {}, win, 0.0, 0.0, timeline=records)


def _round(kind, t0, dispatch, fetch, t1):
    """A record whose span dispatches over ``dispatch`` and fetches over
    ``fetch`` (seconds)."""
    return {"kind": kind, "t": t1, "span": {
        "t0": t0, "t1": t1, "phases": {
            "prepare": [t0, dispatch[0]], "dispatch": list(dispatch),
            "fetch": list(fetch), "emit": [fetch[1], t1]}}}


def test_prefill_wave_reader():
    read = cell.reader("prefill_wave_p50_ms")
    recs = [
        _round("prefill", 1.0, (1.1, 1.2), (1.2, 2.1), 2.2),     # 1.0 s
        _round("decode", 2.3, (2.4, 2.5), (2.5, 2.6), 2.7),
        _round("prefill", 3.0, (3.1, 3.2), (3.2, 3.4), 3.5),     # 0.3 s
        _round("prefill", 5.0, (5.1, 5.2), (5.2, 5.8), 5.9),     # 0.7 s
        _round("prefill", 150.0, (150.1, 150.2), (150.2, 160.0), 160.1),
    ]
    assert read(_ctx(recs)) == pytest.approx(700.0)
    # a window with no wave reads nothing
    assert read(_ctx(recs, 2.25, 2.8)) is None
    # records without spans (spans off) read nothing
    bare = [{k: v for k, v in r.items() if k != "span"} for r in recs]
    assert read(_ctx(bare)) is None
    assert read(_ctx([])) is None


def test_decode_host_gap_reader():
    read = cell.reader("decode_host_gap_p50_ms")
    recs = [
        _round("decode", 1.000, (1.001, 1.002), (1.002, 1.060), 1.061),
        # fetched at 1.060, next dispatch done at 1.063: 3 ms
        _round("decode", 1.061, (1.062, 1.063), (1.063, 1.120), 1.121),
        # fetched at 1.120, next dispatch done at 1.125: 5 ms
        _round("decode", 1.121, (1.123, 1.125), (1.125, 1.180), 1.181),
        _round("prefill", 1.181, (1.182, 1.183), (1.183, 2.0), 2.001),
        # the pair split by the wave counts nothing
        _round("decode", 2.001, (2.002, 2.004), (2.004, 2.060), 2.061),
        # fetched at 2.060, next dispatch done at 2.064: 4 ms
        _round("decode", 2.061, (2.062, 2.064), (2.064, 2.120), 2.121),
    ]
    assert read(_ctx(recs)) == pytest.approx(4.0)
    # a step outside the window pairs with nothing
    assert read(_ctx(recs, 1.05, 1.9)) == pytest.approx(5.0)
    # a window with one step, or steps split by a wave, reads nothing
    assert read(_ctx(recs, 1.05, 1.12)) is None
    assert read(_ctx([recs[2], recs[3], recs[4]])) is None
    bare = [{k: v for k, v in r.items() if k != "span"} for r in recs]
    assert read(_ctx(bare)) is None
