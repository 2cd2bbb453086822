"""Spans inside the serving loop (``Observability.span``).

* every prefill wave, decode step and verify round writes one timeline
  record whose ``span`` field holds its ends and its phases' ends —
  ``prepare``, ``dispatch``, ``fetch``, ``emit``, in order, disjoint and
  inside the round — and what it computed for whom,
* a disabled bundle hands back one shared no-op: no span, no profiler
  annotation, no timeline field,
* nothing in the span or step-time path blocks on the device,
* an idle service loop writes no timeline record per poll,
* the programs keep the names profiles are read by (``decode_step``,
  ``prefill``), on one device and on a mesh.
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantPolicy, restructure
from repro.launch.mesh import make_mesh
from repro.launch.serve import BatchedServer, Request
from repro.models import build_model
from repro.obs import NO_SPAN, Observability

PHASES = ["prepare", "dispatch", "fetch", "emit"]


def _tiny_model(n_layers=2):
    cfg = dataclasses.replace(get_config("llama32-1b").reduced(),
                              n_layers=n_layers)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg, lens, gen):
    return [Request(i, np.random.default_rng(100 + i).integers(
        0, cfg.vocab_size, ln, dtype=np.int32), gen)
        for i, ln in enumerate(lens)]


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` and counts opens."""

    def __init__(self):
        self.names: list[str] = []

    def __call__(self, name, **kw):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _check_round(rec):
    sp = rec["span"]
    assert list(sp["phases"]) == PHASES, sp["phases"]
    t = sp["t0"]
    for name in PHASES:
        a, b = sp["phases"][name]
        assert t <= a <= b, (rec["kind"], name, sp)
        t = b
    assert t <= sp["t1"]
    assert len(sp["rids"]) == rec["rows"]
    return sp


@pytest.mark.parametrize("speculate", [0, 3])
def test_every_round_records_its_span_and_phases(speculate):
    cfg, model, params = _tiny_model()
    draft = (restructure(params, QuantPolicy(bits=4, packed=True))
             .as_executable(group=True) if speculate else None)
    reqs = _requests(cfg, [6, 11, 4, 9], 5)
    server = BatchedServer(model, params, batch_slots=2, max_len=32,
                           paged=True, page_size=4, num_pages=24,
                           speculate=speculate, draft_params=draft)
    stats = server.run(reqs)
    tl = server.timeline
    kind = "verify" if speculate else "decode"
    steps = tl.records(kind)
    assert len(steps) == stats["decode_steps"] > 0
    waves = tl.records("prefill")
    assert len(waves) == stats["prefill_waves"] > 0
    prompts = {r.rid: len(r.prompt) for r in reqs}
    for rec in waves:
        sp = _check_round(rec)
        assert len(sp["rows"]) == rec["rows"]
        assert sum(n for _, n in sp["rows"]) == rec["tokens"]
        for rid, (start, n) in zip(sp["rids"], sp["rows"]):
            assert 0 <= start and start + n <= prompts[rid]
    for rec in steps:
        sp = _check_round(rec)
        assert [p >= prompts[rid] - 1 for rid, p in
                zip(sp["rids"], sp["positions"])] == [True] * rec["rows"]
    # rounds follow one another: no two spans overlap
    spans = sorted((r["span"]["t0"], r["span"]["t1"]) for r in steps + waves)
    assert all(b <= c for (_, b), (c, _) in zip(spans, spans[1:]))
    # the step timer is fed by the same spans, one entry per round
    st = stats["obs"]["step_time"]
    assert st[kind]["count"] == len(steps)
    assert st["prefill"]["count"] == len(waves)
    for seam, recs in ((kind, steps), ("prefill", waves)):
        want = sum(r["span"]["phases"]["fetch"][1]
                   - r["span"]["phases"]["dispatch"][0] for r in recs)
        assert st[seam]["total_s"] == pytest.approx(want)


def test_request_spans_end_with_tokens_on_the_host():
    cfg, model, params = _tiny_model()
    reqs = _requests(cfg, [6, 11], 4)
    server = BatchedServer(model, params, batch_slots=2, max_len=32)
    server.run(reqs)
    ends = {(r["span"]["phases"]["dispatch"][0],
             r["span"]["phases"]["fetch"][1])
            for r in server.timeline.records()
            if r["kind"] in ("prefill", "decode")}
    for r in reqs:
        spans = [s for s in server.tracer.request(r.rid)["spans"]
                 if s["kind"] in ("prefill", "decode")]
        assert spans and all((s["t0"], s["t1"]) in ends for s in spans)


def test_disabled_bundle_records_no_span(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    off = Observability.disabled()
    sp = off.span("host.serve.decode", rows=3)
    assert sp is NO_SPAN and off.span("host.serve.feed") is NO_SPAN
    assert sp.phase("fetch") is NO_SPAN and not sp.live
    with sp as inner:
        assert inner is NO_SPAN
    assert ann.names == []

    cfg, model, params = _tiny_model()
    server = BatchedServer(model, params, batch_slots=2, max_len=32,
                           obs=Observability.disabled())
    stats = server.run(_requests(cfg, [6, 11, 4], 4))
    assert ann.names == []
    assert not any("span" in r for r in server.timeline.records())
    assert stats["obs"]["step_time"] == {}


def test_live_spans_never_block_on_the_device(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the serving loop blocked on the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    cfg, model, params = _tiny_model()
    server = BatchedServer(model, params, batch_slots=2, max_len=32)
    assert server.registry.enabled and server.obs.step_timer.enabled
    stats = server.run(_requests(cfg, [6, 11, 4], 4))
    assert stats["obs"]["step_time"]["decode"]["count"] > 0
    names = set(ann.names)
    assert {"host.serve.retire", "host.serve.admit"} <= names
    assert {f"host.serve.{k}.{p}" for k in ("prefill", "decode")
            for p in PHASES} <= names


def test_idle_service_loop_adds_no_timeline_record(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    cfg, model, params = _tiny_model()
    server = BatchedServer(model, params, batch_slots=2, max_len=32,
                           max_wall_s=1.0)
    before = server.timeline.seq
    server.run([], feed=lambda: [], idle_wait_s=0.0005)
    added = [r["kind"] for r in server.timeline.records()][before:]
    assert added == ["drain"]
    # the loop did poll: every idle wait is a profiler span, not a record
    assert ann.names.count("host.serve.idle") > 100
    assert ann.names.count("host.serve.feed") > 100


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh"])
def test_programs_keep_the_names_profiles_read(mesh):
    cfg, model, params = _tiny_model(n_layers=1)
    server = BatchedServer(
        model, params, batch_slots=2, max_len=16, paged=True, page_size=4,
        mesh=make_mesh((1, 1), ("data", "model")) if mesh else None)
    slots = server.slots
    put = server._put
    dec = server._decode.lower(
        server.params, put(np.zeros((slots, 1), np.int32)), server.cache,
        active=put(np.ones((slots,), bool)))
    pre = server._prefill.lower(
        server.params, put(np.zeros((slots, 8), np.int32)),
        put(np.full((slots,), 8, np.int32)), put(np.ones((slots,), bool)),
        put(np.zeros((slots,), np.int32)), server.cache)
    assert re.search("decode_step", _module_name(dec))
    assert re.search("prefill", _module_name(pre))
