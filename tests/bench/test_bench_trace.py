"""The reduction from a profiler trace to the per-layer metrics."""
import types

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from harness import cell, peaks, stats, work
from harness import trace as tr
from harness.context import Context

MS = 1_000_000  # ns

# two decode programs and one prefill program on device 0; a kernel event
# in each; an idle gap while the host picks tokens
EVENTS = {
    "devices": 1,
    "modules": [[0, "jit_decode_step", 10 * MS, 20 * MS],
                [0, "jit__prefill_fn", 40 * MS, 30 * MS],
                [0, "jit_decode_step", 75 * MS, 20 * MS],
                [0, "jit__argmax", 95 * MS, 1 * MS]],
    "ops": [[0, "while", 10 * MS, 20 * MS],
            [0, "paged_attention_pallas", 11 * MS, 8 * MS],
            [0, "splitq_packed_matmul_pallas", 20 * MS, 4 * MS],
            [0, "splitq_packed_matmul_pallas", 45 * MS, 10 * MS],
            [0, "while", 75 * MS, 20 * MS],
            [0, "paged_attention_pallas", 76 * MS, 8 * MS],
            [0, "splitq_packed_matmul_pallas", 85 * MS, 4 * MS]],
    "host": [["bench.anchor", 1 * MS, 0],
             ["host.decode_step", 8 * MS, 24 * MS],
             ["host.pick", 30 * MS, 9 * MS],
             ["host.prefill_wave", 39 * MS, 35 * MS],
             ["host.decode_step", 73 * MS, 24 * MS]],
}


def test_names_are_cut_to_the_op():
    assert tr.op_name("%paged_attention_pallas.9 = bf16[16,16,128]{2,1,0} "
                      "custom-call(s32[16,160] %x)") == "paged_attention_pallas"
    assert tr.op_name("%while.10 = (s32[]) while(%t)") == "while"
    assert tr.module_name("jit_decode_step(1104081240)") == "jit_decode_step"


def test_busy_union_and_idle_share():
    t = tr.Trace(EVENTS, 0, 100 * MS)
    # programs cover 10-30, 40-70, 75-96 ms
    assert t.busy_s() == pytest.approx(0.071)
    assert t.window_s == pytest.approx(0.1)
    assert t.idle_gaps() == [(0, 10 * MS), (30 * MS, 40 * MS),
                             (70 * MS, 75 * MS), (96 * MS, 100 * MS)]
    idle = t.idle_by_host()
    assert idle["host.pick"] == pytest.approx(0.010)
    assert idle["host.other"] == pytest.approx(0.014)
    assert idle["host.prefill_wave"] == pytest.approx(0.005)
    # a clipped interval counts only what lies inside it
    assert tr.Trace(EVENTS, 20 * MS, 50 * MS).busy_s() == pytest.approx(0.02)


def test_self_time_and_top_ops():
    t = tr.Trace(EVENTS, 0, 100 * MS)
    top = dict(t.top_ops())
    # each while encloses 12 ms of kernels: 8 ms of its own remain
    assert top["while"] == pytest.approx(0.016)
    assert top["paged_attention_pallas"] == pytest.approx(0.016)
    assert top["splitq_packed_matmul_pallas"] == pytest.approx(0.018)


def test_kernel_events_fall_inside_their_programs():
    t = tr.Trace(EVENTS, 0, 100 * MS)
    dec = t.programs_in("decode_step", [(8 * MS, 32 * MS), (73 * MS, 97 * MS)])
    assert len(dec) == 2
    # the prefill program's packed matmul does not count toward decode
    assert t.op_time_in("^splitq_packed_matmul", dec) == pytest.approx(0.008)
    assert t.op_time_in("^paged_attention", dec) == pytest.approx(0.016)
    pre = t.programs_in("prefill", [(39 * MS, 72 * MS)])
    assert t.op_time_in("^splitq_packed_matmul", pre) == pytest.approx(0.010)


def _ctx():
    sizes = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                 head_dim=64, d_ff=512, vocab_size=1000,
                 tie_embeddings=False)
    win = types.SimpleNamespace(
        t_open=0.0, t_close=0.1, records={},
        # host seconds; the anchor maps 0 s to 0 ns
        steps=[(0.008, 0.032, [100, 200]), (0.073, 0.097, [101, 201])],
        waves=[(0.039, 0.072, [(0, 300)])])
    ctx = Context(sizes, {"server": {"slots": 4}}, {},
                  peaks.peaks("TPU v5 lite"), win, 1.0, 0.5)
    ctx.trace = tr.Trace(EVENTS, 0, 100 * MS)
    return ctx


def test_roofline_and_mfu_arithmetic():
    ctx = _ctx()
    p = ctx.peak
    read = {m: cell.reader(m) for m in (
        "step_mfu.decode", "step_mfu.prefill", "packed_mm_roofline.decode",
        "paged_attn_roofline", "device_idle_share")}
    flops = sum(work.decode_token_flops(ctx.sizes, q)
                for q in (100, 200, 101, 201))
    assert read["step_mfu.decode"](ctx) == pytest.approx(
        100 * flops / (0.040 * p["bf16_flops"]))
    assert read["step_mfu.prefill"](ctx) == pytest.approx(
        100 * work.prefill_flops(ctx.sizes, 0, 300)
        / (0.030 * p["bf16_flops"]))
    per_step = sum(peaks.roofline_s(*work.packed_matmul_work(m, k, n), p)
                   for m, k, n in work.decode_packed_calls(ctx.sizes, 4))
    assert read["packed_mm_roofline.decode"](ctx) == pytest.approx(
        100 * 2 * per_step / 0.008)
    need = 0.0
    for rows in ((100, 200), (101, 201)):
        f = sum(work.paged_attention_work(ctx.sizes, q + 1)[0] for q in rows)
        b = sum(work.paged_attention_work(ctx.sizes, q + 1)[1] for q in rows)
        need += peaks.roofline_s(f, b, p)
    assert read["paged_attn_roofline"](ctx) == pytest.approx(
        100 * need / 0.016)
    assert read["device_idle_share"](ctx) == pytest.approx(29.0)


def test_readers_return_nothing_without_a_trace():
    ctx = _ctx()
    ctx.trace = None
    for m in ("step_mfu.decode", "paged_attn_roofline",
              "device_idle_share"):
        assert cell.reader(m)(ctx) is None


def test_percentiles_over_all_samples():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 90) == pytest.approx(90.1)
    assert stats.percentile(vals, 50) == pytest.approx(50.5)
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([], 95) is None
    # not a median of chunk percentiles
    chunks = [vals[:50], vals[50:]]
    assert stats.percentile(vals, 90) != sum(
        stats.percentile(c, 90) for c in chunks) / 2
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def test_context_percentiles_count_waiting_requests():
    rec = types.SimpleNamespace
    win = types.SimpleNamespace(t_open=0.0, t_close=10.0, records={
        1: rec(due=1.0, times=[1.5, 1.7, 2.0]),
        2: rec(due=2.0, times=[]),             # never served: waits 8 s
        3: rec(due=9.0, times=[9.2, 11.0])})   # last token after the close
    ctx = Context({}, {}, {}, {}, win, 0.0, 0.0)
    assert sorted(ctx.ttfts_s()) == pytest.approx([0.2, 0.5, 8.0])
    assert sorted(ctx.token_gaps_s()) == pytest.approx([0.2, 0.3])


def test_a_recorded_chip_trace():
    """A piece of a traced qwen3-0.6b.chat run on one v5e (two decode steps
    and a prefill wave of 16 rows), as ``trace.extract`` kept it."""
    ev = tr.load(str(tiny.BENCH / "testdata" / "qwen3_chat_trace.json.gz"))
    mods = sorted(ev["modules"], key=lambda m: m[2])
    t = tr.Trace(ev, mods[0][2], mods[-1][2] + mods[-1][3])
    dec = t.programs_in("decode_step", [(m[2], m[2] + m[3]) for m in mods])
    pre = t.programs_in("prefill", [(m[2], m[2] + m[3]) for m in mods])
    assert len(dec) == 2 and len(pre) == 1
    # 28 layers: one paged kernel and four packed launches per layer, the
    # tied head is no packed launch
    n = lambda pat, progs: sum(  # noqa: E731
        1 for _, name, s, _ in ev["ops"] if name.startswith(pat)
        and any(a <= s < b for _, a, b in progs))
    assert n("paged_attention", dec) == 2 * 28
    assert n("splitq_packed_matmul", dec) == 2 * 4 * 28
    assert n("splitq_packed_matmul", pre) == 4 * 28
    assert n("paged_attention", pre) == 0
    paged = t.op_time_in("^paged_attention", dec)
    assert 0 < paged < Context.program_s(dec)
    assert 0 < t.busy_s() < t.window_s
    assert dict(t.top_ops(10))["paged_attention_pallas"] == pytest.approx(
        paged)
