"""A sound run passes the check; the control (the reference with fp8
matmul operands in the program's place) fails it, judged by the same
predicate."""
import time

import pytest

import tiny
from harness import cell, check


def test_sound_run_passes_and_control_fails():
    res = cell.run("tiny.chat", 2**31 + 5, 2.0, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   override=tiny.override(), control=True)
    gap = res["checked"]["widest_gap"]
    assert res["correct"] is True
    assert res["control_correct"] is False
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"out_tok_per_s", "ttft_p70_ms",
                                   "itl_p90_ms", "itl_p99_ms", "setup_s"}
    assert gap["value"] <= gap["limit"] < res["control_widest_gap"]
    # the compared numbers come last in the result line
    assert list(res)[-1] == "checked"


@pytest.mark.parametrize("gap,tokens,expect", [
    (0.1, 300, True), (0.3, 300, True), (0.31, 300, False),
    (0.0, 0, False), (None, 0, False)])
def test_verdict(gap, tokens, expect):
    assert check.verdict(gap, tokens, 0.3) is expect
