"""The one traffic generator and the window's feed."""
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from harness import serve, traffic

OPEN = {"loop": "open", "rate_per_s": 2.5, "order_seed": 20250310,
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "min": 32, "max": 2048},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 16, "max": 512}}
CLOSED = {"loop": "closed", "clients": 3, "pool": 40, "order_seed": 20250310,
          "prompt": {"dist": "lognormal", "median": 64, "sigma": 0.6,
                     "min": 16, "max": 256},
          "output": {"dist": "uniform", "min": 4, "max": 9}}


def _key(plan):
    return [(p.due, p.max_new, p.prompt.tobytes()) for p in plan]


def _work(plan):
    return [(p.due, p.max_new, len(p.prompt)) for p in plan]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, 2**31 + 12345, 40.0, 1000)
    b = traffic.schedule(mix, 2**31 + 12345, 40.0, 1000)
    c = traffic.schedule(mix, 7, 40.0, 1000)
    assert _key(a) == _key(b)
    # another seed draws other token ids for the same work
    assert _key(a) != _key(c)
    assert _work(a) == _work(c)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_lengths_inside_clips_and_same_set_every_seed(mix):
    sets = []
    for seed in (1, 2, 3):
        plan = traffic.schedule(mix, seed, 40.0, 1000)
        pl = np.array([len(p.prompt) for p in plan])
        ol = np.array([p.max_new for p in plan])
        assert pl.min() >= mix["prompt"]["min"]
        assert pl.max() <= mix["prompt"]["max"]
        assert ol.min() >= mix["output"]["min"]
        assert ol.max() <= mix["output"]["max"]
        assert all(p.prompt.max() < 1000 for p in plan)
        sets.append((sorted(pl), sorted(ol)))
    assert sets[0] == sets[1] == sets[2]
    # each whole block of the schedule is one stratified sample
    block = traffic.BLOCK
    pl = [len(p.prompt) for p in traffic.schedule(mix, 1, 40.0, 1000)]
    first = sorted(pl[:block])
    assert first == sorted(traffic.stratified(mix["prompt"], block))


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_order_seed_draws_the_same_sizes_in_another_order(mix):
    a = traffic.schedule(mix, 3, 40.0, 1000)
    b = traffic.schedule(dict(mix, order_seed=20250311), 3, 40.0, 1000)
    sizes_a = [(len(p.prompt), p.max_new) for p in a]
    sizes_b = [(len(p.prompt), p.max_new) for p in b]
    assert sizes_a != sizes_b
    for k in range(2):
        assert sorted(x[k] for x in sizes_a) == sorted(x[k] for x in sizes_b)


def test_open_loop_due_times_follow_the_rate():
    seconds = 40.0
    plan = traffic.schedule(OPEN, 3, seconds, 1000)
    due = np.array([p.due for p in plan])
    assert len(plan) == round(OPEN["rate_per_s"] * seconds)
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0)
    assert due[-1] < seconds
    # mean gap is the rate's, and gaps spread like an exponential
    gaps = np.diff(due)
    assert np.mean(gaps) == pytest.approx(1 / OPEN["rate_per_s"], rel=0.05)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.25)


def test_prefill_buckets_cover_the_prompt_range():
    assert traffic.prefill_buckets(OPEN, 8, 2560) == [
        32, 64, 128, 256, 512, 1024, 2048]
    assert traffic.prefill_buckets(
        dict(OPEN, prompt=dict(OPEN["prompt"], min=2048, max=8192)),
        8, 8320) == [2048, 4096, 8192]


class _FakeServer:
    """Serves each request after a delay, one token per tick, recording
    how many requests were in flight at once."""

    def __init__(self):
        self.max_wall_s = 0.0
        self.peak = 0

    def run(self, requests, on_token, *, feed, idle_wait_s):
        import time

        live, t0 = [], time.monotonic()
        while time.monotonic() - t0 < self.max_wall_s:
            live += feed()
            self.peak = max(self.peak, len(live))
            for r in list(live):
                r.out.append(1)
                r.done = len(r.out) >= r.max_new
                on_token(r, 1)
                if r.done:
                    live.remove(r)
            time.sleep(0.002)
        for r in live:
            r.status = "preempted"
        return {}


def test_closed_loop_never_exceeds_its_clients():
    fake = _FakeServer()
    built = serve.Built(None, fake, {}, CLOSED, {}, 0.0, 0)
    plan = traffic.schedule(CLOSED, 5, 1.0, 1000)
    w = serve.drive(built, plan, 0.3)
    assert fake.peak <= CLOSED["clients"]
    assert len(w.records) > CLOSED["clients"]   # clients sent again
    # a client's next request is due when its previous one finished
    by_client = {}
    for r in sorted(w.records.values(), key=lambda r: r.due):
        by_client.setdefault(r.client, []).append(r)
    for rs in by_client.values():
        for a, b in zip(rs, rs[1:]):
            assert b.due >= a.times[-1]
