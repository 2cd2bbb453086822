#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest offered rate the
server sustains without a growing backlog.

    python3 bench/sweep.py --workload qwen3-0.6b.chat --seconds 40 \\
        --rates 0.4,0.6,0.8,1.0,1.2

Builds the cell once, then serves one window per rate with that rate in
place of the mix's own, and prints per rate: requests sent and finished,
output tokens/s, TTFT p50/p90 over the whole window and over each half,
and the queue left at the close. A rate sustains when the second half's
TTFT does not run away from the first's and the queue stays near empty.
The knee found so is written into the mix file by hand, as a number.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    from harness import jaxenv

    jaxenv.start()
    from harness import cell, config, serve, stats, traffic

    wl = cell.workload(cell.spec(), args.workload)
    conf = config.load_json("configs", wl["config"])
    mix = config.load_json("traffic", wl["traffic"])
    cl = config.load_json("cells", args.workload)
    cell._device_info(True, wl["chips"])
    sizes = config.model_sizes(conf)
    built = serve.build(sizes, cl, mix, args.seed, observed=False)
    serve.warm_up(built)
    cell.log(f"set-up {time.perf_counter() - T_START:.1f} s")
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate)
        built.mix = m
        plan = traffic.schedule(m, args.seed, args.seconds,
                                sizes["vocab_size"])
        w = serve.drive(built, plan, args.seconds)
        mid = w.t_open + args.seconds / 2
        recs = list(w.records.values())

        def ttft(rs):
            return [(r.times[0] if r.times else w.t_close) - r.due for r in rs]

        toks = sum(1 for r in recs for t in r.times if t <= w.t_close)
        first = [r for r in recs if r.due < mid]
        second = [r for r in recs if r.due >= mid]
        fin = sum(1 for r in recs if r.request.done
                  and r.request.status == "ok")
        waiting = sum(1 for r in recs if not r.times)
        row = {
            "rate": rate, "sent": len(recs), "finished": fin,
            "waiting_at_close": waiting,
            "out_tok_per_s": toks / (w.t_close - w.t_open),
            "ttft_p50_s": stats.percentile(ttft(recs), 50),
            "ttft_p90_s": stats.percentile(ttft(recs), 90),
            "ttft_p90_first_half_s": stats.percentile(ttft(first), 90),
            "ttft_p90_second_half_s": stats.percentile(ttft(second), 90),
            "itl_p95_s": stats.percentile(
                [b - a for r in recs for a, b in zip(r.times, r.times[1:])],
                95),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
