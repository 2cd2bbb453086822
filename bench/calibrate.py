#!/usr/bin/env python3
"""Readings that the limit of a cell's check is set from, on the chip.

    python3 bench/calibrate.py --workload qwen3-0.6b.chat --seconds 30 \\
        --seeds 101,102,103

For each seed, in one process: a whole run of the cell as the benchmark
makes it (set-up, window, sample, reference), reporting the program's
widest gap and the control's: the reference computed with every matmul
operand rounded to float8 e4m3, whose first choice at each position of
the same sequences is read in the float32 reference, and judged by the
predicate that decides a run's ``correct``. The benchmark's own runs
never run the control. One JSON line per seed; exits 1 where the control
comes out correct on any seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    from harness import jaxenv

    jaxenv.start()
    from harness import cell

    control_passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = cell.run(args.workload, seed, args.seconds, False,
                       t_start=t0, control=True)
        if res["control_correct"]:
            control_passed.append(seed)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "control_correct": res["control_correct"],
            "widest_gap": res["checked"]["widest_gap"]["value"],
            "control_widest_gap": res["control_widest_gap"],
            "tokens": res["checked"]["served_tokens_compared"]["value"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "seconds": time.perf_counter() - t0}), flush=True)
    if control_passed:
        print(f"control judged correct on seeds {control_passed}: the "
              f"limit does not separate it", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
