#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark (``BENCHMARK.json``).

    python3 bench/run_cell.py --workload qwen3-0.6b.chat --seed 7 \\
        --seconds 30 --trace 0

Runs only on a TPU: JAX is held to it, and a run that finds no TPU, or
fewer chips than the cell asks for, exits nonzero without a result. The
persistent compile cache lives at ``.jax_cache`` in the checkout. The
last line of standard output is the result object (see harness/cell.py);
``--trace 1`` reports the per-layer metrics from a profiler trace kept
under ``bench/out/trace``.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness import jaxenv

    compiles = jaxenv.start()

    import json

    from harness import cell

    res = cell.run(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, compile_events=compiles)
    for line in cell.compare_lines(res["checked"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
