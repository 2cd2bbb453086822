"""Host-side profiling hooks: step timing, shared benchmark timing, jit
compile-count gauges, and the optional ``jax.profiler`` trace gate.

``timeit`` is THE timing helper for the repo — the autotuner and
``benchmarks/kernel_bench.py`` both use it, so a "winner" in the tune
cache and a bench row are measured the same way: warmup call(s) first
(compile excluded), ``jax.block_until_ready`` on every iteration's
output (async dispatch excluded), MEDIAN of k iterations (one GC pause
or interrupt can no longer crown the wrong block shape the way a mean
could).

``StepTimer`` wraps the serving loop's jitted seams (prefill / decode /
verify / draft): it blocks on the step's output and files the host wall
time into a per-seam histogram. Blocking is observational — jitted step
values are unchanged — and the timer is only installed when the registry
is live, so a ``NullRegistry`` run pays nothing (the bit-identity test
pins both configurations to the same streams and compile counts).

``compile_counts`` reads each jitted function's compilation-cache size in
one place — the source for the ``decode_compiles``-style stats the tests
pin AND the ``serve_jit_compiles`` gauges the registry exports, replacing
scattered manual ``_cache_size()`` bookkeeping.

``JaxProfile`` gates ``jax.profiler`` around N decode ticks
(``--jax-profile DIR``): tick-bounded so a long serve run produces a
readable trace of its steady state, not an unboundedly large one.
"""
from __future__ import annotations

import statistics
import time


def timeit(f, *args, iters: int = 5, warmup: int = 1) -> float:
    """Median wall seconds of ``f(*args)`` over ``iters`` timed runs.

    Each run blocks on the output (``jax.block_until_ready``) so async
    dispatch cannot hide device time; ``warmup`` untimed runs first so
    compilation never pollutes the measurement."""
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(f(*args))
    times = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


class StepTimer:
    """Per-seam wall timing for the serving loop's jitted device steps."""

    def __init__(self, registry, name: str = "serve_step_seconds",
                 help: str = "host wall seconds per jitted serving step "
                             "(block_until_ready)"):
        self.registry = registry
        self.enabled = registry is not None and registry.enabled
        self._hist = registry.histogram(name, help) if self.enabled else None
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}

    def run(self, seam: str, fn):
        """Execute ``fn()``; when live, block on its output and record the
        wall time under ``seam``. Pass-through when disabled."""
        if not self.enabled:
            return fn()
        import jax

        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self._hist.observe(dt, seam=seam)
        self.totals[seam] = self.totals.get(seam, 0.0) + dt
        self.counts[seam] = self.counts.get(seam, 0) + 1
        return out

    def summary(self) -> dict:
        """Per-seam totals — the tick-time breakdown (where a decode
        tick's wall time actually went)."""
        return {
            seam: {"total_s": self.totals[seam],
                   "count": self.counts[seam],
                   "mean_s": self.totals[seam] / self.counts[seam]}
            for seam in sorted(self.totals)
        }


def compile_counts(**jitted) -> dict[str, int]:
    """Compilation-cache sizes of jitted functions, by seam name.

    The single read path for compile discipline: the stats builder turns
    these into both the pinned ``*_compiles`` stats and the
    ``serve_jit_compiles{step=...}`` gauges."""
    out = {}
    for name, fn in jitted.items():
        if fn is None:
            continue
        try:
            out[name] = int(fn._cache_size())
        except AttributeError:  # not a jitted function (e.g. a plain fn)
            out[name] = 0
    return out


class JaxProfile:
    """Tick-gated ``jax.profiler`` trace around the serving loop.

    Starts the profiler at the first decode tick and stops it after
    ``ticks`` more (or at run end, whichever comes first). A trace that
    cannot start or stop fails the run: a run asked to profile that
    quietly produced no trace would be read as a measured one."""

    def __init__(self, outdir: str, ticks: int = 8):
        if ticks < 1:
            raise ValueError(f"profile ticks must be >= 1, got {ticks}")
        self.outdir = str(outdir)
        self.ticks = ticks
        self.active = False
        self.done = False
        self._start_tick: int | None = None

    def on_tick(self, tick: int) -> None:
        if self.done:
            return
        if not self.active:
            import jax

            jax.profiler.start_trace(self.outdir)
            self.active = True
            self._start_tick = tick
        elif tick - self._start_tick >= self.ticks:
            self.stop()

    def stop(self) -> None:
        self.done = True
        if not self.active:
            return
        import jax

        self.active = False
        jax.profiler.stop_trace()
