"""Set-up and the measured window of a serving cell.

Set-up, in order: the weights are made on the device from the seed in one
jitted ``model.init``; ``restructure`` quantizes them to packed SplitQuantV2
and ``as_executable(group=True)`` builds the grouped executable tree (timed
to ``block_until_ready`` as ``quantize_s``); ``BatchedServer(paged=True)``
is built; every prefill width the mix can ask for, and the decode step, run
once. The window then drives ``BatchedServer.run(..., feed=...)`` in
service mode, with token times taken on the client side.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from harness import traffic

WARM_RID = 1 << 30
# a traced run profiles the last quarter of the window, at most
# TRACE_MAX_S (a decode step writes thousands of device events); the
# profiler stops once the window has closed, because stopping it stalls
# the serving loop for seconds while the trace is collected
TRACE_SHARE = 0.25
TRACE_MAX_S = 6.0


def weights_key(seed: int):
    """A PRNG key for the weights, from any whole-number seed."""
    import jax

    word = np.random.SeedSequence([seed, 0x5EED]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


@dataclasses.dataclass
class Built:
    model: object
    server: object
    cell: dict
    mix: dict
    sizes: dict
    quantize_s: float
    weight_bytes: int


def build(sizes: dict, cell: dict, mix: dict, seed: int, *,
          observed: bool) -> Built:
    import jax

    from repro.configs.base import ArchConfig
    from repro.core import QuantPolicy, restructure
    from repro.launch.serve import BatchedServer
    from repro.models import build_model
    from repro.obs import NullRegistry, Observability, Timeline, Tracer

    arch = ArchConfig(**{k: v for k, v in sizes.items()
                         if k in ArchConfig.__dataclass_fields__})
    model = build_model(arch)
    params = jax.jit(model.init)(weights_key(seed))
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    qm = restructure(params, QuantPolicy(bits=cell["quant"]["bits"],
                                         packed=True))
    ex = qm.as_executable(group=True)
    jax.block_until_ready(ex)
    quantize_s = time.perf_counter() - t0
    del params, qm
    srv = cell["server"]
    # the timeline ring must hold every record of a window
    cap = 1_000_000
    # a traced run records request spans but keeps the registry off: with
    # it live, StepTimer blocks on every jitted call and the window serves
    # differently from an untraced run
    obs = (Observability(registry=NullRegistry(), tracer=Tracer(),
                         timeline=Timeline(cap=cap))
           if observed else Observability.disabled(trace_cap=cap))
    server = BatchedServer(model, ex, batch_slots=srv["slots"],
                           max_len=srv["max_len"], paged=True,
                           page_size=srv["page_size"],
                           num_pages=srv["num_pages"], obs=obs)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(ex))
    return Built(model, server, cell, mix, sizes, quantize_s, nbytes)


def warm_up(b: Built) -> list[int]:
    """Run one request per prefill width the mix can use (each compiles its
    prefill program) through the decode step; returns the widths."""
    from repro.launch.serve import Request

    srv = b.cell["server"]
    widths = traffic.prefill_buckets(b.mix, b.server.bucket_min,
                                     srv["max_len"])
    rng = np.random.default_rng(0)
    for i, w in enumerate(widths):
        n = min(w, srv["max_len"] - 1)
        ids = rng.integers(0, b.sizes["vocab_size"], n, dtype=np.int32)
        b.server.run([Request(WARM_RID + i, ids, 2)])
    return widths


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""
    rid: int
    client: int
    due: float              # monotonic seconds
    prompt: np.ndarray
    max_new: int
    times: list = dataclasses.field(default_factory=list)
    request: object = None


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    records: dict           # rid -> Record, every request sent
    late_s: float           # how late the feed handed over due requests
    traced: tuple | None    # (t0, t1, anchor) monotonic: the traced
    #                         interval and the start of its anchor span
    profiler_s: tuple       # what starting and stopping the profiler took
    compiles: int
    steps: list             # traced runs: decode steps (see _annotate)
    waves: list             # traced runs: prefill waves


class _Hooks:
    """Optional profiler interval at the end of the window, and host
    spans. ``start_s`` and ``stop_s`` are what starting and stopping the
    profiler took (the start inside the window, the stop after it)."""

    def __init__(self, start: float, outdir: str | None):
        self.start, self.outdir = start, outdir
        self.t0 = self.t1 = self.anchor = None
        self.start_s = self.stop_s = 0.0

    def poll(self, now: float) -> None:
        import jax

        if self.outdir is None or self.t0 is not None or now < self.start:
            return
        jax.profiler.start_trace(self.outdir)
        self.t0 = time.monotonic()
        self.start_s = self.t0 - now
        # ties the host clock to the trace's: this span starts at
        # ``anchor`` on the monotonic clock
        self.anchor = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.anchor"):
            pass

    def stop(self) -> None:
        import jax

        if self.t0 is not None and self.t1 is None:
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()
            self.stop_s = time.monotonic() - self.t1


def _annotate(server, steps: list, waves: list) -> None:
    """Wrap the server's host-side entry points in profiler annotations, so
    idle gaps on the device can be named by what the host was doing, and
    record what each decode step and prefill wave computed: ``steps`` gets
    ``(t0, t1, positions)`` (the position each live row fed), ``waves``
    gets ``(t0, t1, [(start, n), ...])`` (the prompt span each row fed)."""
    import jax

    def annotated(fn, label):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **kw)
        return wrapped

    def recorded_step(fn):
        def wrapped():
            live = [(r, len(r.out)) for r in server.active if r is not None]
            t0 = time.monotonic()
            out = fn()
            t1 = time.monotonic()
            pos = [len(r.prompt) + n - 1 for r, n in live if len(r.out) > n]
            if pos:
                steps.append((t0, t1, pos))
            return out
        return wrapped

    def recorded_wave(fn):
        def wrapped():
            live = [(r, r.fed) for r in server.active if r is not None]
            t0 = time.monotonic()
            out = fn()
            t1 = time.monotonic()
            rows = [(f, r.fed - f) for r, f in live if r.fed > f]
            if rows:
                waves.append((t0, t1, rows))
            return out
        return wrapped

    server._fill_slots = annotated(server._fill_slots, "host.admit")
    server._prefill_wave = annotated(recorded_wave(server._prefill_wave),
                                     "host.prefill_wave")
    server.step = annotated(recorded_step(server.step), "host.decode_step")
    server._pick_tokens = annotated(server._pick_tokens, "host.pick")


def drive(b: Built, plan: list[traffic.Planned], seconds: float, *,
          trace_dir: str | None = None, compile_events=None) -> Window:
    """Serve ``plan`` for ``seconds`` through the service loop."""
    from repro.launch.serve import Request

    server, mix = b.server, b.mix
    records: dict[int, Record] = {}
    closed = mix["loop"] == "closed"
    queue = collections.deque(plan)
    free_clients: list[tuple[int, float]] = []
    late = [0.0]
    steps: list = []
    waves: list = []
    if trace_dir is not None:
        _annotate(server, steps, waves)

    def send(p: traffic.Planned, due: float) -> Request:
        r = Request(p.index, p.prompt, p.max_new, queued_t=due)
        records[p.index] = Record(p.index, p.client, due, p.prompt,
                                  p.max_new, request=r)
        return r

    def on_token(req, tok) -> None:
        t = time.monotonic()
        rec = records[req.rid]
        rec.times.append(t)
        if closed and req.done:
            free_clients.append((rec.client, t))

    def feed() -> list:
        now = time.monotonic()
        hooks.poll(now)
        out = []
        if closed:
            while free_clients and queue:
                client, t = free_clients.pop(0)
                p = queue.popleft()
                p.client = client
                out.append(send(p, t))
        else:
            while queue and t_open + queue[0].due <= now:
                p = queue.popleft()
                late[0] = max(late[0], now - (t_open + p.due))
                out.append(send(p, t_open + p.due))
        return out

    n0 = len(compile_events) if compile_events is not None else 0
    server.max_wall_s = seconds
    t_open = time.monotonic()
    hooks = _Hooks(t_open + seconds - min(seconds * TRACE_SHARE,
                                          TRACE_MAX_S), trace_dir)
    if closed:
        free_clients.extend((c, t_open) for c in range(mix["clients"]))
    try:
        server.run([], on_token, feed=feed, idle_wait_s=0.0005)
    finally:
        t_close = time.monotonic()
        hooks.stop()
    compiles = (len(compile_events) - n0) if compile_events is not None else 0
    traced = ((hooks.t0, hooks.t1, hooks.anchor) if hooks.t0 is not None
              else None)
    return Window(t_open, t_close, records, late[0], traced,
                  (hooks.start_s, hooks.stop_s), compiles, steps, waves)
