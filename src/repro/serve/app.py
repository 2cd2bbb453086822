"""Asyncio HTTP/SSE service front-end over ``BatchedServer``.

The engine keeps its synchronous scheduler loop — proven bit-exact under
chaos/mesh/spec — and runs it unchanged in a worker thread via the
``run(feed=...)`` service hook. This module is the thin asynchronous
shell around it:

* ``POST /v1/generate`` — JSON body ``{"prompt": [token ids],
  "max_new": N, "tenant": "...", "weight": W, "priority": P}``; the
  response streams Server-Sent Events, one ``data: {"rid", "index",
  "token"}`` frame per decoded token (fired from the engine's existing
  ``on_token`` callback) and a final ``data: {"done": true, "status",
  "tokens"}`` frame. Greedy streams are BIT-IDENTICAL to a library
  ``BatchedServer.run`` on the same workload: the service changes how
  tokens travel, never which tokens exist.
* ``GET /metrics`` — the live ``Registry.to_prometheus()`` snapshot.
* ``GET /healthz`` — liveness + drain state.
* ``POST /drain`` — trips the PR 6 ``PreemptionGuard`` flag, the same
  path SIGTERM takes: in-flight requests retire with partial streams and
  zero leaks, queued requests return unserved, open SSE streams get a
  terminal ``status: "preempted"`` frame.

Admission is per-tenant weighted-fair: submissions land in
``FairScheduler`` queues and the scheduler thread drains one deficit
round-robin round per scheduler iteration. Tokens cross threads via
``loop.call_soon_threadsafe`` into per-request asyncio queues — the
engine never blocks on a slow client.

The HTTP layer is hand-rolled over ``asyncio.start_server`` (one request
per connection, ``Connection: close``) so the service carries zero
dependencies beyond the standard library.
"""
from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import signal
import threading

import numpy as np

from repro.runtime.fault import PreemptionGuard
from repro.serve.tenants import FairScheduler

_JSON = {"Content-Type": "application/json"}
_SSE_HEAD = (b"HTTP/1.1 200 OK\r\n"
             b"Content-Type: text/event-stream\r\n"
             b"Cache-Control: no-cache\r\n"
             b"Connection: close\r\n\r\n")


def _response(code: int, body: bytes, headers: dict | None = None) -> bytes:
    reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
              503: "Service Unavailable"}.get(code, "OK")
    head = [f"HTTP/1.1 {code} {reason}"]
    for k, v in {"Content-Length": len(body), "Connection": "close",
                 **(headers or {})}.items():
        head.append(f"{k}: {v}")
    return ("\r\n".join(head) + "\r\n\r\n").encode() + body


class ServeApp:
    """One engine, one listener: the serving *process*.

    ``start()`` binds the socket (``port=0`` -> ephemeral, read back from
    ``self.port``) and launches the engine's service loop in a worker
    thread; ``stop()`` drains it through the guard and joins. The engine's
    end-of-run stats dict lands in ``self.stats``.
    """

    def __init__(self, server, *, fair: FairScheduler | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 max_new_cap: int = 4096):
        self.server = server
        self.fair = fair if fair is not None else FairScheduler()
        if server.guard is None:
            # the guard doubles as the drain flag even when no signal
            # handler is installed (POST /drain just sets .requested)
            server.guard = PreemptionGuard()
        self.guard = server.guard
        self.host = host
        self.port = port
        self.max_new_cap = max_new_cap
        self.stats: dict | None = None
        self.error: BaseException | None = None
        self._streams: dict[int, asyncio.Queue] = {}
        self._ended: set[int] = set()
        self._auto_rid = itertools.count(1 << 20)  # clear of client rids
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._srv: asyncio.base_events.Server | None = None

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "ServeApp":
        self._loop = asyncio.get_running_loop()
        self._srv = await asyncio.start_server(self._handle, self.host,
                                               self.port)
        self.port = self._srv.sockets[0].getsockname()[1]
        self._thread = threading.Thread(target=self._engine_loop,
                                        name="engine-loop", daemon=True)
        self._thread.start()
        return self

    async def stop(self) -> dict | None:
        """Drain the engine (same flag SIGTERM sets), join its thread,
        close the listener. Returns the engine's stats dict."""
        self.guard.requested = True
        if self._thread is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self._thread.join)
        if self._srv is not None:
            self._srv.close()
            await self._srv.wait_closed()
        if self.error is not None:
            raise self.error
        return self.stats

    def _engine_loop(self) -> None:
        try:
            self.stats = self.server.run([], on_token=self._on_token,
                                         feed=self.fair.drain)
        except BaseException as e:  # surface engine crashes to stop()
            self.error = e
        finally:
            if self._loop is not None and not self._loop.is_closed():
                self._loop.call_soon_threadsafe(self._finish_all)

    # -- engine thread -> event loop -----------------------------------------

    def _on_token(self, req, tok: int) -> None:
        # engine thread: hop to the loop; req fields are read HERE so the
        # loop-side closure carries plain values
        self._loop.call_soon_threadsafe(self._push, req.rid, int(tok),
                                        req.done, req.status)

    def _push(self, rid: int, tok: int, done: bool, status: str) -> None:
        q = self._streams.get(rid)
        if q is None:
            return
        q.put_nowait(("tok", tok))
        if done:
            q.put_nowait(("end", status))
            self._ended.add(rid)

    def _finish_all(self) -> None:
        """Engine loop exited (drain or crash): close every stream that
        never saw a terminal frame — drained partials and unserved
        requests end with status 'preempted'."""
        for rid, q in list(self._streams.items()):
            if rid not in self._ended:
                q.put_nowait(("end", "preempted"))
                self._ended.add(rid)

    # -- HTTP ----------------------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            line = await reader.readline()
            if not line:
                return
            try:
                method, path, _ = line.decode("latin1").split(None, 2)
            except ValueError:
                writer.write(_response(400, b"malformed request line\n"))
                return
            headers = {}
            while True:
                h = await reader.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
                k, _, v = h.decode("latin1").partition(":")
                headers[k.strip().lower()] = v.strip()
            body = b""
            n = int(headers.get("content-length", "0") or 0)
            if n:
                body = await reader.readexactly(n)
            route = (method.upper(), path.split("?", 1)[0])
            if route == ("POST", "/v1/generate"):
                await self._generate(writer, body)
            elif route == ("GET", "/metrics"):
                text = self.server.registry.to_prometheus()
                writer.write(_response(200, text.encode(), {
                    "Content-Type": "text/plain; version=0.0.4"}))
            elif route == ("GET", "/healthz"):
                payload = {
                    "status": "draining" if self.guard.requested else "ok",
                    "active": sum(1 for r in self.server.active
                                  if r is not None),
                    "backlog": self.fair.backlog,
                }
                writer.write(_response(200,
                                       json.dumps(payload).encode(), _JSON))
            elif route == ("POST", "/drain"):
                self.guard.requested = True
                writer.write(_response(200, b'{"draining": true}', _JSON))
            else:
                writer.write(_response(404, b"not found\n"))
            await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    async def _generate(self, writer: asyncio.StreamWriter,
                        body: bytes) -> None:
        from repro.launch.serve import Request  # deferred: heavy import
        try:
            spec = json.loads(body or b"{}")
            prompt = np.asarray(spec["prompt"], np.int32)
            max_new = int(spec.get("max_new", 16))
            if prompt.ndim != 1 or prompt.size == 0:
                raise ValueError("prompt must be a non-empty 1-D token list")
            if not 1 <= max_new <= self.max_new_cap:
                raise ValueError(f"max_new must be in [1, {self.max_new_cap}]")
        except (KeyError, ValueError, TypeError) as e:
            writer.write(_response(400, f"bad request: {e}\n".encode()))
            return
        if self.guard.requested:
            writer.write(_response(503, b"draining\n"))
            return
        rid = int(spec["rid"]) if "rid" in spec else next(self._auto_rid)
        req = Request(rid, prompt, max_new,
                      priority=int(spec.get("priority", 0)))
        q: asyncio.Queue = asyncio.Queue()
        self._streams[rid] = q
        # queue registered BEFORE submit: the engine thread may emit the
        # first token before this coroutine runs again
        self.fair.submit(str(spec.get("tenant", "default")), req,
                         weight=float(spec.get("weight", 1.0)))
        writer.write(_SSE_HEAD)
        await writer.drain()
        emitted = 0
        try:
            while True:
                kind, val = await q.get()
                if kind == "tok":
                    frame = {"rid": rid, "index": emitted, "token": val}
                    emitted += 1
                else:
                    frame = {"rid": rid, "done": True, "status": val,
                             "tokens": emitted}
                writer.write(b"data: " + json.dumps(frame).encode() + b"\n\n")
                await writer.drain()
                if kind == "end":
                    break
        finally:
            self._streams.pop(rid, None)
            self._ended.discard(rid)


# -- client helpers (tests + selfcheck share them) ---------------------------

async def http_request(host: str, port: int, method: str, path: str,
                       body: bytes = b"") -> tuple[int, bytes]:
    """Minimal one-shot HTTP client; returns (status, body)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        code = int(status.split()[1])
        n = None
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin1").partition(":")
            if k.strip().lower() == "content-length":
                n = int(v)
        data = (await reader.readexactly(n) if n is not None
                else await reader.read())
        return code, data
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except Exception:
            pass


async def sse_generate(host: str, port: int, payload: dict,
                       on_token=None) -> dict:
    """Submit one generation and consume its SSE stream. Returns
    ``{"code", "tokens", "done"}`` (``done`` is the terminal frame,
    None if the stream was cut)."""
    body = json.dumps(payload).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        code = int(status.split()[1])
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
        tokens: list[int] = []
        done = None
        if code == 200:
            while True:
                line = await reader.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                evt = json.loads(line[6:])
                if evt.get("done"):
                    done = evt
                    break
                tokens.append(evt["token"])
                if on_token is not None:
                    on_token(evt)
        return {"code": code, "tokens": tokens, "done": done}
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except Exception:
            pass


# -- CLI ---------------------------------------------------------------------

def _service_parser() -> argparse.ArgumentParser:
    from repro.launch.serve import build_parser
    ap = build_parser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port (0 = ephemeral, printed at startup)")
    ap.add_argument("--quantum", type=float, default=64.0,
                    help="deficit round-robin quantum (cost units = "
                         "prompt + generation tokens per request)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="CI smoke: start the service in-process, run a "
                         "mixed-tenant SSE workload against it, and exit "
                         "nonzero unless every greedy stream is "
                         "bit-identical to the library BatchedServer.run "
                         "reference with zero timeline drops, zero page "
                         "leaks and zero orphaned spill files")
    return ap


def _make_service(args, *, guard=None):
    """(engine, app) for a parsed service CLI namespace."""
    from repro.launch import serve as launch

    (cfg, model, params, draft_params, w_bytes, mesh,
     probe_params) = launch.build_engine(args)
    plens = ([int(x) for x in args.prompt_lens.split(",")]
             if args.prompt_lens else [args.prompt_len])
    max_len = args.shared_prefix + max(plens) + args.gen + 8
    slo = None
    if args.slo_ttft_ms > 0 or args.slo_tpot_ms > 0:
        from repro.serve import SLOController
        slo = SLOController(
            ttft_ms=args.slo_ttft_ms, tpot_ms=args.slo_tpot_ms,
            chunk=args.prefill_chunk or max_len,
            chunk_min=args.slo_chunk_min, chunk_max=max_len,
            spec_floor=args.spec_floor,
        )
    spill = None
    if args.spill_dir:
        from repro.serve import SpillStore
        spill = SpillStore(args.spill_dir)
    obs = (launch.Observability(
        trace_cap=args.trace_cap,
        const_labels={"family": cfg.family,
                      "engine": args.engine if args.bits else "fp"})
        if args.obs else launch.Observability.disabled(
            trace_cap=args.trace_cap))
    server = launch.BatchedServer(
        model, params, args.batch, max_len,
        paged=args.paged, page_size=args.page_size,
        num_pages=args.num_pages or None,
        prefix_cache=args.prefix_cache,
        prefix_state_budget=args.prefix_state_budget,
        prefill_chunk=args.prefill_chunk,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.seed, speculate=args.speculate, draft_params=draft_params,
        page_growth=args.page_growth, growth_headroom=args.growth_headroom,
        preemption=args.preemption, spec_floor=args.spec_floor,
        spec_window=args.spec_window, inject=args.inject or None,
        guard=guard, max_wall_s=args.max_wall_s,
        spill_store=spill, spill_threshold=args.spill_threshold,
        slo=slo, mesh=mesh, obs=obs, trace_cap=args.trace_cap,
        quality_probe=args.quality_probe, probe_params=probe_params,
    )
    app = ServeApp(server, fair=FairScheduler(quantum=args.quantum),
                   host=args.host, port=args.port)
    return server, app


def _selfcheck_workload(args, cfg):
    """The deterministic mixed-tenant workload the selfcheck runs: the
    same request shapes the library CLI generates, spread over two
    tenants with unequal weights."""
    plens = ([int(x) for x in args.prompt_lens.split(",")]
             if args.prompt_lens else [args.prompt_len])
    rng = np.random.default_rng(args.seed)
    common = rng.integers(0, cfg.vocab_size, args.shared_prefix,
                          dtype=np.int32)
    reqs = []
    for i in range(args.requests):
        prompt = np.concatenate([
            common,
            rng.integers(0, cfg.vocab_size, plens[i % len(plens)],
                         dtype=np.int32),
        ])
        tenant, weight = (("heavy", 1.0) if i % 3 else ("light", 3.0))
        reqs.append({"rid": i, "prompt": prompt, "max_new": args.gen,
                     "tenant": tenant, "weight": weight})
    return reqs


async def _run_selfcheck(args) -> int:
    from repro.launch import serve as launch

    # 1) library reference: plain BatchedServer.run — telemetry off, no
    #    faults, no spill tier, no SLO retuning. The service below runs
    #    with every flagged hazard live and must reproduce these streams
    #    bit-exactly anyway.
    ref_args = argparse.Namespace(**vars(args))
    ref_args.inject = ""
    ref_args.spill_dir = ""
    ref_args.slo_ttft_ms = ref_args.slo_tpot_ms = 0.0
    ref_args.obs = False
    ref_server, _ = _make_service(ref_args)
    workload = _selfcheck_workload(args, ref_server.model.cfg)
    ref_reqs = [launch.Request(w["rid"], w["prompt"], w["max_new"])
                for w in workload]
    ref_stats = ref_server.run(ref_reqs)
    ref = {r.rid: list(r.out) for r in ref_reqs}
    print(f"[service] reference: {ref_stats['requests']} requests, "
          f"{ref_stats['tokens']} tokens")

    # 2) the service, with every flagged hazard live (faults, SLO
    #    retuning, spill tier), serving the same workload over HTTP/SSE
    server, app = _make_service(args)
    await app.start()
    print(f"[service] listening on {app.host}:{app.port}")
    results = await asyncio.gather(*[
        sse_generate(app.host, app.port, {
            "rid": w["rid"], "prompt": w["prompt"].tolist(),
            "max_new": w["max_new"], "tenant": w["tenant"],
            "weight": w["weight"],
        }) for w in workload
    ])
    code, health = await http_request(app.host, app.port, "GET", "/healthz")
    assert code == 200, health
    code, metrics = await http_request(app.host, app.port, "GET", "/metrics")
    code, _ = await http_request(app.host, app.port, "POST", "/drain")
    stats = await app.stop()

    failures = []
    got = {w["rid"]: r["tokens"] for w, r in zip(workload, results)}
    if got != ref:
        bad = sorted(rid for rid in ref if got.get(rid) != ref[rid])
        failures.append(f"SSE streams diverge from library run: rids {bad}")
    if any(r["done"] is None or r["done"]["status"] != "ok"
           for r in results):
        failures.append("a stream ended without a clean terminal frame")
    if server.timeline.dropped:
        failures.append(f"{server.timeline.dropped} timeline records "
                        f"dropped")
    if args.paged and stats["pages"]["leaked"]:
        failures.append(f"{stats['pages']['leaked']} KV pages leaked")
    if args.inject and "oop" in args.inject:
        if not stats["resilience"]["preemptions"]:
            failures.append("oop injection fired no preemption")
    if args.spill_dir:
        orphans = stats["resilience"]["spill_store"]["orphans"]
        if orphans:
            failures.append(f"{orphans} orphaned spill file(s)")
    if args.slo_ttft_ms or args.slo_tpot_ms:
        print(f"[service] slo: {stats['slo']['adjustments']} adjustment(s),"
              f" final chunk={stats['slo']['chunk']}")
    if args.obs:
        from repro.obs import parse_prometheus
        snap = parse_prometheus(metrics.decode())
        if "serve_tokens_total" not in snap:
            failures.append("/metrics snapshot missing serve_tokens_total")
    print(f"[service] fair shares: "
          f"{json.dumps(app.fair.stats()['tenants'], default=str)}")
    for f in failures:
        print(f"[service] FAIL: {f}")
    if not failures:
        print(f"[service] selfcheck OK: {len(workload)} streams "
              f"bit-identical through SSE, "
              f"{stats['resilience']['preemptions']} preemption(s), "
              f"{stats['resilience']['spills']} spill(s)")
    return 1 if failures else 0


async def _run_service(args) -> int:
    server, app = _make_service(args, guard=PreemptionGuard())
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig,
                                lambda: setattr(app.guard, "requested", True))
    await app.start()
    print(f"[service] listening on http://{app.host}:{app.port} "
          f"(POST /v1/generate, GET /metrics, GET /healthz, POST /drain)")
    while not app.guard.requested:
        await asyncio.sleep(0.05)
    stats = await app.stop()
    print(f"[service] drained: {stats['requests']} requests retired")
    return 0


def main(argv=None) -> int:
    from repro.launch.compile_cache import enable_compile_cache

    args = _service_parser().parse_args(argv)
    enable_compile_cache()
    if args.selfcheck:
        return asyncio.run(_run_selfcheck(args))
    return asyncio.run(_run_service(args))


if __name__ == "__main__":
    raise SystemExit(main())
