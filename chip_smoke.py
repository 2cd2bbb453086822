#!/usr/bin/env python3
"""Smoke test of packed INT4 paged serving on a TPU.

    python chip_smoke.py              # one chip, full-width llama32-1b
    python chip_smoke.py --mesh 2x2   # four chips: sharded vs one device

One chip: builds full-width llama32-1b (16 layers, d_model 2048, 32 heads,
8 KV heads, d_ff 8192, vocab 128256, bf16) with random weights from a
fixed seed, quantizes it to packed INT4 with the SplitQuantV2 split, and
serves 8 requests through ``BatchedServer(paged=True)`` on the executable
tree (grouped QKV and gate/up launches, Pallas paged decode attention).
It then checks, on the chip:

* every request got its tokens, decode compiled once, no page leaked;
* the compiled decode step holds the Pallas kernels (``tpu_custom_call``);
* logits of the packed tree after prefill and one decode step agree with
  those of ``qm.materialize()`` (the same quantized values run as dense
  XLA matmuls) within ``LOGIT_TOL``;
* the paged-attention kernel agrees with ``paged_attention_reference`` at
  the served shapes within ``PAGED_TOL``.

``--mesh DxM`` runs only the sharded-serving check: the same workload at
reduced depth on a DxM (data x model) mesh and on one device, comparing
the greedy streams (and, where they part, the logits up to that point).

Times printed on the way are smoke timings, not metrics. The script exits
nonzero, and prints no result, when JAX finds no TPU or any check fails.
The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import pathlib
import sys
import time

# Fail fast where there is no TPU, instead of falling back to the CPU.
os.environ.setdefault("JAX_PLATFORMS", "tpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "llama32-1b"
SEED = 0
BATCH = 4
PAGE = 16
PROMPT_LENS = (32, 128, 512)
N_REQUESTS = 8
GEN = 32

# Relative error bounds of the correctness checks, fixed before any chip
# run. Packed vs materialized: the kernels dequantize to fp32 and emit
# bf16, the dense reference holds the same values rounded to bf16, so the
# logits differ by bf16 rounding accumulated over the layers: 2.0e-2
# relative L2 at 16 layers of reduced width on the CPU. A wrong kernel,
# layout or cluster table gives errors of order 1.
LOGIT_TOL = {"rel_l2": 0.08, "rel_max": 0.15}
# Paged kernel vs XLA reference on bf16 inputs: both compute in fp32 and
# round the output to bf16.
PAGED_TOL = {"atol": 5e-2, "rtol": 5e-2}
# Sharded vs one-device logits up to where greedy streams part: the same
# program under another partitioning, so only reduction order differs.
MESH_TOL = {"rel_max": 0.05}


class SmokeFailure(Exception):
    """A check did not hold."""


def check(cond: bool, what: str) -> None:
    print(f"[smoke] {'PASS' if cond else 'FAIL'} {what}", flush=True)
    if not cond:
        raise SmokeFailure(what)


def requests(cfg, n: int, lens, gen: int, seed: int):
    import numpy as np

    from repro.launch.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, lens[i % len(lens)],
                                    dtype=np.int32), gen)
            for i in range(n)]


def build(cfg, seed: int):
    """Random weights -> SplitQuantV2 packed INT4 -> grouped executable."""
    import jax

    from repro.core import QuantPolicy, restructure
    from repro.models import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    qm = restructure(params, QuantPolicy(bits=4, packed=True))
    ex = qm.as_executable(group=True)
    jax.block_until_ready(ex)
    print(f"[smoke] timing: quantize to packed INT4 "
          f"{time.perf_counter() - t0:.1f}s (smoke timing, not a metric)",
          flush=True)
    return model, qm, ex


def serve_phase(cfg, model, ex) -> None:
    """Serve the request mix through the paged server and check it."""
    import jax
    import numpy as np

    from repro.launch.serve import BatchedServer

    max_len = max(PROMPT_LENS) + GEN + 8
    server = BatchedServer(model, ex, batch_slots=BATCH, max_len=max_len,
                           paged=True, page_size=PAGE)
    reqs = requests(cfg, N_REQUESTS, PROMPT_LENS, GEN, seed=1)
    t0 = time.perf_counter()
    stats = server.run(reqs)
    wall = time.perf_counter() - t0
    served = sum(len(r.out) for r in reqs)
    print(f"[smoke] timing: served {served} tokens for {len(reqs)} requests "
          f"in {wall:.1f}s, compiles included (smoke timing, not a metric)",
          flush=True)
    check(all(r.done and len(r.out) == GEN for r in reqs),
          f"all {len(reqs)} requests served {GEN} tokens")
    check(stats["decode_compiles"] == 1,
          f"decode compiled once (got {stats['decode_compiles']})")
    check(stats["pages"]["leaked"] == 0,
          f"no page leaked (got {stats['pages']['leaked']})")

    tokens = server._put(np.zeros((BATCH, 1), np.int32))
    active = server._put(np.ones((BATCH,), bool))
    hlo = server._decode.lower(server.params, tokens, server.cache,
                               active=active).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    print(f"[smoke] decode step: {len(calls)} tpu_custom_call ops", flush=True)
    check(any("splitq_packed" in ln for ln in calls),
          "decode HLO runs the packed matmul kernel (tpu_custom_call)")
    check(any("paged_attention" in ln for ln in calls),
          "decode HLO runs the paged attention kernel (tpu_custom_call)")
    del server
    jax.clear_caches()


def _rel_errors(got, want) -> dict:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = float(np.abs(want).max()) or 1.0
    return {
        "rel_l2": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
        "rel_max": float(np.abs(got - want).max() / scale),
    }


def logits_phase(cfg, model, qm, ex) -> None:
    """Packed executable vs ``qm.materialize()``: prefill + one decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lens = np.array([PROMPT_LENS[0], PROMPT_LENS[1]], np.int32)
    width = int(lens.max())
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, (len(lens), width),
                          dtype=np.int32)
    n_row = math.ceil((width + 8) / PAGE)

    def run(params):
        cache = model.init_paged_cache(len(lens), width + 8, page_size=PAGE,
                                       num_pages=len(lens) * n_row)
        cache["page_table"] = jnp.arange(
            len(lens) * n_row, dtype=jnp.int32).reshape(len(lens), n_row)
        logits0, cache = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(tokens),
                     "lengths": jnp.asarray(lens)}, cache)
        nxt = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)[:, None]
        logits1, _ = jax.jit(model.decode_step)(params, nxt, cache)
        return np.asarray(logits0), np.asarray(logits1), np.asarray(nxt)

    t0 = time.perf_counter()
    p0, p1, p_next = run(ex)
    ref = qm.materialize()
    r0, r1, _ = run(ref)
    del ref
    print(f"[smoke] timing: logit comparison {time.perf_counter() - t0:.1f}s "
          f"(smoke timing, not a metric)", flush=True)
    for name, got, want in (("prefill", p0, r0), ("decode", p1, r1)):
        err = _rel_errors(got, want)
        check(np.isfinite(got).all() and got.shape == want.shape,
              f"{name} logits finite, shape {got.shape}")
        check(all(err[k] <= LOGIT_TOL[k] for k in LOGIT_TOL),
              f"{name} logits packed vs materialized: rel_l2 "
              f"{err['rel_l2']:.2e}, rel_max {err['rel_max']:.2e} "
              f"(tolerance {LOGIT_TOL})")
    print(f"[smoke] greedy next tokens (packed): {p_next[:, 0].tolist()}",
          flush=True)


def paged_phase(cfg) -> None:
    """Paged kernel vs the XLA reference at the served shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_attention
    from repro.kernels.paged_attention import paged_attention_reference

    kv, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    n_row = math.ceil((max(PROMPT_LENS) + GEN + 8) / PAGE)
    pool = BATCH * n_row
    rng = np.random.default_rng(3)
    dt = jnp.dtype(cfg.dtype)
    q = jnp.asarray(rng.normal(size=(BATCH, kv, g, hd)), dt)
    kp = jnp.asarray(rng.normal(size=(pool, PAGE, kv, hd)), dt)
    vp = jnp.asarray(rng.normal(size=(pool, PAGE, kv, hd)), dt)
    table = jnp.asarray(rng.permutation(pool).reshape(BATCH, n_row), jnp.int32)
    lengths = jnp.asarray([0, 37, 300, n_row * PAGE][:BATCH], jnp.int32)
    got = jax.jit(paged_attention)(q, kp, vp, table, lengths)
    want = jax.jit(paged_attention_reference)(q, kp, vp, table, lengths)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    ok = bool(np.allclose(got, want, **PAGED_TOL)) and not got[0].any()
    check(ok, f"paged kernel vs reference at B={BATCH} KV={kv} G={g} "
              f"hd={hd} page={PAGE}: max abs diff {err:.2e} "
              f"(tolerance {PAGED_TOL}), empty row exact zeros")


def mesh_phase(cfg, model, ex, mesh_shape: tuple[int, int],
               lens=PROMPT_LENS, gen: int = 16) -> None:
    """Greedy streams of the same workload on a mesh and on one device."""
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.launch.serve import BatchedServer

    mesh = make_mesh(mesh_shape, ("data", "model"))
    max_len = max(lens) + gen + 8
    runs = {}
    for name, m in (("one device", None), (f"{mesh_shape} mesh", mesh)):
        server = BatchedServer(model, ex, batch_slots=BATCH, max_len=max_len,
                               paged=True, page_size=PAGE, mesh=m,
                               capture_logits=True)
        if m is not None:
            import jax

            leaves = jax.tree.leaves((server.params, server.cache))
            on = {len(a.sharding.device_set) for a in leaves}
            check(on == {m.size}, f"{name}: weights and cache span all "
                                  f"{m.size} devices (device sets {on})")
        reqs = requests(cfg, N_REQUESTS, lens, gen, seed=1)
        t0 = time.perf_counter()
        stats = server.run(reqs)
        print(f"[smoke] timing: {name} served "
              f"{sum(len(r.out) for r in reqs)} tokens in "
              f"{time.perf_counter() - t0:.1f}s (smoke timing, not a metric)",
              flush=True)
        check(all(r.done and len(r.out) == gen for r in reqs),
              f"{name}: all requests served")
        check(stats["pages"]["leaked"] == 0, f"{name}: no page leaked")
        runs[name] = reqs
    one, sharded = runs.values()
    diverged = [(a.rid, next(i for i, (x, y) in enumerate(zip(a.out, b.out))
                             if x != y))
                for a, b in zip(one, sharded) if a.out != b.out]
    if not diverged:
        check(True, f"greedy streams bit-identical, {mesh_shape} mesh vs "
                    f"one device ({len(one)} requests x {gen} tokens)")
        return
    print(f"[smoke] streams part (request, first token index): {diverged}",
          flush=True)
    worst = 0.0
    for a, b in zip(one, sharded):
        upto = next((i for i, (x, y) in enumerate(zip(a.out, b.out))
                     if x != y), len(a.out) - 1)
        for i in range(upto + 1):
            worst = max(worst, _rel_errors(b.logits[i], a.logits[i])["rel_max"])
    check(worst <= MESH_TOL["rel_max"],
          f"logits up to the first parting agree: rel_max {worst:.2e} "
          f"(tolerance {MESH_TOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default="",
                    help="DxM: run only the sharded-serving check on D*M "
                         "chips (data x model)")
    args = ap.parse_args(argv)

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no TPU backend on this machine
        print(f"[smoke] no TPU: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX runs on {dev.platform}", file=sys.stderr)
        return 1
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[smoke] the repository's sources are missing: {e}",
              file=sys.stderr)
        return 1
    print(f"[smoke] device: {dev.device_kind} x{len(devices)}, "
          f"compile cache {enable_compile_cache()}", flush=True)

    cfg = get_config(ARCH)
    t_start = time.perf_counter()
    try:
        if args.mesh:
            d, m = (int(v) for v in args.mesh.lower().split("x"))
            if d * m > len(devices):
                raise SmokeFailure(f"--mesh {args.mesh} needs {d * m} chips, "
                                   f"have {len(devices)}")
            cfg = dataclasses.replace(cfg, n_layers=2)
            model, _, ex = build(cfg, SEED)
            mesh_phase(cfg, model, ex, (d, m))
        else:
            model, qm, ex = build(cfg, SEED)
            serve_phase(cfg, model, ex)
            logits_phase(cfg, model, qm, ex)
            paged_phase(cfg)
    except SmokeFailure as e:
        print(f"[smoke] failed: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] timing: total {time.perf_counter() - t_start:.1f}s "
          f"(smoke timing, not a metric)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
