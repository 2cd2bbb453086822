"""Shared serving-test helpers.

One home for the fixtures the serving suites kept re-growing locally:
tiny reduced models, deterministic request workloads, one-shot
``BatchedServer`` runs, and the hermetic subprocess environment the
mesh/CLI smokes launch under. Imported by ``test_resilience.py``,
``test_sharded_serving.py``, ``test_service.py`` and ``test_spill.py`` —
change a knob here and every suite sees the same workload.
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.serve import BatchedServer, Request
from repro.models import build_model


def tiny_model(arch="llama32-1b", n_layers=2, seed=0):
    """A reduced config shrunk to ``n_layers`` with seeded fp weights —
    small enough that CPU suites stay in seconds."""
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def make_requests(cfg, lens, gens, seed0=100, priorities=None):
    """Deterministic per-request prompts: request ``i`` draws its tokens
    from ``default_rng(seed0 + i)``, so workloads rebuild identically."""
    if isinstance(gens, int):
        gens = [gens] * len(lens)
    return [
        Request(i, np.random.default_rng(seed0 + i).integers(
            0, cfg.vocab_size, ln, dtype=np.int32), g,
            priority=(priorities[i] if priorities else 0))
        for i, (ln, g) in enumerate(zip(lens, gens))
    ]


def serve_once(model, params, reqs, **kw):
    """Run one fresh ``BatchedServer`` over ``reqs``; returns
    ``({rid: out}, stats)`` with the legacy event strings attached as
    ``stats["_events"]``."""
    server = BatchedServer(model, params, **kw)
    stats = server.run(reqs)
    stats["_events"] = server.events
    return {r.rid: r.out for r in reqs}, stats


REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def subprocess_env(devices=8):
    """The hermetic environment the subprocess smokes run under: repo
    sources on the path, fake host devices for mesh runs, the CPU backend,
    and from the caller only where to find programs and where to write."""
    env = {
        "PYTHONPATH": str(REPO_ROOT / "src"),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
    }
    for key in ("PATH", "HOME", "TMPDIR"):
        if key in os.environ:
            env[key] = os.environ[key]
    return env


def run_python(code, timeout=600, devices=8):
    """``python -c code`` in the hermetic env (inline mesh smokes)."""
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=REPO_ROOT, env=subprocess_env(devices),
    )


def run_module(module, args, timeout=600, devices=8):
    """``python -m module *args`` in the hermetic env (CLI smokes)."""
    return subprocess.run(
        [sys.executable, "-m", module, *args], capture_output=True,
        text=True, timeout=timeout, cwd=REPO_ROOT,
        env=subprocess_env(devices),
    )
