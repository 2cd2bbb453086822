"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample of the requests the server finished
is drawn from the seed, the longest among them, until it holds
``sample_tokens`` served tokens. The configuration's reference runs once
over each prompt with its served tokens, and the number compared is the
widest gap by which a served token's logit lies below the reference's best
logit at its position (greedy serving: 0 for an exact program).
"""
from __future__ import annotations

import importlib.util
import sys

import numpy as np

from harness.config import BENCH


def load_reference(name: str):
    mod_name = f"bench_reference_{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(
        mod_name, BENCH / "reference" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def finished(records: dict) -> list:
    return [r for r in records.values()
            if r.request is not None and r.request.status == "ok"
            and r.request.done and len(r.request.out) == r.max_new]


def sample(records: dict, seed: int, tokens: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample holds ``tokens`` served tokens."""
    done = sorted(finished(records), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.max_new, r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 0xC0EC]).permutation(len(rest))
    out, n = [longest], longest.max_new
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += rest[i].max_new
    return out


def sequences(picked: list) -> tuple[list, list]:
    """Each request's prompt and served tokens but the last, and the served
    token due after each position (-1 inside the prompt)."""
    seqs, served = [], []
    for r in picked:
        out = np.asarray(r.request.out, np.int32)
        seqs.append(np.concatenate([np.asarray(r.prompt, np.int32),
                                    out[:-1]]))
        s = np.full(len(seqs[-1]), -1, np.int32)
        s[len(r.prompt) - 1:] = out
        served.append(s)
    return seqs, served


def verdict(widest_gap: float | None, tokens: int, limit: float) -> bool:
    """The predicate that decides ``correct``: some served tokens were
    compared, and none lies further below the reference's best than
    ``limit``. The control's reading goes through it too."""
    return tokens > 0 and widest_gap is not None and widest_gap <= limit


def compare(ref, ref_cfg, key, picked: list, *, control: bool = False,
            pad_to: int = 0) -> dict:
    """The numbers compared, read from the f32 reference; with
    ``control``, also the control's reading at the same positions (the
    widest gap of the tokens the fp8 reference puts first)."""
    if not picked:
        return {"widest_gap": None, "tokens": 0, "requests": 0}
    seqs, served = sequences(picked)
    precs = ("f32", "fp8") if control else ("f32",)
    res = ref.gaps(ref_cfg, key, seqs, served, precs, pad_to=pad_to)
    out = {"widest_gap": max(float(np.max(g)) for g, _ in res["f32"]),
           "tokens": int(sum(int((s >= 0).sum()) for s in served)),
           "requests": len(picked)}
    if control:
        out["control_widest_gap"] = max(float(np.max(g))
                                        for g, _ in res["fp8"])
    return out
