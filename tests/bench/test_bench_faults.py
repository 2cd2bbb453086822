"""The benchmark's check fails a broken timed path.

The whole run of a tiny cell (set-up, warm-up, window, sample, reference)
on the CPU, with the served path broken underneath after warm-up. Each
fault a serving cell can have must turn ``correct`` false. (The exchange
between chips does not exist in a one-chip cell.)
"""
import time

import jax.numpy as jnp
import pytest

import tiny
from harness import cell

SEED = 2**31 + 77


def _run(fault=None, control=False):
    return cell.run("tiny.chat", SEED, 2.0, False,
                    t_start=time.perf_counter(), require_tpu=False,
                    override=tiny.override(), fault=fault, control=control)


def state_unchanged(b):
    """The decode step returns the cache it was given."""
    dec = b.server._decode

    def step(params, tokens, cache, active=None):
        logits, _ = dec(params, tokens, cache, active=active)
        return logits, cache

    b.server._decode = step


def half_batch(b):
    """The decode step leaves out half of the rows (the odd slots) and
    answers each with its even neighbour's logits."""
    dec = b.server._decode

    def step(params, tokens, cache, active=None):
        logits, new = dec(params, tokens, cache, active=active)
        return jnp.repeat(logits[0::2], 2, axis=0), new

    b.server._decode = step


def token_altered(b):
    """Every third served token is changed where it is produced."""
    emit = b.server._emit
    vocab = b.sizes["vocab_size"]

    def altered(req, tok):
        if len(req.out) % 3 == 2:
            tok = (tok + 1) % vocab
        return emit(req, tok)

    b.server._emit = altered


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(fault):
    res = _run(fault)
    gap = res["checked"]["widest_gap"]
    assert res["checked"]["served_tokens_compared"]["value"] > 0
    assert gap["value"] > gap["limit"]
    assert res["correct"] is False
