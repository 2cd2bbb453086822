"""Per-tick scheduler timeline: the structured event log of a server run.

Replaces the server's old bare ``events: list[str]`` as the source of
truth for what the scheduler did and when. Every record is a plain dict —
monotonically sequenced (``seq``), stamped with the decode-tick clock
(``tick``, the same clock the fault injector fires on) and a monotonic
timestamp — carrying the wave type plus whatever scheduler state the
emitter sampled (active slots, pool free pages / fragmentation, spec
draft width, degraded flag, faults fired this tick). ``to_jsonl`` dumps
the buffer one JSON object per line (``--trace-out``).

The buffer is a RING: long serving runs must not grow host memory without
bound (the old string list did), so the ``cap`` newest records are kept
and ``dropped`` counts what fell off the front — exported as a metric and
asserted zero in the CI smokes, where the default cap is generous enough
that any drop means an event-volume bug.

Backward compatibility: :meth:`legacy_events` renders the records back
into the exact strings the old list held (``"prefill"``, ``"decode"``,
``"verify"``, ``"draft_prefill"``, ``"drain"``, ``"preempt:<rid>"``,
``"replay:<rid>"``) and ``BatchedServer.events`` is now a property over
it — existing tests and callers read the same strings from the new
source of truth.
"""
from __future__ import annotations

import json
import time
from collections import deque

DEFAULT_CAP = 100_000

# record kinds that existed in the old ``events`` string list, and how
# they rendered there; anything else is timeline-only detail. The spill
# tier's "spill"/"restore" render in the same "<kind>:<rid>" shape so
# ``server.events`` keeps telling the whole preemption story.
_LEGACY_PLAIN = ("prefill", "decode", "verify", "draft_prefill", "drain")
_LEGACY_RID = ("preempt", "replay", "spill", "restore")


class Timeline:
    """Ring-buffered structured event log for one server run."""

    def __init__(self, cap: int = DEFAULT_CAP):
        if cap < 0:
            raise ValueError(f"cap must be >= 0 (0 = unbounded), got {cap}")
        self.cap = cap
        self._buf: deque[dict] = deque(maxlen=cap or None)
        self.seq = 0          # records ever emitted (monotone)
        self.dropped = 0      # records that fell off the ring
        self.tick = -1        # decode-tick clock, set by the scheduler

    def set_tick(self, tick: int) -> None:
        self.tick = tick

    def emit(self, kind: str, **fields) -> dict:
        """Append one record and return it: a caller may add fields it
        learns later (a round's spans, once the round is over)."""
        rec = {"seq": self.seq, "tick": self.tick,
               "t": time.monotonic(), "kind": kind}
        rec.update(fields)
        if self.cap and len(self._buf) == self.cap:
            self.dropped += 1  # deque drops the oldest on append
        self._buf.append(rec)
        self.seq += 1
        return rec

    # -- reads ---------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._buf)

    def records(self, kind: str | None = None) -> list[dict]:
        if kind is None:
            return list(self._buf)
        return [r for r in self._buf if r["kind"] == kind]

    def tail(self, n: int = 8) -> list[dict]:
        """The newest ``n`` records (stall diagnostics)."""
        return list(self._buf)[-n:]

    def legacy_events(self) -> list[str]:
        """The old ``server.events`` strings, rendered from the records."""
        out = []
        for r in self._buf:
            k = r["kind"]
            if k in _LEGACY_PLAIN:
                out.append(k)
            elif k in _LEGACY_RID:
                out.append(f"{k}:{r['rid']}")
        return out

    def to_jsonl(self, path) -> int:
        """Write one JSON object per line; returns records written. A
        ``meta`` head line carries the drop accounting so a consumer can
        tell a complete log from a ring that wrapped."""
        recs = self.records()
        with open(path, "w") as f:
            f.write(json.dumps({
                "kind": "meta", "events": self.seq,
                "dropped": self.dropped, "cap": self.cap,
            }) + "\n")
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return len(recs)


def read_jsonl(path) -> tuple[dict, list[dict]]:
    """Load a ``to_jsonl`` dump back into (meta, records)."""
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    if not lines or lines[0].get("kind") != "meta":
        raise ValueError(f"{path}: missing timeline meta head line")
    return lines[0], lines[1:]
