"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps, on
one clock (nanoseconds), the device's program executions (``XLA Modules``),
its operations (``XLA Ops``, HLO instruction names cut to the op name) and
the host spans the benchmark annotated. ``Trace`` answers the questions
the readers ask: device busy time as the union of program intervals, idle
gaps named by the host span they fall in, self time per operation, and the
kernel time inside the programs that ran during given host intervals.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re

_OP = re.compile(r"%?([A-Za-z0-9_\-]+?)(?:\.\d+)?\s*=")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
HOST_PREFIXES = ("bench.", "host.")


def op_name(text: str) -> str:
    """``'%paged_attention_pallas.9 = bf16[..] custom-call(..)'`` ->
    ``'paged_attention_pallas'``."""
    m = _OP.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def module_name(text: str) -> str:
    """``'jit_decode_step(1104..)'`` -> ``'jit_decode_step'``."""
    return _MODULE.match(text).group(1)


def extract(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": 0, "modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            out["devices"] += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["modules"] += [[dev, module_name(e.name),
                                        e.start_ns, e.duration_ns]
                                       for e in line.events]
                elif line.name == "XLA Ops":
                    out["ops"] += [[dev, op_name(e.name), e.start_ns,
                                    e.duration_ns] for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIXES)]
    return out


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events) -> dict[str, float]:
    """Self time per name (ns) of properly nested events on one line: an
    event's duration less that of the events it encloses."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [name, end, child_time]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        e = s + d
        while stack and s >= stack[-1][1]:
            n, _, child = stack.pop()
            out[n] = out.get(n, 0.0) + child
        if stack:
            stack[-1][2] -= d
        stack.append([name, e, d])
    for n, _, child in stack:
        out[n] = out.get(n, 0.0) + child
    return out


@dataclasses.dataclass
class Trace:
    """One traced interval ``[lo, hi]`` (trace nanoseconds)."""
    events: dict
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_intervals(self, dev: int) -> list[tuple[float, float]]:
        return clip(union((s, s + d) for dv, _, s, d in self.events["modules"]
                          if dv == dev), self.lo, self.hi)

    def devices(self) -> list[int]:
        return sorted({m[0] for m in self.events["modules"]}) or [0]

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the devices."""
        devs = self.devices()
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in devs) / len(devs) / 1e9

    def idle_gaps(self, dev: int = 0) -> list[tuple[float, float]]:
        busy = self.busy_intervals(dev)
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.hi > t:
            gaps.append((t, self.hi))
        return gaps

    def idle_by_host(self, dev: int = 0) -> dict[str, float]:
        """Idle seconds by the innermost host span covering each gap's
        midpoint (``host.other`` where none does)."""
        spans = [(n, s, s + d) for n, s, d in self.events["host"]
                 if n.startswith("host.")]
        out: dict[str, float] = {}
        for s, e in self.idle_gaps(dev):
            mid = (s + e) / 2
            inside = [(se - ss, n) for n, ss, se in spans if ss <= mid < se]
            label = min(inside)[1] if inside else "host.other"
            out[label] = out.get(label, 0.0) + (e - s) / 1e9
        return out

    def top_ops(self, n: int = 10, dev: int = 0) -> list[tuple[str, float]]:
        evs = [(name, s, d) for dv, name, s, d in self.events["ops"]
               if dv == dev and self.lo <= s < self.hi]
        st = self_times(evs)
        return [(k, v / 1e9) for k, v in
                sorted(st.items(), key=lambda kv: -kv[1])[:n]]

    def programs_in(self, pattern: str, intervals) -> list[tuple]:
        """Program executions whose name matches ``pattern`` and which
        start inside one of the host ``intervals`` (trace ns)."""
        rx = re.compile(pattern)
        iv = sorted(intervals)
        starts = [a for a, _ in iv]
        out = []
        for dv, name, s, d in self.events["modules"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < iv[i][1] and rx.search(name):
                out.append((dv, s, s + d))
        return out

    def op_time_in(self, pattern: str, programs) -> float:
        """Seconds of the ops matching ``pattern`` that start inside the
        given program executions."""
        rx = re.compile(pattern)
        spans = sorted((a, b, dv) for dv, a, b in programs)
        starts = [a for a, _, _ in spans]
        tot = 0
        for dv, name, s, d in self.events["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1] and spans[i][2] == dv \
                    and rx.search(name):
                tot += d
        return tot / 1e9
