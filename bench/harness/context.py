"""What a metric reader (``bench/metrics/<name>.py``) is handed.

A reader defines ``read(ctx) -> float | None`` and returns ``None`` where
it finds nothing to read; the harness then leaves the metric out.
"""
from __future__ import annotations

import dataclasses

from harness import stats as st


@dataclasses.dataclass
class Context:
    sizes: dict             # the model (see harness.config.model_sizes)
    cell: dict              # bench/cells/<workload>.json
    mix: dict               # bench/traffic/<mix>.json
    peak: dict              # harness.peaks
    window: object          # harness.serve.Window
    setup_s: float
    quantize_s: float
    trace: object = None    # harness.trace.Trace (traced runs)
    trace_offset_ns: float = 0.0   # trace ns = monotonic s * 1e9 + offset
    tracer: list | None = None     # obs.tracer request dicts (traced runs)
    timeline: list | None = None   # scheduler timeline records

    # -- client-side views of the window ------------------------------------

    def in_window(self, t: float) -> bool:
        w = self.window
        return w.t_open <= t <= w.t_close

    def ttfts_s(self) -> list[float]:
        """Time to first token of every request sent, from its due time; a
        request still waiting when the window closes counts its wait."""
        w = self.window
        return [(r.times[0] if r.times else w.t_close) - r.due
                for r in w.records.values()]

    def token_gaps_s(self) -> list[float]:
        """Every gap between consecutive output tokens of a request, both
        tokens inside the window."""
        out = []
        for r in self.window.records.values():
            ts = [t for t in r.times if self.in_window(t)]
            out += [b - a for a, b in zip(ts, ts[1:])]
        return out

    def percentile_ms(self, values, q) -> float | None:
        v = st.percentile(values, q)
        return None if v is None else v * 1000.0

    # -- traced interval ----------------------------------------------------

    def to_ns(self, t: float) -> float:
        return t * 1e9 + self.trace_offset_ns

    def traced_steps(self) -> list:
        """Decode steps recorded inside the traced interval, as
        ``(t0_ns, t1_ns, positions)``."""
        return self._inside(self.window.steps)

    def traced_waves(self) -> list:
        return self._inside(self.window.waves)

    def _inside(self, recs) -> list:
        if self.trace is None:
            return []
        lo, hi = self.trace.lo, self.trace.hi
        out = []
        for t0, t1, rows in recs:
            a, b = self.to_ns(t0), self.to_ns(t1)
            if a >= lo and b <= hi:
                out.append((a, b, rows))
        return out

    def decode_programs(self) -> list:
        if self.trace is None:
            return []
        return self.trace.programs_in(
            r"decode_step", [(a, b) for a, b, _ in self.traced_steps()])

    def prefill_programs(self) -> list:
        if self.trace is None:
            return []
        return self.trace.programs_in(
            r"prefill", [(a, b) for a, b, _ in self.traced_waves()])

    @staticmethod
    def program_s(programs) -> float:
        return sum(b - a for _, a, b in programs) / 1e9

    def slots(self) -> int:
        return self.cell["server"]["slots"]
