"""Mean live rows per decode step inside the window, from the scheduler
timeline's ``decode`` records."""


def read(ctx):
    rows = [r["rows"] for r in (ctx.timeline or [])
            if r["kind"] == "decode" and ctx.in_window(r["t"])]
    return sum(rows) / len(rows) if rows else None
