"""Median host turnaround between decode steps: for each pair of
consecutive ``host.serve.decode`` spans inside the window with no
``host.serve.prefill`` span between them, from the end of step t's
``fetch`` phase (its tokens on the host) to the end of step t+1's
``dispatch`` (the next step enqueued), which the device waits for. Read
from the ``span`` field of the program's timeline records; nothing where
the program writes none."""


def read(ctx):
    gaps, fetched = [], None
    for r in ctx.timeline or []:
        sp = r.get("span")
        if sp is None or r["kind"] not in ("prefill", "decode"):
            continue
        inside = ctx.in_window(sp["t0"]) and ctx.in_window(sp["t1"])
        if r["kind"] == "prefill" or not inside:
            fetched = None
            continue
        if fetched is not None:
            gaps.append(sp["phases"]["dispatch"][1] - fetched)
        fetched = sp["phases"]["fetch"][1]
    return ctx.percentile_ms(gaps, 50)
