"""70th percentile of queued -> admitted over the requests sent in the
window, from the program's request spans (``obs.tracer``), queued at the
due time; a request never admitted counts its wait to the close."""


def read(ctx):
    if ctx.tracer is None:
        return None
    sent = set(ctx.window.records)
    waits = []
    for r in ctx.tracer:
        if r["rid"] not in sent:
            continue
        q = [s for s in r["spans"] if s["kind"] == "queued"][0]["t0"]
        adm = [s["t0"] for s in r["spans"] if s["kind"] == "admitted"]
        waits.append((adm[0] if adm else ctx.window.t_close) - q)
    return ctx.percentile_ms(waits, 70)
