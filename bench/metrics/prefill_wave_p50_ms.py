"""Median time the serving loop, and every request decoding in it, stands
behind one prefill wave: for each ``host.serve.prefill`` span inside the
window, from the start of its ``dispatch`` phase to the end of its
``fetch`` (the wave's first tokens on the host). Read from the ``span``
field of the program's ``prefill`` timeline records; nothing where the
program writes none."""


def read(ctx):
    waits = []
    for r in ctx.timeline or []:
        sp = r.get("span")
        if (r["kind"] != "prefill" or sp is None
                or not (ctx.in_window(sp["t0"]) and ctx.in_window(sp["t1"]))):
            continue
        ph = sp["phases"]
        waits.append(ph["fetch"][1] - ph["dispatch"][0])
    return ctx.percentile_ms(waits, 50)
