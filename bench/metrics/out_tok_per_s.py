"""Output tokens the clients received inside the window, over its length."""


def read(ctx):
    w = ctx.window
    n = sum(1 for r in w.records.values() for t in r.times if ctx.in_window(t))
    return n / (w.t_close - w.t_open)
