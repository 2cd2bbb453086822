"""70th percentile of time to first token over every request sent in the
window, from its due time; one still waiting at the close counts its wait.
(The highest percentile with about ten of a 51 s chat window's 31 requests
beyond it.)"""


def read(ctx):
    return ctx.percentile_ms(ctx.ttfts_s(), 70)
