"""90th percentile of the gap between consecutive output tokens of a
request, over every such gap inside the window: a decode step. (The gaps
that a prefill wave interrupts lie above it; ``itl_p99_ms`` reads them.)"""


def read(ctx):
    return ctx.percentile_ms(ctx.token_gaps_s(), 90)
