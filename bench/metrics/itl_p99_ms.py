"""99th percentile of the gap between consecutive output tokens of a
request, over every such gap inside the window. In the chat cell about 6%
of gaps hold a prefill wave, so this percentile reads the gaps in which
decode stood still behind a wave; ``itl_p90_ms`` reads a decode step."""


def read(ctx):
    return ctx.percentile_ms(ctx.token_gaps_s(), 99)
