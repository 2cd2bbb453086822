"""Seconds from the start of the run to the opening of the window: backend
start, weights, quantization, server build and warm-up compiles."""


def read(ctx):
    return ctx.setup_s
