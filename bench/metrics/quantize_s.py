"""Host seconds of ``restructure`` + ``as_executable(group=True)``, to
``block_until_ready``: the paper's preprocessing, paid by every start."""


def read(ctx):
    return ctx.quantize_s
