"""Roofline time of paged decode attention for the live rows' real
lengths (their K/V pages, query and output; 4 flops per key, head and
dim), over the device time of the ``paged_attention`` kernel events inside
the traced decode programs (%)."""

from harness import work
from harness.peaks import roofline_s


def read(ctx):
    steps = ctx.traced_steps()
    progs = ctx.decode_programs()
    if not steps or not progs:
        return None
    t = ctx.trace.op_time_in(r"^paged_attention", progs)
    if t <= 0:
        return None
    need = 0.0
    for _, _, pos in steps:
        flops = nbytes = 0
        for p in pos:
            f, b = work.paged_attention_work(ctx.sizes, p + 1)
            flops, nbytes = flops + f, nbytes + b
        need += roofline_s(flops, nbytes, ctx.peak)
    return 100.0 * need / t
