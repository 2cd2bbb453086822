"""Roofline time of the packed SplitQuantV2 matmul launches of the traced
decode steps (codes, cluster ids, tables, x and out at the launch shapes;
2*M*K*N), over the device time of the ``splitq_packed_matmul`` kernel
events inside those decode programs (%)."""

from harness import work
from harness.peaks import roofline_s


def read(ctx):
    steps = ctx.traced_steps()
    progs = ctx.decode_programs()
    if not steps or not progs:
        return None
    t = ctx.trace.op_time_in(r"^splitq_packed_matmul", progs)
    if t <= 0:
        return None
    per_step = sum(roofline_s(*work.packed_matmul_work(m, k, n), ctx.peak)
                   for m, k, n in work.decode_packed_calls(ctx.sizes,
                                                        ctx.slots()))
    return 100.0 * len(progs) * per_step / t
