"""Model FLOPs of the live rows' decode tokens over the device time of the
decode programs that computed them, as a share of the chip's bf16 peak (%).
Padding rows and slots count as waste."""

from harness import work


def read(ctx):
    steps = ctx.traced_steps()
    progs = ctx.decode_programs()
    if not steps or not progs:
        return None
    flops = sum(work.decode_token_flops(ctx.sizes, p)
                for _, _, pos in steps for p in pos)
    return 100.0 * flops / (ctx.program_s(progs) * ctx.peak["bf16_flops"])
