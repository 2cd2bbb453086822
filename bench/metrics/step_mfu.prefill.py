"""Model FLOPs of the live prompt tokens over the device time of the
prefill programs that computed them, as a share of the chip's bf16 peak
(%). Padding to the wave's width and its idle rows count as waste."""

from harness import work


def read(ctx):
    waves = ctx.traced_waves()
    progs = ctx.prefill_programs()
    if not waves or not progs:
        return None
    flops = sum(work.prefill_flops(ctx.sizes, s, n)
                for _, _, rows in waves for s, n in rows)
    return 100.0 * flops / (ctx.program_s(progs) * ctx.peak["bf16_flops"])
