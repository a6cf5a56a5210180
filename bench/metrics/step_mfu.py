"""Model FLOP/s utilisation of the whole round, in %: 6·N per position of
every worker's rows (prefix included) times the rounds completed, over the
window, the chips and the chip's bf16 peak (f32 matmuls at default precision
run as bf16 passes)."""

from bench.yardstick import model_flops


def read(ctx):
    if not ctx.rounds:
        return None
    per_call = model_flops(ctx.session.params_count(), ctx.session.positions_per_call)
    flops = per_call * ctx.calls
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
