"""Model FLOP utilization of the window: the forward and backward FLOPs of
the local gradients that the rounds' updates use (the configuration's
module counts them per round), over the calls completed in the traced
window, divided by the window's seconds and the chip's bf16 peak."""


def read(ctx):
    flops = ctx.config_module.grad_flops_per_round(ctx.config, ctx.traffic)
    done = len(ctx.calls) * ctx.cell.rounds_per_call * flops
    return 100.0 * done / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
