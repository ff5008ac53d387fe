"""fairk_kernel_ms: device time per round of the fused FAIR-k kernel."""

from chipbench import trace


def read(ctx):
    return ctx.per_round_ms(trace.is_fairk_kernel)
