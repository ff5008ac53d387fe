"""step_mfu: the whole round's model FLOPs over the traced window, as a
share of the chips' bf16 peak (``flops.train_flops_per_round``)."""

from chipbench import flops, trace


def read(ctx):
    per_round = flops.train_flops_per_round(ctx.cell.config,
                                            ctx.cell.traffic)
    peak = trace.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return (100.0 * per_round * ctx.rounds / ctx.trace_window_s()
            / (ctx.chips * peak))
