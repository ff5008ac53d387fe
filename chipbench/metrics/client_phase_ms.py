"""client_phase_ms: device time per round of the ops of the microbatch
scan (the clients' forward and backward)."""

from chipbench import trace


def read(ctx):
    return ctx.per_round_ms(trace.in_client_phase)
