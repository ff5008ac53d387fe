"""unattributed_ms: device time per round of the top-level ops no single
scope owns (XLA fused work of several scopes into them, or work outside
every scope), other than the FAIR-k kernel."""

from chipbench import scopes


def read(ctx):
    return scopes.unattributed_ms(ctx)
