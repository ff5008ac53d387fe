"""client_scope_ms: device time per round of the top-level ops the
``client`` scope owns alone: the clients' forward and backward scan, its
accumulators and the gradient's scaling (``launch/steps.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "client")
