"""state_cast_ms: device time per round of the top-level ops the
``server_cast`` scope owns alone: the casts of the server buffers to their
stored dtypes and ``pending``'s read back to f32 (``launch/steps.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "server_cast")
