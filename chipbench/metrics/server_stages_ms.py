"""server_stages_ms: device time per round of the top-level ops the
``server_stages`` scope owns alone: the wireless, async shadow mix,
one-bit, fade and population draws and the controller step
(``launch/steps.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "server_stages")
