"""adamw_ms: device time per round of the top-level ops the ``adamw``
scope owns alone: the optimizer update and the parameter add
(``launch/steps.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "adamw")
