"""The work a round needs.

A configuration's counts live in its plain reference,
``chipbench/reference/<reference>.py``, beside its equations:
``param_count(model)`` and ``forward_flops_per_token(model, seq_len)``,
plain arithmetic on the config's ``model`` dict.  Model FLOPs count what
the forward and backward passes require, 2 per multiply-add, backward
twice forward.  Layers of several kinds are counted per kind, by the
period the config states.  A routed expert layer that holds a share of
the experts counts, per token, ``experts_per_token x held / published
experts`` of one expert's matmuls: the expected work of the held share.
The router and shared experts count whole.  Attention counts its causal
half.  Nothing recomputed by rematerialisation counts, and an embedding
gather is no matmul.  FAIR-k bytes count the least traffic the round's
selection needs at the persisted dtypes, not what today's kernel moves."""

from __future__ import annotations

import importlib
from typing import Any, Dict

# per coordinate: read g f32 (4), g_prev bf16 (2), age int8 (1); write
# g_prev' bf16 (2), age' int8 (1), merged g f32 (4)
FAIRK_BYTES = 14
# error feedback adds the residual, read and written in f32
FAIRK_EF_BYTES = 8


def reference(name: str):
    """The plain reference module ``chipbench.reference.<name>``: its
    ``loss`` and its two counts."""
    return importlib.import_module(f"chipbench.reference.{name}")


def train_flops_per_round(config: Dict[str, Any],
                          traffic: Dict[str, Any]) -> float:
    """Forward + backward (2x forward) model FLOPs of one round."""
    fwd = reference(config["reference"]).forward_flops_per_token(
        config["model"], traffic["seq_len"])
    return 3.0 * fwd * traffic["seq_len"] * traffic["batch"]


def fairk_bytes(coords: int, error_feedback: bool) -> int:
    """Least HBM bytes of one FAIR-k selection pass over ``coords``."""
    return coords * (FAIRK_BYTES + (FAIRK_EF_BYTES if error_feedback else 0))
