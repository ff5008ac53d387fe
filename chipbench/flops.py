"""The work a round needs, from the architecture alone.

Model FLOPs count what the forward and backward passes require: the
projections, the LM head, and the sequence mixer's own term (the SSD
state recurrence, or causal attention), forward plus twice that backward.
Nothing recomputed by rematerialisation counts, and the embedding gather
is no matmul.  FAIR-k bytes count the least traffic the round's selection
needs at the persisted dtypes, not what today's kernel moves."""

from __future__ import annotations

from typing import Any, Dict

# per coordinate: read g f32 (4), g_prev bf16 (2), age int8 (1); write
# g_prev' bf16 (2), age' int8 (1), merged g f32 (4)
FAIRK_BYTES = 14
# error feedback adds the residual, read and written in f32
FAIRK_EF_BYTES = 8


def _ssm_layer(m: Dict[str, Any]) -> Dict[str, int]:
    d, n, g = m["d_model"], m["ssm_state"], m["ssm_groups"]
    d_in = m["ssm_expand"] * d
    h = d_in // m["ssm_head_dim"]
    conv_ch = d_in + 2 * g * n
    return {"d_in": d_in, "heads": h, "conv_ch": conv_ch,
            "in_proj": d * (2 * d_in + 2 * g * n + h),
            "out_proj": d_in * d}


def ssm_param_count(m: Dict[str, Any]) -> int:
    """Parameters of a Mamba-2 LM: per layer the projections, the
    convolution weights and biases, A, D, dt_bias, the gated norm and two
    pre-norms (the program keeps a second one unused); the embedding,
    tied to the head, and the final norm."""
    lay = _ssm_layer(m)
    per_layer = (lay["in_proj"] + lay["out_proj"]
                 + (m["ssm_conv"] + 1) * lay["conv_ch"]
                 + 3 * lay["heads"] + lay["d_in"] + 2 * m["d_model"])
    head = 0 if m["tie_embeddings"] else m["vocab"] * m["d_model"]
    return (m["n_layers"] * per_layer + m["vocab"] * m["d_model"] + head
            + m["d_model"])


def ssm_forward_flops_per_token(m: Dict[str, Any]) -> int:
    """Forward FLOPs per token: 2 per multiply-add of the projections and
    the head, the depthwise convolution, and the SSD recurrence's state
    update (B x^T, 2 P N per head) and read-out (C h, 2 P N per head)."""
    lay = _ssm_layer(m)
    p, n = m["ssm_head_dim"], m["ssm_state"]
    per_layer = (2 * (lay["in_proj"] + lay["out_proj"])
                 + 2 * m["ssm_conv"] * lay["conv_ch"]
                 + 4 * lay["heads"] * p * n)
    return m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab"]


FORWARD = {"ssd_lm": ssm_forward_flops_per_token}


def train_flops_per_round(config: Dict[str, Any],
                          traffic: Dict[str, Any]) -> float:
    """Forward + backward (2x forward) model FLOPs of one round."""
    fwd = FORWARD[config["reference"]](config["model"])
    return 3.0 * fwd * traffic["seq_len"] * traffic["batch"]


def fairk_bytes(coords: int, error_feedback: bool) -> int:
    """Least HBM bytes of one FAIR-k selection pass over ``coords``."""
    return coords * (FAIRK_BYTES + (FAIRK_EF_BYTES if error_feedback else 0))
