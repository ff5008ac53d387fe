"""Plain Mamba-2 language model in float32: the reference for SSM configs.

Follows arXiv:2405.21060 as published: per layer an RMSNorm, the input
projections to z, x, B, C and dt, a causal depthwise convolution with SiLU
over x and over (B, C), the SSD scan with a per-head scalar decay
A = -exp(a_log), step dt = softplus(. + dt_bias) and skip D, a gated
RMSNorm of y * silu(z), and the output projection, added to the residual
stream.  The scan is the paper's own minimal discrete SSD listing (chunked,
with the stable masked-cumsum segment sum), at block length 64.  The LM
head is tied to the embedding.

Every matmul runs at ``Precision.HIGHEST``.  ``quant`` rounds what the
program holds in its compute dtype: both operands of each matmul, the
SSD's inputs and the residual stream between layers (the lower-precision
control); the identity for the reference itself.  Imports nothing of the
program.  ``param_count`` and ``forward_flops_per_token`` count the model
from its config alone (``chipbench/flops.py`` states the rules)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BLOCK = 64


def _mm(x, w, quant):
    return jnp.einsum("...i,io->...o", quant(x), quant(w), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, w, b):
    """Causal depthwise convolution: y[t] = sum_i w[i] x[t - K + 1 + i]."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(y + b)


def segsum(x):
    """(..., T) -> (..., T, T): out[i, j] = sum_{j < k <= i} x[k], -inf
    above the diagonal; summed without differences of cumulative sums."""
    t = x.shape[-1]
    xr = jnp.broadcast_to(x[..., :, None], x.shape + (t,))
    xr = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xr, 0.0)
    s = jnp.cumsum(xr, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool), 0), s, -jnp.inf)


def ssd(x, a, b, c, block=BLOCK):
    """Minimal discrete SSD.  x (B, L, H, P) already times dt; a (B, L, H)
    = A * dt; b, c (B, L, H, N).  Returns y (B, L, H, P)."""
    bsz, l, h, p = x.shape
    nc = l // block
    ch = lambda t: t.reshape((bsz, nc, block) + t.shape[2:])
    x, b, c = ch(x), ch(b), ch(c)
    a = jnp.moveaxis(ch(a), -1, 1)                             # (B,H,C,L)
    a_cs = jnp.cumsum(a, -1)
    lmat = jnp.exp(segsum(a))                                  # (B,H,C,L,L)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", c, b, lmat, x,
                        precision=HI)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", b, decay_states, x,
                        precision=HI)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                        precision=HI)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs),
                       precision=HI)
    return (y_diag + y_off).reshape(bsz, l, h, p)


def _layer(x, p, m, quant):
    eps = m["norm_eps"]
    d_in = m["ssm_expand"] * m["d_model"]
    n, hd = m["ssm_state"], m["ssm_head_dim"]
    heads, g = d_in // hd, m["ssm_groups"]
    mx = p["mixer"]
    hn = _rms(x, p["norm1"]["scale"], eps)
    z = _mm(hn, mx["wz"]["w"], quant)
    xc = _conv(_mm(hn, mx["wx"]["w"], quant), mx["conv_x_w"], mx["conv_x_b"])
    bc = _conv(_mm(hn, mx["wbc"]["w"], quant), mx["conv_bc_w"],
               mx["conv_bc_b"])
    dt = jax.nn.softplus(_mm(hn, mx["wdt"]["w"], quant) + mx["dt_bias"])
    a = -jnp.exp(mx["a_log"])
    bsz, l = x.shape[:2]
    rep = lambda t: jnp.repeat(t.reshape(bsz, l, g, n), heads // g, axis=2)
    bm, cm = rep(bc[..., :g * n]), rep(bc[..., g * n:])
    xh = xc.reshape(bsz, l, heads, hd)
    y = ssd(quant(xh * dt[..., None]), a * dt, quant(bm), quant(cm))
    y = (y + mx["d_skip"][:, None] * xh).reshape(bsz, l, d_in)
    y = _rms(y * jax.nn.silu(z), mx["norm"]["scale"], eps)
    return quant(x + _mm(y, mx["out_proj"]["w"], quant))


def loss(params, tokens, labels, m, quant=lambda t: t):
    """Mean next-token cross entropy over a (B, L) batch."""
    x = quant(params["embed"][tokens])
    block = params["blocks"][0]

    def body(x, p):
        return jax.checkpoint(lambda x_, p_: _layer(x_, p_, m, quant))(x, p), None

    x, _ = jax.lax.scan(body, x, block)
    x = _rms(x, params["final_norm"]["scale"], m["norm_eps"])
    logits = jnp.einsum("bld,vd->blv", quant(x), quant(params["embed"]),
                        precision=HI)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def _ssm_layer(m):
    d, n, g = m["d_model"], m["ssm_state"], m["ssm_groups"]
    d_in = m["ssm_expand"] * d
    h = d_in // m["ssm_head_dim"]
    conv_ch = d_in + 2 * g * n
    return {"d_in": d_in, "heads": h, "conv_ch": conv_ch,
            "in_proj": d * (2 * d_in + 2 * g * n + h),
            "out_proj": d_in * d}


def param_count(m) -> int:
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


def forward_flops_per_token(m, seq_len: int) -> int:
    """Forward FLOPs per token: 2 per multiply-add of the projections and
    the head, the depthwise convolution, and the SSD recurrence's state
    update (B x^T, 2 P N per head) and read-out (C h, 2 P N per head).
    None of it grows with ``seq_len``."""
    lay = _ssm_layer(m)
    p, n = m["ssm_head_dim"], m["ssm_state"]
    per_layer = (2 * (lay["in_proj"] + lay["out_proj"])
                 + 2 * m["ssm_conv"] * lay["conv_ch"]
                 + 4 * lay["heads"] * p * n)
    return m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab"]
