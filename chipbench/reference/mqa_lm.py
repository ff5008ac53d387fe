"""Plain multi-query-attention language model in float32: the reference for
dense transformer configs.

Per layer, pre-norm: a LayerNorm (scale and bias); query projection to
``n_heads`` heads and key and value projections to ``n_kv_heads`` (one,
multi-query) heads of width ``head_dim``; rotary position embedding (the
half-split rotation, base ``rope_theta``) on queries and keys; causal
softmax attention in float32; the output projection, added to the residual
stream; a second LayerNorm and the feed-forward block ``wd(gelu(wu x +
b_u)) + b_d`` with ``jax.nn.gelu``'s default (tanh) form, added to the
residual stream.  Then a final LayerNorm and an untied LM head.

Departures from the published Granite Code 34B block (arXiv:2405.04324,
GPTBigCode architecture), all of them the program's, mirrored here so that
the comparison measures arithmetic and not architecture:

- rotary positions, base 1e4, where the paper's model learns absolute
  position embeddings added to the token embedding;
- no bias on the query, key, value and output projections, where
  GPTBigCode's projections carry biases;
- the tanh form of GELU (GPTBigCode's ``gelu_pytorch_tanh`` is the same);
- the LM head untied from the embedding, as the program keeps it; whether
  the published checkpoint ties them was not checked.

Every matmul runs at ``Precision.HIGHEST``.  ``quant`` rounds what the
program holds in its compute dtype: the operands of each matmul, the
normed activations, the projections, the rotated queries and keys, the
attention probabilities before they weight the values, the residual
stream between layers (the lower-precision control); the identity for the
reference itself.  Each layer and each block of query rows is
rematerialised in the backward pass, so that one block's scores and
probabilities are live at a time.  Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows whose attention scores are live at once


def _mm(x, w, quant):
    return jnp.einsum("...i,io->...o", quant(x), quant(w), precision=HI)


def _ln(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rope(x, theta):
    """x (B, L, H, D): rotate the pairs (x[i], x[i + D/2]) of position t by
    t * theta ** (-i / (D/2))."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant, block=Q_BLOCK):
    """Causal softmax attention, q (B, L, H, D), k and v (B, L, G, D) with G
    dividing H; each group of H / G query heads reads one key/value head.
    Rows are taken ``block`` at a time."""
    bsz, l, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(bsz, l, g, h // g, d)
    block = min(block, l)
    cols = jnp.arange(l)

    def rows(qb, start):
        s = jnp.einsum("bqgjd,bkgd->bgjqk", qb, k, precision=HI) / d ** 0.5
        pos = start + jnp.arange(qb.shape[1])
        s = jnp.where(cols[None, :] <= pos[:, None], s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        o = jnp.einsum("bgjqk,bkgd->bqgjd", quant(p), v, precision=HI)
        return o / jnp.moveaxis(jnp.sum(p, -1), -1, 1)[..., None]

    starts = jnp.arange(0, l, block)
    qs = jnp.moveaxis(q.reshape(bsz, l // block, block, g, h // g, d), 1, 0)
    out = jax.lax.map(lambda a: jax.checkpoint(rows)(*a), (qs, starts))
    return jnp.moveaxis(out, 0, 1).reshape(bsz, l, h, d)


def _layer(x, p, m, quant):
    eps, theta = m["norm_eps"], m["rope_theta"]
    n_heads, n_kv = m["n_heads"], m["n_kv_heads"]
    hd = m["d_model"] // n_heads
    bsz, l = x.shape[:2]
    mx, ffn = p["mixer"], p["ffn"]
    h = quant(_ln(x, p["norm1"], eps))
    q = quant(_mm(h, mx["wq"]["w"], quant)).reshape(bsz, l, n_heads, hd)
    k = quant(_mm(h, mx["wk"]["w"], quant)).reshape(bsz, l, n_kv, hd)
    v = quant(_mm(h, mx["wv"]["w"], quant)).reshape(bsz, l, n_kv, hd)
    o = attention(quant(rope(q, theta)), quant(rope(k, theta)), v, quant)
    x = quant(x + quant(_mm(o.reshape(bsz, l, n_heads * hd),
                            mx["wo"]["w"], quant)))
    h = quant(_ln(x, p["norm2"], eps))
    u = quant(jax.nn.gelu(quant(_mm(h, ffn["wu"]["w"], quant)
                                + ffn["wu"]["b"])))
    return quant(x + quant(_mm(u, ffn["wd"]["w"], quant) + ffn["wd"]["b"]))


def loss(params, tokens, labels, m, quant=lambda t: t):
    """Mean next-token cross entropy over a (B, L) batch."""
    x = quant(params["embed"][tokens])
    block = params["blocks"][0]

    def body(x, p):
        return jax.checkpoint(lambda x_, p_: _layer(x_, p_, m, quant))(x, p), None

    x, _ = jax.lax.scan(body, x, block)
    x = quant(_ln(x, params["final_norm"], m["norm_eps"]))
    logits = _mm(x, params["head"]["w"], quant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
