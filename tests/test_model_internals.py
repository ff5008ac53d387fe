"""Deep correctness oracles for the model internals.

* ssd_chunked (the TPU-adapted chunked SSD) vs the exact token-by-token
  recurrence (ssd_step) — the state-space-duality identity itself.
* chunked (flash-style) attention vs single-tile plain attention, across
  causal/window/GQA configurations.
* causal conv decode-state consistency.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import attention as attn
from repro.models.mamba2 import _causal_conv, ssd_chunked, ssd_step


class TestSSD:
    @pytest.mark.parametrize("seq,chunk", [(32, 8), (64, 16), (48, 16),
                                           (17, 8)])
    def test_chunked_equals_recurrence(self, seq, chunk):
        """SSD chunked scan == exact recurrent scan (fp32, tight tol)."""
        rng = np.random.default_rng(seq * chunk)
        b, h, p, n = 2, 4, 8, 16
        x = jnp.asarray(rng.normal(size=(b, seq, h, p)).astype("f4"))
        dt = jnp.asarray(0.5 * rng.random((b, seq, h)).astype("f4") + 0.1)
        a = -jnp.asarray(np.linspace(0.5, 2.0, h).astype("f4"))
        bmat = jnp.asarray(rng.normal(size=(b, seq, h, n)).astype("f4"))
        cmat = jnp.asarray(rng.normal(size=(b, seq, h, n)).astype("f4"))

        y_chunk, state_chunk = ssd_chunked(x, dt, a, bmat, cmat, chunk)

        state = jnp.zeros((b, h, p, n), jnp.float32)
        ys = []
        for t in range(seq):
            y_t, state = ssd_step(state, x[:, t], dt[:, t], a,
                                  bmat[:, t], cmat[:, t])
            ys.append(y_t)
        y_rec = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_rec),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(state_chunk),
                                   np.asarray(state), rtol=2e-4, atol=2e-4)

    def test_initial_state_carries(self):
        """Prefill with an initial state == recurrence from that state."""
        rng = np.random.default_rng(7)
        b, seq, h, p, n = 1, 16, 2, 4, 8
        x = jnp.asarray(rng.normal(size=(b, seq, h, p)).astype("f4"))
        dt = jnp.asarray(0.3 * np.ones((b, seq, h), "f4"))
        a = -jnp.ones((h,), jnp.float32)
        bm = jnp.asarray(rng.normal(size=(b, seq, h, n)).astype("f4"))
        cm = jnp.asarray(rng.normal(size=(b, seq, h, n)).astype("f4"))
        s0 = jnp.asarray(rng.normal(size=(b, h, p, n)).astype("f4"))
        y1, sf1 = ssd_chunked(x, dt, a, bm, cm, chunk=8, init_state=s0)
        state = s0
        for t in range(seq):
            y_t, state = ssd_step(state, x[:, t], dt[:, t], a, bm[:, t],
                                  cm[:, t])
        np.testing.assert_allclose(np.asarray(sf1), np.asarray(state),
                                   rtol=2e-4, atol=2e-4)


class TestAttentionEquivalence:
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 48),
                                               (False, 0)])
    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2), (6, 1)])
    def test_chunked_equals_plain(self, causal, window, n_heads, n_kv):
        rng = np.random.default_rng(n_heads * 97 + n_kv)
        b, s, hd = 2, 128, 16
        q = jnp.asarray(rng.normal(size=(b, s, n_heads, hd)).astype("f4"))
        k = jnp.asarray(rng.normal(size=(b, s, n_kv, hd)).astype("f4"))
        v = jnp.asarray(rng.normal(size=(b, s, n_kv, hd)).astype("f4"))
        pos = jnp.arange(s)
        out_plain = attn.plain_attention(q, k, v, pos, pos, causal=causal,
                                         window=window)
        out_chunk = attn.chunked_attention(q, k, v, pos, pos, causal=causal,
                                           window=window, q_chunk=32,
                                           kv_chunk=32)
        np.testing.assert_allclose(np.asarray(out_plain),
                                   np.asarray(out_chunk), rtol=2e-4,
                                   atol=2e-4)

    def test_causal_skip_matches_full(self):
        rng = np.random.default_rng(3)
        b, s, h, hd = 1, 128, 4, 16
        q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype("f4"))
        k = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype("f4"))
        v = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype("f4"))
        pos = jnp.arange(s)
        full = attn.chunked_attention(q, k, v, pos, pos, causal=True,
                                      q_chunk=32, kv_chunk=32,
                                      causal_skip=False)
        skip = attn.chunked_attention(q, k, v, pos, pos, causal=True,
                                      q_chunk=32, kv_chunk=32,
                                      causal_skip=True)
        np.testing.assert_allclose(np.asarray(full), np.asarray(skip),
                                   rtol=2e-4, atol=2e-4)

    def test_decode_attend_matches_plain_last_row(self):
        rng = np.random.default_rng(11)
        b, s, h, n_kv, hd = 2, 64, 8, 2, 16
        q_all = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype("f4"))
        k = jnp.asarray(rng.normal(size=(b, s, n_kv, hd)).astype("f4"))
        v = jnp.asarray(rng.normal(size=(b, s, n_kv, hd)).astype("f4"))
        pos = jnp.arange(s)
        ref = attn.plain_attention(q_all, k, v, pos, pos, causal=True)
        cache = attn.init_cache(b, s, n_kv, hd, jnp.float32)
        cache = attn.cache_fill(cache, k, v, pos)
        out = attn.decode_attend(q_all[:, -1:], cache, jnp.asarray(s - 1))
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(ref[:, -1]), rtol=2e-4,
                                   atol=2e-4)


class TestCausalConv:
    @settings(max_examples=15, deadline=None)
    @given(seq=st.integers(4, 32), seed=st.integers(0, 50))
    def test_streaming_equals_full(self, seq, seed):
        """Running the conv one token at a time with the carried state must
        equal the full-sequence conv (decode-path correctness)."""
        rng = np.random.default_rng(seed)
        c, kk = 6, 4
        x = jnp.asarray(rng.normal(size=(1, seq, c)).astype("f4"))
        w = jnp.asarray(rng.normal(size=(kk, c)).astype("f4"))
        bias = jnp.asarray(rng.normal(size=(c,)).astype("f4"))
        y_full, _ = _causal_conv(x, w, bias)
        state = jnp.zeros((1, kk - 1, c), jnp.float32)
        ys = []
        for t in range(seq):
            y_t, state = _causal_conv(x[:, t:t + 1], w, bias, state)
            ys.append(y_t)
        y_stream = jnp.concatenate(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_stream),
                                   rtol=1e-5, atol=1e-5)
