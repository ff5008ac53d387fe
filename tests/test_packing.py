"""Packed server state (core.packing + the engine's packed backend).

Pins the tentpole guarantees:
* pack -> unpack identity on multi-dtype pytrees (bf16 g_prev, int8 age);
* padding protocol: pads never selected, sentinel survives round trips,
  sampled thresholds exclude pad coordinates (incl. the exact
  block-boundary regression);
* bit-exact parity: packed backend == per-leaf application of the SAME
  global thresholds == exact top-k selection, on tie-free inputs with
  ``exact_theta=True``;
* warm-start thresholds: steady-state rounds skip the quantile pass while
  the realised count keeps tracking the budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.engine import (EngineConfig, SelectionEngine,
                               exact_thresholds, make_engine, masked_merge,
                               sampled_thresholds, threshold_mask)
from repro.kernels import ops
from repro.launch.mesh import make_mesh


def transformer_tree(seed=0, n_layers=3, d_model=64, vocab=500,
                     dtype="f4"):
    """Multi-dtype transformer-ish pytree with odd + exactly-lane-aligned
    leaf sizes (vocab*d_model = 32000 is NOT lane aligned; d_model**2 =
    4096 IS — the block-boundary case)."""
    rng = np.random.default_rng(seed)
    tree = {"embed": rng.standard_normal((vocab, d_model)),
            "final_norm": rng.standard_normal((d_model,))}
    for i in range(n_layers):
        tree[f"layer_{i}"] = {
            "w": rng.standard_normal((d_model, d_model)),
            "norm": rng.standard_normal((d_model,)),
            "b": rng.standard_normal((7,)),                # odd leaf
        }
    return jax.tree.map(lambda x: jnp.asarray(x.astype(dtype)), tree)


def tie_free_state(tree, seed=1, int8_ages=True):
    """(g, g_prev bf16, age) trees with distinct |g|.

    ``int8_ages=True``: ages in int8 (0..119, int8-safe but TIED — valid for
    paths that share the index-jitter tie-break).  ``False``: globally
    distinct f32 ages (a permutation of the whole tree) — required when
    comparing against the exact backend, whose ``lax.top_k`` breaks ties by
    lowest index instead of the jitter hash."""
    rng = np.random.default_rng(seed)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    perm = rng.permutation(sum(sizes))
    if int8_ages:
        perm = perm % 120                                 # int8-safe
    g, gp, age, off = [], [], [], 0
    for leaf, n in zip(leaves, sizes):
        g.append(jnp.asarray(rng.normal(size=leaf.shape).astype("f4")))
        gp.append(jnp.asarray(
            rng.normal(size=leaf.shape).astype("f4")).astype(jnp.bfloat16))
        chunk = perm[off:off + n].reshape(leaf.shape)
        age.append(jnp.asarray(chunk.astype("i1") if int8_ages
                               else chunk.astype("f4")))
        off += n
    mk = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
    return mk(g), mk(gp), mk(age)


# ---------------------------------------------------------------------------
# layout / pack / unpack
# ---------------------------------------------------------------------------

class TestLayout:
    def test_block_table_lane_alignment(self):
        tree = transformer_tree()
        lay = packing.PackedLayout.from_tree(tree)
        for e in lay.table:
            assert e.offset % lay.lane == 0
            assert (e.size + e.pad) % lay.lane == 0
        assert lay.d_valid == sum(e.size for e in lay.table)
        assert lay.d_packed % lay.lane == 0

    def test_pack_unpack_identity_multi_dtype(self):
        """f32 grads, bf16 g_prev and int8 age all round-trip bitwise."""
        tree = transformer_tree()
        for t in tie_free_state(tree):
            lay = packing.PackedLayout.from_tree(t)   # records leaf dtypes
            back = lay.unpack(lay.pack(t))
            for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32))

    @pytest.mark.parametrize("cast", [True, False], ids=["cast", "raw"])
    @pytest.mark.parametrize("src", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_unpack_equals_slice_reshape_astype(self, src, cast):
        """Bit for bit the plain per-leaf slice -> reshape -> astype, for
        every leaf class of the launch path's buffer: minor widths 32, 256,
        1024 and 2048, 1-D and scalar leaves, f32 and bf16 leaves, a leaf
        that ends on a LANE boundary (3 x 256, no pad) and a buffer whose
        last 1024-tile is partial (9,984 coordinates)."""
        specs = [((5, 32), jnp.float32), ((3, 256), jnp.float32),
                 ((2, 1024), jnp.bfloat16), ((3, 4, 32), jnp.float32),
                 ((2, 3, 256), jnp.float32), ((1, 2, 2048), jnp.float32),
                 ((100,), jnp.float32), ((7,), jnp.bfloat16),
                 ((), jnp.float32)]
        lay = packing.PackedLayout.from_tree(
            [jax.ShapeDtypeStruct(s, dt) for s, dt in specs])
        assert lay.table[1].pad == 0 and lay.d_packed % 1024
        flat = jnp.asarray(np.random.default_rng(0).standard_normal(
            lay.d_packed).astype("f4")).astype(src)

        def plain(flat):
            out = []
            for e in lay.table:
                leaf = jax.lax.slice(flat, (e.offset,),
                                     (e.offset + e.size,)).reshape(e.shape)
                out.append(leaf.astype(e.dtype) if cast else leaf)
            return out

        got = jax.jit(lambda f: lay.unpack(f, cast=cast))(flat)
        for e, a, b in zip(lay.table, got, jax.jit(plain)(flat)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype) == (
                e.shape, e.dtype if cast else flat.dtype)
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    def test_pack_age_sentinel_and_init_age(self):
        tree = transformer_tree()
        _, _, age = tie_free_state(tree)
        lay = packing.PackedLayout.from_tree(tree)
        buf = lay.pack_age(age)
        valid = np.asarray(lay.valid_mask())
        assert (np.asarray(buf)[~valid] == packing.PAD_AGE).all()
        assert (np.asarray(buf)[valid] >= 0).all()
        init = np.asarray(lay.init_age(jnp.int8))
        assert (init[valid] == 0).all() and (init[~valid] == -1).all()

    def test_exact_block_boundary_leaf_has_no_pad(self):
        """A leaf of exactly lane*k elements must get pad == 0 (off-by-one
        guard for the block table)."""
        lay = packing.PackedLayout.from_tree(
            [jnp.zeros((256,)), jnp.zeros((512,)), jnp.zeros((100,))])
        assert [e.pad for e in lay.table] == [0, 0, 156]
        assert lay.d_packed == 256 + 512 + 256


# ---------------------------------------------------------------------------
# pad-excluding thresholds (satellite regression)
# ---------------------------------------------------------------------------

class TestPadExcludingThresholds:
    def test_sample_ids_hit_only_valid_coords(self):
        tree = transformer_tree()
        lay = packing.PackedLayout.from_tree(tree)
        ids = lay.sample_ids(1 << 14)
        valid = np.asarray(lay.valid_mask())
        assert valid[ids].all()

    def test_pad_zeros_would_bias_theta_m_low(self):
        """Regression: a heavily padded buffer (many small leaves) must
        produce the same θ_M as the unpadded flat vector; the naive strided
        sample over the padded buffer is biased low by the pad zeros."""
        rng = np.random.default_rng(3)
        # 64 leaves x 300 elements -> pad fraction 212/512 per leaf
        leaves = [jnp.asarray(rng.normal(size=300).astype("f4"))
                  for _ in range(64)]
        lay = packing.PackedLayout.from_tree(leaves)
        ages = [jnp.asarray(rng.integers(0, 40, 300).astype("f4"))
                for _ in range(64)]
        g_buf = lay.pack(leaves)
        age_buf = lay.pack_age(ages)
        kw = dict(rho=0.1, k_m_frac=0.75, sample_cap=lay.d_packed)
        tm_clean, _ = sampled_thresholds(g_buf, age_buf,
                                         sample_ids=lay.sample_ids(
                                             lay.d_valid), **kw)
        tm_naive, _ = sampled_thresholds(g_buf, age_buf, **kw)
        flat = jnp.concatenate([l for l in leaves])
        flat_age = jnp.concatenate(ages)
        tm_ref, _ = sampled_thresholds(flat, flat_age, rho=0.1,
                                       k_m_frac=0.75,
                                       sample_cap=flat.shape[0])
        assert abs(float(tm_clean) - float(tm_ref)) < 0.02
        assert float(tm_naive) < float(tm_ref) - 0.1   # the bias being fixed

    def test_exact_block_boundary_leaf_thresholds(self):
        """At an exactly lane-aligned leaf length there are no pads at all:
        pad-excluding ids must equal the plain strided sample."""
        rng = np.random.default_rng(4)
        leaves = [jnp.asarray(rng.normal(size=512).astype("f4")),
                  jnp.asarray(rng.normal(size=256).astype("f4"))]
        lay = packing.PackedLayout.from_tree(leaves)
        assert lay.d_packed == lay.d_valid == 768
        ids = lay.sample_ids(768)
        np.testing.assert_array_equal(ids, np.arange(768))


# ---------------------------------------------------------------------------
# parity: packed == per-leaf(same θ) == exact  (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestPackedParity:
    def _packed_inputs(self, int8_ages=True):
        tree = transformer_tree()
        g, gp, age = tie_free_state(tree, int8_ages=int8_ages)
        lay = packing.PackedLayout.from_tree(g)
        return lay, g, gp, age

    def test_packed_matches_per_leaf_same_thresholds(self):
        """One fused pass over the packed buffer == the per-leaf loop
        applying the SAME global (θ_M, θ_A) leaf by leaf (index_offset
        aligns the jitter) — bit-exact, incl. the int8 age round-trip."""
        lay, g, gp, age = self._packed_inputs()
        g_buf, gp_buf = lay.pack(g), lay.pack(gp)
        age_buf = lay.pack_age(age)
        k = max(2, round(0.1 * lay.d_valid))
        k_m = int(round(0.75 * k))
        tm, ta = exact_thresholds(g_buf, age_buf, k=k, k_m=k_m)
        gt_buf, age_next = ops.fairk_update(g_buf, gp_buf, age_buf, tm, ta)
        gt_tree = lay.unpack(gt_buf, cast=False)
        age_tree = lay.unpack(age_next, cast=False)
        g_ls = lay.treedef.flatten_up_to(g)
        gp_ls = lay.treedef.flatten_up_to(gp)
        age_ls = lay.treedef.flatten_up_to(age)
        for e, gl, gpl, al, gt_l, an_l in zip(
                lay.table, g_ls, gp_ls, age_ls,
                jax.tree.leaves(gt_tree), jax.tree.leaves(age_tree)):
            mask, _ = threshold_mask(gl.reshape(-1),
                                     al.reshape(-1).astype(jnp.float32),
                                     tm, ta, index_offset=e.offset)
            ref_g, ref_age = masked_merge(
                gl.reshape(-1), gpl.reshape(-1).astype(jnp.float32),
                al.reshape(-1).astype(jnp.float32), mask)
            np.testing.assert_array_equal(np.asarray(gt_l).reshape(-1),
                                          np.asarray(ref_g))
            np.testing.assert_array_equal(np.asarray(an_l).reshape(-1),
                                          np.asarray(ref_age))
            # int8 server round trip is exact (ages <= AGE_CAP = 120)
            np.testing.assert_array_equal(
                np.asarray(an_l).astype(np.int8).astype(np.float32),
                np.asarray(an_l))

    def test_packed_matches_exact_backend(self):
        """Packed threshold backend (exact_theta) == exact lax.top_k
        backend run on the same packed buffer, bit-exact on the valid
        coordinates (tie-free inputs)."""
        lay, g, gp, age = self._packed_inputs(int8_ages=False)
        g_buf, gp_buf = lay.pack(g), lay.pack(gp)
        age_buf = lay.pack_age(age)
        pk = SelectionEngine(
            EngineConfig(policy="fairk", backend="packed", rho=0.1,
                         k_m_frac=0.75, exact_theta=True,
                         kernel_mode="interpret"),
            lay.d_packed, layout=lay)
        k, k_m, r = pk.budgets()
        assert k == max(2, round(0.1 * lay.d_valid))      # budgets on d_valid
        ex = SelectionEngine(
            EngineConfig(policy="fairk", backend="exact", k=k, k_m=k_m,
                         r=r), lay.d_packed)
        g1, a1, s1 = pk.select_and_merge(g_buf, gp_buf, age_buf)
        g2, a2, s2 = jax.jit(ex.select_and_merge)(g_buf, gp_buf, age_buf)
        valid = np.asarray(lay.valid_mask())
        np.testing.assert_array_equal(np.asarray(g1)[valid],
                                      np.asarray(g2)[valid])
        np.testing.assert_array_equal(np.asarray(a1)[valid],
                                      np.asarray(a2)[valid])
        assert float(s1["n_selected"]) == k               # pads never count
        # pads: sentinel survives, never selected, value = g_prev (= pad 0)
        assert (np.asarray(a1)[~valid] == packing.PAD_AGE).all()

    def test_select_and_merge_tree_facade(self):
        lay, g, gp, age = self._packed_inputs()
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend="packed", rho=0.1,
                         k_m_frac=0.75, exact_theta=True),
            lay.d_packed, layout=lay)
        gt_tree, age_tree, stats = eng.select_and_merge_tree(g, gp, age)
        g_buf, gp_buf, age_buf = (lay.pack(g), lay.pack(gp),
                                  lay.pack_age(age))
        gt_buf, age_next, _ = eng.select_and_merge(g_buf, gp_buf, age_buf)
        for a, b in zip(jax.tree.leaves(gt_tree),
                        jax.tree.leaves(lay.unpack(gt_buf, cast=False))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert jax.tree_util.tree_structure(
            gt_tree) == jax.tree_util.tree_structure(g)


# ---------------------------------------------------------------------------
# warm-start thresholds
# ---------------------------------------------------------------------------

class TestWarmStart:
    def test_steady_state_warms_and_tracks_budget(self):
        """After the cold-start transient the warm branch carries the
        thresholds (streak >= warm_streak) and the realised count stays
        inside the trust region; no round ever explodes past 2k."""
        rng = np.random.default_rng(0)
        shapes = {"a": (100, 100), "b": (999,), "c": (3, 7)}
        tree = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
        lay = packing.PackedLayout.from_tree(tree)
        eng = make_engine("fairk", "packed", layout=lay, rho=0.1,
                          k_m_frac=0.75, sample_cap=8192, warm_start=True)
        k = eng.budgets()[0]
        gp = jnp.zeros((lay.d_packed,), jnp.float32)
        ag = lay.init_age(jnp.float32)
        ts = packing.init_threshold_state()
        step = jax.jit(lambda g, gp, ag, ts:
                       eng.select_and_merge(g, gp, ag, tstate=ts))
        warm, sels = [], []
        for r in range(150):
            g = lay.pack({kk: jnp.asarray(
                rng.normal(size=s).astype("f4"))
                for kk, s in shapes.items()})
            warm.append(float(ts["streak"]) >= eng.cfg.warm_streak)
            g_t, ag2, stats = step(g, gp, ag, ts)
            ts, gp, ag = stats["tstate"], g_t, ag2
            sels.append(float(stats["n_selected"]))
        assert np.mean(warm[100:]) > 0.7          # steady state mostly warm
        assert max(sels) < 2 * k                  # no cohort blow-ups
        assert abs(np.mean(sels[100:]) - k) < 0.15 * k

    def test_bootstrap_round_equals_plain_packed(self):
        """Round 0 (init=0) must take the bootstrap branch == the
        non-warm packed path, bit-exact."""
        tree = transformer_tree()
        g, gp, age = tie_free_state(tree)
        lay = packing.PackedLayout.from_tree(g)
        mk = lambda warm: make_engine("fairk", "packed", layout=lay,
                                      rho=0.1, warm_start=warm)
        bufs = (lay.pack(g), lay.pack(gp), lay.pack_age(age))
        g1, a1, s1 = mk(True).select_and_merge(
            *bufs, tstate=packing.init_threshold_state())
        g2, a2, _ = mk(False).select_and_merge(*bufs)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        assert float(s1["tstate"]["init"]) == 1.0

    def test_threshold_state_vec_round_trip(self):
        ts = packing.init_threshold_state()
        ts["theta_m"] = jnp.float32(1.5)
        ts["n_sel"] = jnp.float32(42.0)
        back = packing.threshold_state_from_vec(
            packing.threshold_state_to_vec(ts))
        for f in packing.THRESHOLD_STATE_FIELDS:
            assert float(back[f]) == float(ts[f])


# ---------------------------------------------------------------------------
# pad-aware kernel
# ---------------------------------------------------------------------------

class TestPadAwareKernel:
    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    def test_pads_never_select_and_sentinel_survives(self, mode):
        rng = np.random.default_rng(7)
        d = 1024
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        gp = jnp.asarray(rng.normal(size=d).astype("f4"))
        age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
        pad = np.zeros(d, bool)
        pad[100:356] = True                      # interior pad block
        g = g.at[100:356].set(0.0)
        age = age.at[100:356].set(packing.PAD_AGE)
        # theta_a = -inf-like low would select everything valid; pads must
        # still refuse
        g_t, age_next = ops.fairk_update(g, gp, age, jnp.float32(0.05),
                                         jnp.float32(0.0), mode=mode,
                                         block_size=256)
        assert (np.asarray(age_next)[pad] == packing.PAD_AGE).all()
        np.testing.assert_array_equal(np.asarray(g_t)[pad],
                                      np.asarray(gp)[pad])
        assert (np.asarray(age_next)[~pad] == 0).all()   # all valid selected


# ---------------------------------------------------------------------------
# block-AoU clip in the FL-OAC step (satellite bugfix)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fl_oac_age_clipped_at_cap():
    """make_fl_oac_step must clip the block AoU at AGE_CAP (int8-safety
    invariant, DESIGN.md §5) — seeded at the cap, one round must not
    exceed it."""
    from repro.configs import get_config
    from repro.core.engine import AGE_CAP
    from repro.data.tokens import lm_batch
    from repro.launch.steps import make_fl_oac_step
    from repro.models import transformer as tr
    from jax.flatten_util import ravel_pytree

    mesh = make_mesh((1,), ("data",))
    cfg = get_config("mamba2-370m", reduced_variant=True)
    b = make_fl_oac_step(cfg, mesh, seq_len=32, rho=0.05)
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    w, _ = ravel_pytree(params)
    d, nb = b.meta["d"], b.meta["blocks"]
    g_prev = jnp.zeros((d,), jnp.float32)
    age = jnp.full((nb,), AGE_CAP, jnp.float32)   # already at the cap
    toks, labels = lm_batch(0, 1, 32, cfg.vocab)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    with mesh:
        fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                     out_shardings=b.out_shardings)
        _, _, age_next, _ = fn(w, g_prev, age, batch,
                               jnp.asarray(0, jnp.int32))
    assert float(jnp.max(age_next)) <= AGE_CAP
    assert float(jnp.min(age_next)) == 0.0        # selected blocks reset
