"""Persisted packed server state (launch.steps, DESIGN.md §10).

The server state of the big-model trainer is now the lane-aligned flat
buffers themselves — g_prev bf16 / age int8 (PAD_AGE sentinel in the lane
pads) / optional EF residual f32 — carried across steps.  These tests pin:

* ``server_layout`` (built outside shard_map from abstract local shapes)
  matches the layout ``PackedLayout.from_tree(local_grads)`` builds inside;
* ``init_server_state`` / ``abstract_server_state`` agree with the input
  specs ``make_train_step`` expects, for all (packed, error_feedback)
  flavours;
* two real steps execute with finite loss, budget-tracking selection, the
  pad sentinel intact, and (EF) a live residual buffer.

The zero-re-pack-per-round structural claim is asserted by
``benchmarks/packed_bench.py --smoke`` (trace-time pack/unpack counters);
multi-device execution is covered by tests/test_sharded.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import packing
from repro.launch import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.launch.steps import (OacServerConfig, abstract_params,
                                abstract_server_state, init_server_state,
                                make_train_step, server_layout)


class _FakeMesh:
    """Just enough mesh for the static local-shape math."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def test_server_layout_local_shapes():
    """The layout built from (params_abs, p_specs, mesh) must describe the
    per-shard leaves — dims sharded by a spec axis divide by its size."""
    from jax.sharding import PartitionSpec as P
    mesh = _FakeMesh({"data": 2, "model": 4})
    params = [jax.ShapeDtypeStruct((16, 8), jnp.float32),
              jax.ShapeDtypeStruct((100,), jnp.float32)]
    specs = [P("model", ("data",)), P()]
    lay = server_layout(params, specs, mesh)
    assert [e.shape for e in lay.table] == [(4, 4), (100,)]
    assert lay.d_valid == 16 + 100
    assert lay.d_packed % packing.LANE == 0


@pytest.mark.parametrize("ef,async_agg", [(False, False), (True, False),
                                          (False, True), (True, True)])
def test_init_matches_abstract_and_specs(ef, async_agg):
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    oac = OacServerConfig(error_feedback=ef, async_agg=async_agg)
    params_abs = abstract_params(cfg)
    p_specs = shlib.param_pspecs(params_abs, cfg, mesh)
    srv_abs = abstract_server_state(params_abs, mesh=mesh, p_specs=p_specs,
                                    oac=oac)
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_abs)
    srv = init_server_state(params, mesh=mesh, cfg=cfg, oac=oac)
    want = {"g", "age", "theta"}
    if ef:
        want |= {"res"}
    if async_agg:
        want |= {"shadow", "pending"}
    assert set(srv) == set(srv_abs) == want
    for k in srv:
        assert srv[k].shape == srv_abs[k].shape, k
        assert srv[k].dtype == srv_abs[k].dtype, k
    # age init: zeros on valid coords, PAD_AGE sentinel in the lane pads
    lay = server_layout(params_abs, p_specs, mesh)
    valid = np.asarray(lay.valid_mask())
    ages = np.asarray(srv["age"])
    assert (ages[valid] == 0).all() and (ages[~valid] == packing.PAD_AGE).all()
    if async_agg:
        # the double-buffer lane starts cold
        assert float(jnp.abs(srv["shadow"].astype(jnp.float32)).sum()) == 0.0
        assert float(jnp.abs(srv["pending"].astype(jnp.float32)).sum()) == 0.0


def test_packed_init_requires_mesh_and_cfg():
    params = {"w": jnp.zeros((8,), jnp.float32)}
    with pytest.raises(ValueError):
        init_server_state(params)                  # packed default needs mesh
    srv = init_server_state(params, oac=OacServerConfig(packed=False))
    assert srv["g"]["w"].shape == (8,)             # per-leaf tree flavour


def test_per_leaf_rejects_error_feedback():
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        make_train_step(cfg, InputShape("t", 64, 2, "train"), mesh,
                        oac=OacServerConfig(packed=False,
                                            error_feedback=True))


def test_async_validation():
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = InputShape("t", 64, 2, "train")
    with pytest.raises(ValueError, match="packed"):
        make_train_step(cfg, shape, mesh,
                        oac=OacServerConfig(packed=False, async_agg=True))
    with pytest.raises(ValueError, match="straggler_frac"):
        make_train_step(cfg, shape, mesh,
                        oac=OacServerConfig(async_agg=True,
                                            straggler_frac=1.5))
    with pytest.raises(ValueError, match="straggler_lag"):
        make_train_step(cfg, shape, mesh,
                        oac=OacServerConfig(async_agg=True,
                                            straggler_lag=0))


# ---------------------------------------------------------------------------
# checkpoint compatibility across the async field-set change (satellite)
# ---------------------------------------------------------------------------

class TestCheckpointMigration:
    def _states(self):
        from repro import checkpoint
        d = 512
        sync = {"g": jnp.ones((d,), jnp.bfloat16),
                "age": jnp.ones((d,), jnp.int8),
                "theta": jnp.ones((packing.THRESHOLD_STATE_SIZE,),
                                  jnp.float32)}
        async_like = dict(sync,
                          shadow=jnp.zeros((d,), jnp.bfloat16),
                          pending=jnp.zeros((d,), jnp.bfloat16))
        return checkpoint, sync, async_like

    def test_migrates_pre_async_checkpoint_to_cold_buffers(self, tmp_path):
        """A synchronous checkpoint resumed under --async-agg gains cold
        (zero) shadow/pending buffers — exact, since zeros ARE the async
        round-0 contents — and survives the save/restore round trip."""
        checkpoint, sync, async_like = self._states()
        path = checkpoint.save_server_state(str(tmp_path / "s.npz"), sync)
        srv_np, _ = checkpoint.restore_server_state(path)
        out = checkpoint.migrate_server_state(srv_np, like=async_like)
        assert set(out) == set(async_like)
        for name in checkpoint.ASYNC_FIELDS:
            assert out[name].shape == async_like[name].shape
            assert jnp.asarray(out[name]).dtype == jnp.bfloat16
            assert float(jnp.abs(jnp.asarray(out[name], jnp.float32)
                                 ).sum()) == 0.0
        # the carried fields pass through untouched
        np.testing.assert_array_equal(np.asarray(out["age"]),
                                      np.asarray(sync["age"]))

    def test_identity_when_field_sets_match(self):
        checkpoint, sync, async_like = self._states()
        out = checkpoint.migrate_server_state(dict(async_like),
                                              like=async_like)
        assert set(out) == set(async_like)

    def test_rejects_async_checkpoint_on_sync_config(self):
        """Dropping a pending merge on the floor would lose one round of
        gradient — the async -> sync direction must REJECT, naming the
        unexpected fields."""
        checkpoint, sync, async_like = self._states()
        with pytest.raises(ValueError, match="pending"):
            checkpoint.migrate_server_state(dict(async_like), like=sync)

    def test_rejects_non_async_field_mismatch(self):
        """Only the async double-buffer lane is synthesizable: a missing
        EF residual (different --ef flag) still errors."""
        checkpoint, sync, async_like = self._states()
        like = dict(async_like, res=jnp.zeros((512,), jnp.float32))
        with pytest.raises(ValueError, match="res"):
            checkpoint.migrate_server_state(sync, like=like)


class TestCheckpointChecksums:
    """Content checksums on the packed server checkpoints (satellite):
    save records a CRC per stored array, restore verifies it, and a
    corrupt newest checkpoint makes --resume fall back to the previous
    one instead of resuming from rotted buffers."""

    def _save(self, tmp_path, step, seed=0):
        from repro import checkpoint
        rng = np.random.default_rng(seed)
        d = 512
        srv = {"g": jnp.asarray(rng.normal(size=d).astype("f4")
                                ).astype(jnp.bfloat16),
               "age": jnp.ones((d,), jnp.int8),
               "theta": jnp.ones((packing.THRESHOLD_STATE_SIZE,),
                                 jnp.float32)}
        path = checkpoint.save_server_state(str(tmp_path), srv, step=step)
        return checkpoint, srv, path

    def test_roundtrip_verifies(self, tmp_path):
        checkpoint, srv, path = self._save(tmp_path, 1)
        back, _ = checkpoint.restore_server_state(path)
        np.testing.assert_array_equal(
            np.asarray(back["g"], np.float32),
            np.asarray(srv["g"], np.float32))

    def test_corruption_raises_corrupt_error(self, tmp_path):
        checkpoint, _, path = self._save(tmp_path, 1)
        data = dict(np.load(path))
        g = data["g"].copy()
        g[17] ^= 0xFF                            # single-bit-ish flip
        data["g"] = g
        np.savez(path, **data)
        with pytest.raises(checkpoint.CorruptCheckpointError,
                           match="checksum"):
            checkpoint.restore_server_state(path)

    def test_pre_checksum_checkpoint_loads(self, tmp_path):
        import json
        checkpoint, _, path = self._save(tmp_path, 1)
        data = dict(np.load(path))
        meta = json.loads(str(data["__server_meta__"][()]))
        meta.pop("checksums")                    # a pre-checksum save
        data["__server_meta__"] = np.asarray(json.dumps(meta))
        np.savez(path, **data)
        back, _ = checkpoint.restore_server_state(path)
        assert set(back) == {"g", "age", "theta"}

    def test_server_steps_newest_first(self, tmp_path):
        checkpoint, _, _ = self._save(tmp_path, 3)
        self._save(tmp_path, 10)
        self._save(tmp_path, 7)
        assert checkpoint.server_steps(str(tmp_path)) == [10, 7, 3]
        assert checkpoint.latest_server_step(str(tmp_path)) == 10
        assert checkpoint.server_steps(str(tmp_path / "nope")) == []


@pytest.mark.slow
@pytest.mark.parametrize("ef", [False, True])
def test_two_steps_execute_with_persisted_buffers(ef):
    from repro.data.tokens import lm_batch
    from repro.models import transformer as tr
    from repro.optim import make_optimizer
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = InputShape("t", 64, 2, "train")
    oac = OacServerConfig(error_feedback=ef)
    bundle = make_train_step(cfg, shape, mesh, oac=oac)
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(bundle.meta["optimizer"], 3e-3)
    opt_state = opt.init(params)
    server = init_server_state(params, mesh=mesh, cfg=cfg, oac=oac)
    step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                   out_shardings=bundle.out_shardings,
                   donate_argnums=(0, 1, 2))
    nm = bundle.meta["n_micro"]
    with mesh:
        for t in range(2):
            toks, labels = lm_batch(t, 2, 64, cfg.vocab)
            batch = {"tokens": jnp.asarray(toks).reshape(nm, 2 // nm, 64),
                     "labels": jnp.asarray(labels).reshape(nm, 2 // nm, 64)}
            params, opt_state, server, loss = step(
                params, opt_state, server, batch, jnp.asarray(t, jnp.int32))
    assert np.isfinite(float(loss))
    ages = np.asarray(server["age"])
    valid = ages >= 0
    frac_fresh = (ages[valid] == 0).mean()
    assert 0.03 < frac_fresh < 0.3                 # rho = 0.1 target
    assert (ages[~valid] == packing.PAD_AGE).all()
    assert float(np.asarray(server["theta"])[4]) == 1.0   # init flag set
    if ef:
        assert float(jnp.abs(server["res"]).sum()) > 0.0


@pytest.mark.slow
def test_two_async_steps_execute_with_double_buffers():
    """--async-agg flavour: two real steps with the shadow/pending
    double-buffer live.  The refreshed ages restart at the straggler lag
    (never 0), both buffers carry mass after the first round, and the pad
    sentinel survives."""
    from repro.data.tokens import lm_batch
    from repro.models import transformer as tr
    from repro.optim import make_optimizer
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = InputShape("t", 64, 2, "train")
    oac = OacServerConfig(async_agg=True, straggler_frac=0.25,
                          straggler_lag=1)
    bundle = make_train_step(cfg, shape, mesh, oac=oac)
    assert bundle.meta["oac_async"]
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(bundle.meta["optimizer"], 3e-3)
    opt_state = opt.init(params)
    server = init_server_state(params, mesh=mesh, cfg=cfg, oac=oac)
    step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                   out_shardings=bundle.out_shardings,
                   donate_argnums=(0, 1, 2))
    nm = bundle.meta["n_micro"]
    with mesh:
        for t in range(2):
            toks, labels = lm_batch(t, 2, 64, cfg.vocab)
            batch = {"tokens": jnp.asarray(toks).reshape(nm, 2 // nm, 64),
                     "labels": jnp.asarray(labels).reshape(nm, 2 // nm, 64)}
            params, opt_state, server, loss = step(
                params, opt_state, server, batch, jnp.asarray(t, jnp.int32))
    assert np.isfinite(float(loss))
    ages = np.asarray(server["age"])
    valid = ages >= 0
    # async age bookkeeping: refreshed coordinates restart at the lag —
    # nothing can sit at age 0
    assert (ages[valid] == 0).sum() == 0
    frac_lagged = (ages[valid] == oac.straggler_lag).mean()
    assert 0.03 < frac_lagged < 0.3                # rho = 0.1 target
    assert (ages[~valid] == packing.PAD_AGE).all()
    # both halves of the double buffer carry mass after round 1
    assert float(jnp.abs(server["pending"].astype(jnp.float32)).sum()) > 0.0
    assert float(jnp.abs(server["shadow"].astype(jnp.float32)).sum()) > 0.0
