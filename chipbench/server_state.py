"""What the FAIR-k server holds after a round, read alike on both sides.

The program persists its server state as lane-aligned flat buffers: on
each device, the parameter leaves' local shards in flatten order, each
padded to a multiple of ``LANE`` coordinates, and over several devices
those local buffers one after another.  The reference keeps trees.
``flat_norms`` takes per-leaf norms of a one-chip flat buffer by that
layout, ``sharded_reading`` reads a sharded server device by device with
the same rule applied to each leaf's shard shape, and ``reading`` takes
the same numbers from trees or one-chip buffers:

- ``n_sel``: coordinates refreshed this round (their age is the delivery
  lag: 0, or the straggler lag under async; every other age is larger);
- ``energy``: the sum of squares of the stored merged gradient over them;
- ``merged`` (and ``res``, ``shadow``, ``pending`` where the server keeps
  them): per-leaf norms of the stored buffers;
- ``age_counts``: how many coordinates hold age 0, 1, ... ``AGE_READ - 2``,
  and, last, how many hold any larger age;
- ``ctrl``: the adaptive split's state (split, damped step, seen flag,
  round counter) and its age EMA folded to the ``age_counts`` bins, as a
  distribution."""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

LANE = 256
AGE_READ = 9            # ages 0..7 one by one, then the rest
CTRL_SCALARS = ("k_m_frac", "prev_step", "init", "tick")
BUFFERS = ("res", "shadow", "pending")


def leaf_keys(abstract) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(abstract)[0]]


def flat_norms(flat, abstract):
    """Per-leaf norms of a one-chip flat server buffer: a leaf's lanes,
    whose pad coordinates hold 0.  Plain slices, so that no reshaped copy
    of the buffer is made."""
    out, at = [], 0
    for leaf in jax.tree.leaves(abstract):
        n = -(-int(np.prod(leaf.shape)) // LANE) * LANE
        x = jax.lax.slice(flat, (at,), (at + n,)).astype(jnp.float32)
        out.append(jnp.sqrt(jnp.sum(jnp.square(x))))
        at += n
    if at != flat.shape[0]:
        raise ValueError(f"a buffer of {flat.shape[0]} coordinates is not "
                         f"the one-chip layout of {at}")
    return out


def sharded_reading(buffers: Dict[str, Any], lag: int, local_shapes,
                    global_shapes, mesh, spec, ctrl=None) -> Dict[str, Any]:
    """``reading`` of a server whose flat buffers (``g``, ``age`` and any of
    ``BUFFERS``, each laid out as ``spec`` over ``mesh``) hold one local
    buffer a device.  Each device sums its own leaves' lanes, by the
    one-chip rule applied to ``local_shapes``; a leaf held whole or in part
    on several devices (a replicated norm or bias) counts once, as in the
    reference: its sums are divided by the number of devices that hold
    each coordinate.  Counts are summed in int32.  Traceable."""
    from jax.sharding import PartitionSpec as P
    names = ["g", "age"] + [b for b in BUFFERS if b in buffers]
    sizes = [int(np.prod(s)) for s in local_shapes]
    offsets = np.cumsum([0] + [-(-n // LANE) * LANE for n in sizes])
    n_dev = mesh.size
    rep = np.array([n_dev * n / int(np.prod(s))
                    for n, s in zip(sizes, global_shapes)], np.float32)

    def local(*flats):
        bufs = dict(zip(names, flats))
        if bufs["g"].shape[0] != offsets[-1]:
            raise ValueError(f"a local buffer of {bufs['g'].shape[0]} "
                             f"coordinates is not the layout of {offsets[-1]}")
        out = {"n_sel": [], "energy": [], "age_counts": [],
               **{b: [] for b in names if b != "age"}}
        for at, n in zip(offsets, sizes):
            cut = {b: jax.lax.slice(x, (int(at),), (int(at) + n,))
                   for b, x in bufs.items()}
            a = cut["age"]
            sel = a == lag
            g2 = jnp.square(cut["g"].astype(jnp.float32))
            out["n_sel"].append(jnp.sum(sel, dtype=jnp.int32))
            out["energy"].append(jnp.sum(jnp.where(sel, g2, 0.0)))
            out["age_counts"].append(jnp.stack(
                [jnp.sum(a == v, dtype=jnp.int32)
                 for v in range(AGE_READ - 1)]
                + [jnp.sum(a >= AGE_READ - 1, dtype=jnp.int32)]))
            for b in names:
                if b != "age":
                    out[b].append(jnp.sum(jnp.square(
                        cut[b].astype(jnp.float32))))
        return {k: jnp.stack(v)[None] for k, v in out.items()}

    per_dev = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * len(names),
                            out_specs=P(spec[0]))(
        *(buffers[b] for b in names))
    w = jnp.asarray(1.0 / rep)
    total = {k: jnp.sum(v.astype(jnp.float32), 0) for k, v in per_dev.items()}
    out = {"n_sel": jnp.sum(total["n_sel"] * w),
           "energy": jnp.sum(total["energy"] * w),
           "age_counts": jnp.sum(total["age_counts"] * w[:, None], 0)}
    for b in names:
        if b != "age":
            norms = jnp.sqrt(total[b] * w)
            out["merged" if b == "g" else b] = [norms[i]
                                                for i in range(len(sizes))]
    if ctrl is not None:
        out["ctrl"] = ctrl_reading(ctrl)
    return out


def fold_ages(hist):
    """A histogram over unit age bins -> the ``age_counts`` bins."""
    hist = jnp.asarray(hist, jnp.float32)
    return jnp.concatenate([hist[:AGE_READ - 1],
                            jnp.sum(hist[AGE_READ - 1:])[None]])


def age_counts(age_leaves):
    counts = [sum(jnp.sum(a == v, dtype=jnp.float32) for a in age_leaves)
              for v in range(AGE_READ - 1)]
    rest = sum(jnp.sum(a >= AGE_READ - 1, dtype=jnp.float32)
               for a in age_leaves)
    return jnp.stack(counts + [rest])


def norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves]


def reading(g, age, leaf_norms: Dict[str, Any], lag: int, ctrl=None
            ) -> Dict[str, Any]:
    """The numbers above from the stored merged gradient ``g`` and ages
    ``age`` (trees, or flat buffers whose pad coordinates hold g 0 and a
    negative age), the per-leaf norms of ``g`` (``merged``) and of any of
    ``BUFFERS``, and the controller's scalars and age EMA.  Traceable:
    call it inside ``jax.jit``."""
    g, age = jax.tree.leaves(g), jax.tree.leaves(age)
    sel = [a == lag for a in age]
    out = {
        "n_sel": sum(jnp.sum(s, dtype=jnp.float32) for s in sel),
        "energy": sum(jnp.sum(jnp.where(s, jnp.square(x.astype(jnp.float32)),
                                        0.0)) for s, x in zip(sel, g)),
        "age_counts": age_counts(age),
        **leaf_norms,
    }
    if ctrl is not None:
        out["ctrl"] = ctrl_reading(ctrl)
    return out


def ctrl_reading(ctrl) -> Dict[str, Any]:
    """The controller's scalars and its age EMA as a distribution."""
    ema = fold_ages(ctrl["age_ema"])
    return {**{k: jnp.asarray(ctrl[k], jnp.float32) for k in CTRL_SCALARS},
            "age_pmf": ema / jnp.maximum(jnp.sum(ema), 1.0)}


def to_host(r: Dict[str, Any], keys: List[str]) -> Dict[str, Any]:
    """Device readings -> plain numbers, per-leaf lists keyed by leaf."""
    out = {}
    for name, v in r.items():
        if name == "ctrl":
            out[name] = {k: (np.asarray(x, np.float64).tolist())
                         for k, x in v.items()}
        elif isinstance(v, list):
            out[name] = dict(zip(keys, (float(x) for x in v)))
        else:
            out[name] = np.asarray(v, np.float64).tolist()
    return out
