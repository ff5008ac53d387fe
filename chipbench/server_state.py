"""What the FAIR-k server holds after a round, read alike on both sides.

The program persists its server state as lane-aligned flat buffers (one
chip: the parameter leaves in flatten order, each padded to a multiple of
``LANE`` coordinates); the reference keeps trees.  ``flat_norms`` takes
per-leaf norms of a flat buffer by that layout, and ``reading`` takes the
same numbers from either:

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
        ema = fold_ages(ctrl["age_ema"])
        out["ctrl"] = {**{k: jnp.asarray(ctrl[k], jnp.float32)
                          for k in CTRL_SCALARS},
                       "age_pmf": ema / jnp.maximum(jnp.sum(ema), 1.0)}
    return out


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
