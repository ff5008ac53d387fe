"""Plain FAIR-k server rounds and AdamW: the reference for the training step.

One round, as the FAIR-k paper states it with the server options a cell
turns on (error feedback, the sanitize mask, asynchronous double-buffered
rounds with a straggler share):

- the fresh aggregate is the mean gradient over the round's microbatches;
- async: a fixed share of coordinates (a Knuth hash of the coordinate's
  index in the lane-aligned flattening of the parameter tree) arrives one
  round late, through the shadow buffer;
- error feedback folds the residual into the score;
- round 0 has no history and refreshes every coordinate; later rounds
  select the k_M largest |score| (exact order statistic, by bisection on
  the float bits) and then the k - k_M oldest of the rest, ties broken by
  a uniform draw of this module's own;
- the merged gradient takes the fresh score where selected and the stale
  stored value elsewhere; ages reset (to the delivery lag under async) or
  grow, capped at 120; g_prev, shadow and pending are stored in bfloat16,
  as the server persists them;
- AdamW (b1 0.9, b2 0.999, eps 1e-8, no weight decay) steps on the merged
  gradient, or under async on the previous round's;
- the adaptive k_M controller keeps an EMA (decay 0.9, seeded by the first
  round's) of the post-round age histogram, counts rounds, and may move
  the split only every ``CTRL_PERIOD`` rounds: the checked rounds end
  before its first move, so the move itself (a Lemma-1 target) is not
  modelled and the split stays at its configured 0.75.

``Server.selection`` plants a fault for the comparison's calibration:
``random`` refreshes a uniform draw of about k coordinates, ``none``
refreshes nothing after round 0.  Imports nothing of the program."""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import server_state

LANE = server_state.LANE
AGE_CAP = 120.0
B1, B2, EPS = 0.9, 0.999, 1e-8
CTRL_EMA, CTRL_PERIOD = 0.9, 5
SELECTIONS = ("exact", "random", "none")


@dataclasses.dataclass(frozen=True)
class Server:
    rho: float = 0.1
    k_m_frac: float = 0.75
    error_feedback: bool = False
    sanitize: bool = False
    async_agg: bool = False
    straggler_frac: float = 0.25
    straggler_lag: int = 1
    adaptive_km: bool = False
    selection: str = "exact"

    @property
    def lag(self) -> int:
        return self.straggler_lag if self.async_agg else 0


def parse_server(flags: List[str]) -> Server:
    """The server a cell's launcher flags describe."""
    kw, it = {}, iter(flags)
    names = {"--ef": "error_feedback", "--sanitize": "sanitize",
             "--async-agg": "async_agg", "--adaptive-km": "adaptive_km"}
    for f in it:
        if f in names:
            kw[names[f]] = True
        elif f == "--rho":
            kw["rho"] = float(next(it))
        elif f == "--straggler-frac":
            kw["straggler_frac"] = float(next(it))
        else:
            raise ValueError(f"the reference does not model {f}")
    return Server(**kw)


def straggler_masks(params, frac: float):
    """True where a coordinate's uplink is one round late."""
    out, offset = [], 0
    leaves, treedef = jax.tree_util.tree_flatten(params)
    for leaf in leaves:
        n = int(np.prod(leaf.shape))
        idx = jax.lax.iota(jnp.uint32, n) + jnp.uint32(offset)
        h = idx * jnp.uint32(2654435761) % jnp.uint32(1 << 24)
        out.append((h.astype(jnp.float32) / float(1 << 24) < frac)
                   .reshape(leaf.shape))
        offset += -(-n // LANE) * LANE
    return jax.tree_util.tree_unflatten(treedef, out)


def _kth_bits(leaves, k):
    """Bits of the k-th largest non-negative value across ``leaves``
    (negative entries never count)."""
    bits = [jax.lax.bitcast_convert_type(x, jnp.int32) for x in leaves]

    def count(th):
        return sum(jnp.sum(b >= th, dtype=jnp.uint32) for b in bits)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = count(mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 32, body,
                              (jnp.int32(0), jnp.int32(0x7F800001)))
    return lo


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _round(first: bool, srv: Server, k: int, k_m: int):
    """One server round as a pure function of trees."""
    f32 = jnp.float32
    tmap = jax.tree.map

    def fn(g, st, key):
        new = dict(st)
        if srv.async_agg:
            new["shadow"] = tmap(lambda a, s: jnp.where(s, a, 0.0).astype(
                jnp.bfloat16), g, st["strag"])
            g = tmap(lambda a, s, sh: jnp.where(s, 0.0, a) + sh.astype(f32),
                     g, st["strag"], st["shadow"])
        score = tmap(lambda a, r: a + r, g, st["res"]) if "res" in st else g
        ok = tmap(jnp.isfinite, score) if srv.sanitize else tmap(
            lambda s: jnp.ones(s.shape, bool), score)
        if first:
            mask = ok
        elif srv.selection == "none":
            mask = tmap(lambda o: jnp.zeros(o.shape, bool), ok)
        elif srv.selection == "random":
            leaves, treedef = jax.tree_util.tree_flatten(ok)
            d = sum(l.size for l in leaves)
            mask = jax.tree_util.tree_unflatten(treedef, [
                o & (jax.random.uniform(jax.random.fold_in(key, i), o.shape)
                     < k / d) for i, o in enumerate(leaves)])
        else:
            mag = tmap(lambda s, o: jnp.where(o, jnp.abs(s), -1.0), score, ok)
            tm = _kth_bits(jax.tree.leaves(mag), k_m)
            mask_m = tmap(lambda m_: _bits(m_) >= tm, mag)
            leaves, treedef = jax.tree_util.tree_flatten(st["age"])
            u = [jax.random.uniform(jax.random.fold_in(key, i), l.shape)
                 for i, l in enumerate(leaves)]
            u = jax.tree_util.tree_unflatten(treedef, u)
            key_a = tmap(lambda a, uu, mm, o: jnp.where(mm | ~o, -1.0,
                                                        a.astype(f32) + uu),
                         st["age"], u, mask_m, ok)
            ta = _kth_bits(jax.tree.leaves(key_a), k - k_m)
            mask = tmap(lambda mm, ka: mm | (_bits(ka) >= ta), mask_m, key_a)
        merged = tmap(lambda m_, s, gp: jnp.where(m_, s, gp.astype(f32)),
                      mask, score, st["g_prev"])
        new["g_prev"] = tmap(lambda x: x.astype(jnp.bfloat16), merged)
        lag = float(srv.lag)
        new["age"] = tmap(lambda m_, a: jnp.where(
            m_, lag, jnp.minimum(a.astype(f32) + 1.0, AGE_CAP)
        ).astype(jnp.int8), mask, st["age"])
        if "res" in st:
            new["res"] = tmap(lambda m_, s: jnp.where(m_, 0.0, s), mask, score)
        if srv.async_agg:
            out = tmap(lambda x: x.astype(f32), st["pending"])
            new["pending"] = new["g_prev"]
        else:
            out = merged
        return out, new
    return fn


def _adamw(lr: float):
    def fn(p, m, v, g, step):
        m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, v, g)
        bc1, bc2 = 1 - B1 ** step, 1 - B2 ** step
        p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / bc1) / (
            jnp.sqrt(v_ / bc2) + EPS), p, m, v)
        return p, m, v
    return fn


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def _round_grad(loss_fn: Callable):
    """Mean loss and gradient over a round's microbatches (one scan, each
    microbatch's forward recomputed in the backward)."""
    def fn(params, tokens, labels):
        def total(p):
            def body(acc, tl):
                return acc + jax.checkpoint(loss_fn)(p, *tl), None
            s, _ = jax.lax.scan(body, jnp.float32(0.0), (tokens, labels))
            return s / tokens.shape[0]
        return jax.value_and_grad(total)(params)
    return fn


def _controller(ctrl, age):
    """One round of the adaptive split's bookkeeping from the post-round
    ages: the histogram EMA, the seen flag and the round counter."""
    h = server_state.age_counts(jax.tree.leaves(age))
    seen = ctrl["init"] > 0
    tick = ctrl["tick"] + 1.0
    if seen and tick >= CTRL_PERIOD:
        raise NotImplementedError("the controller moves the split after "
                                  f"{CTRL_PERIOD} rounds; not modelled")
    return dict(ctrl, init=1.0, tick=tick,
                age_ema=CTRL_EMA * ctrl["age_ema"] + (1 - CTRL_EMA) * h
                if seen else h)


_PROGRAMS: Dict[tuple, tuple] = {}


def _programs(loss_fn: Callable, srv: Server, k: int, k_m: int, lr: float):
    """The jitted gradient, server rounds, AdamW and server reading, built
    once a process for each (loss, server, budget, learning rate)."""
    key = (loss_fn, srv, k, k_m, lr)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (
            jax.jit(_round_grad(loss_fn)),
            {f: jax.jit(_round(f, srv, k, k_m), donate_argnums=(0, 1))
             for f in (True, False)},
            jax.jit(_adamw(lr), donate_argnums=(1, 2, 3)),
            jax.jit(lambda st, c: server_state.reading(
                st["g_prev"], st["age"],
                {"merged": server_state.norms(jax.tree.leaves(st["g_prev"])),
                 **{b: server_state.norms(jax.tree.leaves(st[b]))
                    for b in server_state.BUFFERS if b in st}},
                srv.lag, c)))
    return _PROGRAMS[key]


def train(params, batches, loss_fn: Callable, srv: Server, *, lr: float,
          steps: int = 3, micro: Optional[int] = None, seed: int = 0):
    """``steps`` rounds from ``params`` on ``batches`` (each a dict of
    ``(n_micro, mb, L)`` tokens and labels).  ``micro`` keeps only the
    first ``micro`` microbatches of each round (a planted fault).  Returns
    the losses, the per-leaf norms of the first non-zero gradient AdamW
    received, the server's ``server_state.reading`` after each round, and
    the parameters after the rounds."""
    d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    k = int(round(srv.rho * d))
    k_m = int(round(srv.k_m_frac * k))
    grad_fn, rounds, adamw, read = _programs(loss_fn, srv, k, k_m, lr)
    zeros = lambda dt: jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)
    st = {"g_prev": zeros(jnp.bfloat16), "age": zeros(jnp.int8)}
    if srv.error_feedback:
        st["res"] = zeros(jnp.float32)
    if srv.async_agg:
        st |= {"shadow": zeros(jnp.bfloat16), "pending": zeros(jnp.bfloat16),
               "strag": straggler_masks(params, srv.straggler_frac)}
    ctrl = None
    if srv.adaptive_km:
        ctrl = {"k_m_frac": srv.k_m_frac, "prev_step": 0.0, "init": 0.0,
                "tick": 0.0,
                "age_ema": jnp.zeros((server_state.AGE_READ,), jnp.float32)}
    keys = server_state.leaf_keys(params)
    m, v = zeros(jnp.float32), zeros(jnp.float32)
    losses, first_grad, server = [], None, []
    key = jax.random.PRNGKey(seed)
    for t in range(steps):
        used = micro or batches[t]["tokens"].shape[0]
        lv, g = grad_fn(params, batches[t]["tokens"][:used],
                        batches[t]["labels"][:used])
        losses.append(float(lv))
        out, st = rounds[t == 0](g, st, jax.random.fold_in(key, t))
        if ctrl is not None:
            ctrl = _controller(ctrl, st["age"])
        server.append(server_state.to_host(read(st, ctrl), keys))
        if first_grad is None:
            norms = leaf_norms(out)
            if any(n > 0 for n in norms.values()):
                first_grad = norms
        params, m, v = adamw(params, m, v, out, jnp.float32(t + 1))
    return {"losses": losses, "first_grad": first_grad, "server": server,
            "params": params}
