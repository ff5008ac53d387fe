"""Steps broken underneath a whole run, for the tests that see
``correct`` come out false: ``BROKEN`` names each, so that code run in a
subprocess can pick one by name."""

from __future__ import annotations

from chipbench import harness


class Unchanged(harness.Program):
    """A step that returns its state unchanged."""

    def __call__(self, state, batch, seed):
        import jax
        import jax.numpy as jnp
        _, loss = super().__call__(jax.tree.map(jnp.copy, state), batch,
                                   seed)
        return state, loss


class HalfBatch(harness.Program):
    """A step that leaves out half of the batch and takes the mean over
    the rest: the program's own step, built for half the microbatches."""

    def __init__(self, cell):
        half = dict(cell.traffic, batch=cell.traffic["batch"] // 2)
        super().__init__(harness.Cell(**dict(vars(cell), traffic=half)))
        self.full = cell.traffic["batch"] // self.micro_batch

    def pool(self, seed, vocab, seq_len):
        from chipbench import feed
        return feed.batch_pool(seed, harness.POOL, self.full,
                               self.micro_batch, seq_len, vocab,
                               self.in_sh[3])

    def _half(self, batch):
        return {k: v[:self.n_micro] for k, v in batch.items()}

    def compile(self, state, batch):
        return super().compile(state, self._half(batch))

    def __call__(self, state, batch, seed):
        return super().__call__(state, self._half(batch), seed)


class NoExchange(harness.Program):
    """Several devices with nothing summed between them: the model's loss
    taken on each device over its own rows, on its own whole copy of the
    weights; in the backward pass each device keeps, of the gradient its
    rows give, the shard it holds."""

    def compile(self, state, batch):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models import transformer as tr
        plain, mesh = tr.loss_fn, self.mesh
        n = mesh.shape["data"]
        dims = [next((i for i, e in enumerate(sh.spec) if e is not None
                      and "data" in (e if isinstance(e, tuple) else (e,))),
                     None) for sh in jax.tree.leaves(self.in_sh[0])]

        @jax.custom_vjp
        def spread(p):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), p)

        def own(c, d):
            if d is None:
                return c[0]
            k = c.shape[d + 1] // n
            return jnp.concatenate([jax.lax.slice_in_dim(
                c[i], i * k, (i + 1) * k, axis=d) for i in range(n)], d)

        def bwd(_, ct):
            leaves, treedef = jax.tree_util.tree_flatten(ct)
            return (jax.tree_util.tree_unflatten(
                treedef, [own(c, d) for c, d in zip(leaves, dims)]),)
        spread.defvjp(lambda p: (spread(p), None), bwd)

        def local(params, cfg, mbatch, residual_fn=None):
            return jax.shard_map(
                lambda p, b: plain(jax.tree.map(lambda x: x[0], p), cfg, b),
                mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=P(), check_vma=False)(spread(params), mbatch)
        tr.loss_fn = local
        try:
            return super().compile(state, batch)
        finally:
            tr.loss_fn = plain


BROKEN = {"sound": harness.Program, "unchanged": Unchanged,
          "half_batch": HalfBatch, "no_exchange": NoExchange}
