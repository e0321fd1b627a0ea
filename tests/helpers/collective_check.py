"""Subprocess body for test_collectives: equivalence of the Snow
ppermute collectives against psum/broadcast semantics on 8 devices."""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.collectives.tree_collectives import (snow_allreduce,
                                                snow_broadcast,
                                                snow_reduce,
                                                two_tree_broadcast)

# Auto axes: plain indexing of the shard_map output (``out[root]``)
# needs no out_sharding, as it would on an Explicit mesh
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)


def run(fn):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                       out_specs=P("x"), check_vma=False)
    def body(xx):
        return fn(xx[0])[None]
    return body(x)


for root in (0, 3, 7):
    for k in (2, 4):
        out = run(lambda v: snow_broadcast(v, "x", axis_size=8, root=root, k=k))
        assert jnp.allclose(out, jnp.broadcast_to(x[root], x.shape)), (root, k)

        out = run(lambda v: two_tree_broadcast(v, "x", axis_size=8, root=root, k=k))
        assert jnp.allclose(out, jnp.broadcast_to(x[root], x.shape)), (root, k)

        out = run(lambda v: snow_allreduce(v, "x", axis_size=8, root=root, k=k))
        assert jnp.allclose(out, jnp.broadcast_to(x.sum(0), x.shape)), (root, k)

        out = run(lambda v: snow_reduce(v, "x", axis_size=8, root=root, k=k))
        assert jnp.allclose(out[root], x.sum(0)), (root, k)

# odd payload through the two-tree splitter
out = run(lambda v: two_tree_broadcast(v[:5], "x", axis_size=8, root=1, k=4))
assert jnp.allclose(out, jnp.broadcast_to(x[1, :5], (8, 5)))

# checkpoint distribution fan-out applies the same schedule tree-wide
from repro.checkpoint.distribution import distribute_params, plan_for
params = {"w": x, "b": x[:, 0]}
dist = distribute_params(params, mesh, "x", root=2, k=2)
plan = plan_for(params, 8)
assert plan.payload_bytes == x.size * 4 + 8 * 4
assert plan.est_time_s > 0

print("ALL-OK")
