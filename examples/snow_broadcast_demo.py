#!/usr/bin/env python
"""The Snow data plane: tree / two-tree collectives as ppermute
schedules, plus the checkpoint-distribution cost model.

Must run with >1 XLA host device; re-execs itself with
XLA_FLAGS=--xla_force_host_platform_device_count=8 if needed."""
import functools
import os
import subprocess
import sys

if os.environ.get("XLA_FLAGS", "").find("device_count") < 0:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.exit(subprocess.run([sys.executable, __file__], env=env).returncode)

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.collectives.schedule import DCN, best_broadcast
from repro.collectives.tree_collectives import (snow_allreduce,
                                                snow_broadcast,
                                                two_tree_broadcast)

# Auto axes: plain indexing of the shard_map output (``out[root]``)
# needs no out_sharding, as it would on an Explicit mesh
mesh = jax.make_mesh((8,), ("hosts",), axis_types=(AxisType.Auto,))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)


def run(fn):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("hosts"),
                       out_specs=P("hosts"), check_vma=False)
    def body(xx):
        return fn(xx[0])[None]
    return body(x)


print("per-host values:", x[:, 0].tolist())
out = run(lambda v: snow_broadcast(v, "hosts", axis_size=8, root=3, k=4))
print("snow_broadcast(root=3):", out[:, 0].tolist())
out = run(lambda v: two_tree_broadcast(v, "hosts", axis_size=8, root=3, k=4))
print("two_tree_broadcast    :", out[:, 0].tolist())
out = run(lambda v: snow_allreduce(v, "hosts", axis_size=8, root=0, k=2))
print("snow_allreduce (sum)  :", out[:, 0].tolist())

print("\ncheckpoint fan-out of a 144 GB model over 512 DCN hosts:")
print(best_broadcast(int(144e9), 512, 4, DCN))
