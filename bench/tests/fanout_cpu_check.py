"""The fan-out cell at a tiny size on four virtual CPU devices: a sound
run, the float8 control and the faults its check must catch, one of them
in the rollouts before the last.  Run as a script (the device count is
fixed before JAX starts); prints one JSON object: for each case, whether
the run came out correct and the elements that differed."""
import json
import os
import sys
import tempfile
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tinybench  # noqa: E402


def main() -> None:
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import distribution
    from snowbench import harness, rollout
    from snowbench.manifest import Bench

    bench = Bench.load(tinybench.make(Path(tempfile.mkdtemp())))
    devices = jax.devices()[:4]
    sound = distribution.distribute_params
    shards = {}

    def per_chip(fn):
        """``fn(x, chip)`` on each chip's own buffer of a leaf."""
        mesh = jax.make_mesh((4,), (rollout.AXIS,), devices=devices,
                             axis_types=(jax.sharding.AxisType.Auto,))
        return jax.shard_map(
            lambda x: fn(x, jax.lax.axis_index(rollout.AXIS)), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec(),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False)

    def no_exchange(params, mesh, axis, root=0, k=2):
        return params

    def half_leaves(params, mesh, axis, root=0, k=2):
        # one leaf of every two stays where it was
        shards["n"] = shards.get("n", 0) + 1
        return params if shards["n"] % 2 else sound(params, mesh, axis,
                                                    root=root, k=k)

    def altered(params, mesh, axis, root=0, k=2):
        out = sound(params, mesh, axis, root=root, k=k)
        flip = per_chip(lambda x, chip: jnp.where(
            chip == 2, -x, x))
        return flip(out)

    cases = {"sound": sound, "no_exchange": no_exchange,
             "half_leaves": half_leaves, "altered": altered}
    out = {}
    for name, fn in cases.items():
        distribution.distribute_params = fn
        shards.clear()
        res = harness.run(bench, "tiny-fanout", 2**31 + 77, 0.3, False,
                          devices)
        out[name] = {"correct": res["correct"],
                     "elements_off": res["checks"].get(
                         "elements_off", {}).get("value"),
                     "metrics": sorted(res["metrics"])}
    distribution.distribute_params = sound
    gen = harness.generator(bench.traffic("ckpt-rollout"))(
        bench.config("rwkv6-tiny"), bench.traffic("ckpt-rollout"), 5,
        devices)
    gen.setup()
    gen.step(0)
    gen.release()
    sys.path.insert(0, str(tinybench.BENCH))
    import calibrate

    out["control"] = calibrate.rollout_control(gen)

    # an answer altered in every rollout but the last: only the rollout
    # drawn from the seed can show it
    gen = harness.generator(bench.traffic("ckpt-rollout"))(
        bench.config("rwkv6-tiny"), bench.traffic("ckpt-rollout"), 11,
        devices)
    gen.setup()
    sound_rollout = gen.rollout
    gen.rollout = jax.jit(lambda x: altered(x, gen.mesh, rollout.AXIS,
                                            root=gen.reader, k=gen.cfg["k"]))
    for i in range(5):
        gen.step(i)
    gen.rollout = sound_rollout
    gen.step(5)
    gen.release()
    out["earlier_fault"] = {"drawn_last": gen.sample is gen.out,
                            **gen.check()[0]}
    out["traced"] = sorted(harness.run(bench, "tiny-fanout", 3, 0.3, True,
                                       devices))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
