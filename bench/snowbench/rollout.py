"""Rollout traffic: back-to-back checkpoint fan-outs across chips.

Each chip stands for one host.  Every chip starts with a parameter tree
of its own (made on the device from ``--seed`` in one jitted call, in
bfloat16), as each host holds its own copy before a restore; the reader
chip's tree is the checkpoint.  A rollout calls
``checkpoint.distribution.distribute_params`` under ``jax.jit`` once per
leaf, over a one-axis mesh of the cell's chips: the Coloring two-tree
schedule with the configuration's fan-out.  It ends when every chip
holds the reader's bytes of every leaf; the next rollout starts then.
(One jitted call over the whole tree builds a program of some 12,000
collective-permutes that takes minutes to compile.)

Work is counted in bytes received: (chips − 1) × the tree's bytes per
rollout.  The check regenerates the reader's bytes on every chip from
the seed and counts the elements that differ from them in two rollouts'
outputs: the last one, and one drawn from the seed among all the
window's rollouts (a reservoir of one, kept on the chips until the
check: one more tree's bytes a chip).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Tuple

import numpy as np

AXIS = "hosts"


def param_shapes(cfg: dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """The RWKV-6 (Finch) parameter tree at the configuration's sizes,
    leaf names as in the published checkpoints."""
    c, a = cfg["hidden_size"], cfg["attention_hidden_size"]
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    hs = cfg["head_size"]
    dm, dd = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
    vec = (1, 1, c)
    out = [("emb.weight", (v, c))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"blocks.{i}."
        if i == 0:
            out += [(p + "ln0.weight", (c,)), (p + "ln0.bias", (c,))]
        out += [(p + "ln1.weight", (c,)), (p + "ln1.bias", (c,)),
                (p + "ln2.weight", (c,)), (p + "ln2.bias", (c,))]
        out += [(p + f"att.time_maa_{x}", vec) for x in "xwkvrg"]
        out += [(p + "att.time_maa_w1", (c, 5 * dm)),
                (p + "att.time_maa_w2", (5, dm, c)),
                (p + "att.time_decay", (1, 1, a)),
                (p + "att.time_decay_w1", (c, dd)),
                (p + "att.time_decay_w2", (dd, a)),
                (p + "att.time_faaaa", (a // hs, hs))]
        out += [(p + f"att.{x}.weight", (a, c))
                for x in ("receptance", "key", "value", "gate")]
        out += [(p + "att.output.weight", (c, a)),
                (p + "att.ln_x.weight", (a,)), (p + "att.ln_x.bias", (a,)),
                (p + "ffn.time_maa_k", vec), (p + "ffn.time_maa_r", vec),
                (p + "ffn.key.weight", (f, c)),
                (p + "ffn.receptance.weight", (c, c)),
                (p + "ffn.value.weight", (c, f))]
    out += [("ln_out.weight", (c,)), ("ln_out.bias", (c,)),
            ("head.weight", (v, c))]
    return out


def tree_bytes(cfg: dict) -> int:
    return 2 * sum(int(np.prod(s)) for _, s in param_shapes(cfg))


def leaf(key, index: int, chip, shape):
    """Chip ``chip``'s bfloat16 leaf ``index``: threefry bits keyed by
    ``(seed → leaf → chip)``, kept to normal finite numbers of magnitude
    2**-15 to 2 (sign, 4 low exponent bits and the mantissa are random)."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.fold_in(key, index), chip)
    bits = jax.random.bits(k, shape, dtype=jnp.uint16)
    bits = (bits & jnp.uint16(0x87FF)) | jnp.uint16(0x3800)
    return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)


def by_shape(shapes) -> Dict[Tuple[int, ...], List[int]]:
    """Leaf indices grouped by shape: one vectorised draw per group keeps
    the generating and checking programs small."""
    groups: Dict[Tuple[int, ...], List[int]] = {}
    for i, (_, shape) in enumerate(shapes):
        groups.setdefault(tuple(shape), []).append(i)
    return groups


def stacked(key, indices: List[int], chip, shape):
    """``leaf`` for every index of one shape group, stacked."""
    import jax
    import jax.numpy as jnp

    return jax.vmap(lambda i: leaf(key, i, chip, shape))(
        jnp.asarray(indices, dtype=jnp.uint32))


def seed32(seed: int) -> int:
    return int(np.random.SeedSequence([int(seed) % 2**64]).generate_state(1)[0])


class Generator:
    unit = "rollout"
    e2e_name = "fanout_GBps"

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        import jax
        from jax.sharding import AxisType

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices = list(devices)
        self.mesh = jax.make_mesh((len(self.devices),), (AXIS,),
                                  axis_types=(AxisType.Auto,),
                                  devices=self.devices)
        self.shapes = param_shapes(cfg)
        self.reader = int(cfg["reader"])
        self.params = self.out = self.sample = None
        self.draw = np.random.default_rng(seed32(seed))
        self.failed: List[str] = []

    def _per_chip(self, body, in_specs, out_specs):
        import jax

        return jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def _key_data(self):
        """The seed's threefry key as data: an argument, not a constant,
        so one compiled program serves every seed."""
        import jax

        return jax.random.key_data(jax.random.key(seed32(self.seed)))

    def make_params(self):
        """Every chip's own tree, in one jitted call."""
        import jax
        from jax.sharding import PartitionSpec as P

        shapes = self.shapes

        def body(kd):
            key = jax.random.wrap_key_data(kd)
            chip = jax.lax.axis_index(AXIS)
            out = {}
            for shape, idx in by_shape(shapes).items():
                block = stacked(key, idx, chip, shape)
                out.update({shapes[i][0]: block[j]
                            for j, i in enumerate(idx)})
            return out

        return self._per_chip(body, (P(),), P())(self._key_data())

    def setup(self) -> None:
        import jax
        from repro.checkpoint import distribution

        self.params = self.make_params()
        mesh, reader, k = self.mesh, self.reader, int(self.cfg["k"])
        self.rollout = jax.jit(lambda x: distribution.distribute_params(
            x, mesh, AXIS, root=reader, k=k))
        jax.block_until_ready(self._rollout())

    def _rollout(self) -> dict:
        """One fan-out of the whole tree: one call per leaf, dispatched
        back to back as a restore streams tensors; every leaf shape is one
        compiled program."""
        return {name: self.rollout(x) for name, x in self.params.items()}

    def step(self, index: int) -> float:
        import jax

        self.out = None
        self.out = jax.block_until_ready(self._rollout())
        if self.draw.random() * (index + 1) < 1.0:
            self.sample = self.out  # each rollout kept with chance 1/(index+1)
        return float((len(self.devices) - 1) * tree_bytes(self.cfg))

    def e2e(self, work: float, seconds: float) -> Dict[str, float]:
        return {self.e2e_name: work / seconds / 1e9}

    @contextlib.contextmanager
    def spans(self):
        yield

    def release(self) -> None:
        """Free the input trees; the last rollout's output and the drawn
        one stay for the check."""
        self.params = None

    def elements_off(self, outs: List[dict]) -> np.ndarray:
        """Per rollout output in ``outs``, per chip, per group of leaves of
        one shape: how many elements differ from the reader's bytes
        regenerated from the seed."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        shapes, reader = self.shapes, self.reader

        def body(out, kd):
            key = jax.random.wrap_key_data(kd)
            counts = []
            for shape, idx in by_shape(shapes).items():
                want = jax.lax.bitcast_convert_type(
                    stacked(key, idx, reader, shape), jnp.uint16)
                got = jax.lax.bitcast_convert_type(
                    jnp.stack([out[shapes[i][0]] for i in idx]), jnp.uint16)
                counts.append(jnp.sum(got != want, dtype=jnp.int32))
            return jnp.stack(counts)[None, :]

        count = self._per_chip(body, (P(), P()), P(AXIS))
        kd = self._key_data()
        return np.stack([np.asarray(count(out, kd)) for out in outs])

    def check(self) -> Tuple[Dict[str, float], int]:
        if self.out is None:
            return {}, len(self.failed)
        outs = [self.out] + ([self.sample] if self.sample is not self.out
                             else [])
        return ({"elements_off": float(self.elements_off(outs).sum())},
                len(self.failed))
