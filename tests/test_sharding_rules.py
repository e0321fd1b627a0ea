"""Logical-axis → mesh assignment: greedy, divisibility-checked."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.models.shardings import rules_for, spec_for


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def spec(axes, shape, fsdp=False, mesh_shape=(16, 16), names=("data", "model")):
    # use abstract mesh-like object: construct with real devices is fine for 1x1;
    # for 16x16 math we only need shape/axis_names
    am = AbstractMesh(mesh_shape, names)
    return spec_for(axes, shape, am, rules_for(fsdp))


def test_expert_shards_model_axis_when_divisible():
    s = spec(("expert", "embed", "expert_ff"), (384, 7168, 2048))
    assert s == P("model")


def test_expert_fallback_to_ff_when_not_divisible():
    # Granite: 40 experts cannot split 16 ways → per-expert ff takes model
    s = spec(("expert", "embed", "expert_ff"), (40, 1536, 512))
    assert s == P(None, None, "model")


def test_kv_heads_not_divisible_stays_replicated():
    s = spec(("embed", "kv_heads", "head_dim"), (1024, 8, 128))
    assert s == P(None, "model") or s == P(None, None, "model") or s == P()
    # kv=8 on a 16-way axis cannot shard; greedy must NOT assign it
    assert "model" not in (s[1] if len(s) > 1 else ())


def test_fsdp_spreads_over_both_axes():
    s = spec(("embed", "d_ff"), (8192, 29568), fsdp=True)
    assert s == P("data", "model")


def test_batch_takes_pod_and_data():
    am = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    s = spec_for(("batch", None, "embed"), (256, 4096, 1024), am,
                 rules_for(False))
    assert s[0] == ("pod", "data")
