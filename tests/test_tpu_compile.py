"""Ahead-of-time compiles of the device engine's programs for one TPU
v5e chip, at the sizes ``chip_smoke.py`` runs them.

Nothing runs: the TPU compiler compiles for a described ``v5e:2x2``
topology from ``ShapeDtypeStruct`` arguments on one of its devices.  A
program the chip's compiler refuses, or one whose buffers do not fit one
chip's HBM, fails here without a chip.  The topology is described in a
fixture, never at import, so only the worker that runs this file loads
the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import device_sweep as ds
from repro.core.churn import paper_churn_trace
from repro.core.engine import compile_trace

SEEDS = 5
#: one v5e chip's HBM, in decimal bytes (the chip has 16 GiB)
CHIP_HBM_BYTES = 16 * 10**9
#: (root, height, slot) per plan, as the k = 4 planner builds them at
#: n = 1M: the standard tree is 10 levels deep, the coloring secondary 12
N = 1_000_000
SNOW = ((0, 10, 0),)
COLORING = ((0, 10, 0), (0, 12, 1))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a described chip can be written but never read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes
             - m.alias_size_in_bytes)
    assert 0 < total < CHIP_HBM_BYTES, m
    return total


def _plan_args(sharding, n, meta):
    plans = tuple(_shape(sharding, (n,), jnp.int32) for _ in meta)
    return (_shape(sharding, (SEEDS,), jnp.uint32), plans, plans,
            _shape(sharding, (), jnp.float32),
            _shape(sharding, (), jnp.float32))


@pytest.mark.parametrize("meta", [SNOW, COLORING], ids=["snow", "coloring"])
def test_stable_stats_compiles_for_one_chip(one_chip, meta):
    compiled = ds._stable_stats.lower(
        *_plan_args(one_chip, N, meta), meta=meta, n_messages=20,
        n_fixed=N).compile()
    _fits_one_chip(compiled)


def test_stable_stats_loss_compiles_for_one_chip(one_chip):
    f32 = _shape(one_chip, (), jnp.float32)
    compiled = ds._stable_stats_loss.lower(
        *_plan_args(one_chip, N, COLORING), f32, f32, meta=COLORING,
        n_messages=20, n_fixed=N, max_attempts=3).compile()
    _fits_one_chip(compiled)


def test_trace_ldt_compiles_for_one_chip(one_chip):
    """The churn trace program at the stacked shapes of the paper's
    churn cadence over 1M members."""
    trace = paper_churn_trace(N, 20)
    epochs = compile_trace("snow", trace, 4, trace.all_ids(), 64)
    args, static = ds.trace_ldt_args(epochs, trace, range(SEEDS))
    shapes = jax.tree.map(
        lambda a: _shape(one_chip, a.shape,
                         jax.dtypes.canonicalize_dtype(a.dtype)), args)
    assert static["height"] == 10 and static["m_total"] == 20
    compiled = ds._trace_ldt.lower(*shapes, **static).compile()
    _fits_one_chip(compiled)


def test_workload_times_compiles_for_one_chip(one_chip):
    m = 8
    compiled = ds._workload_times.lower(
        _shape(one_chip, (), jnp.uint32), _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (N,), jnp.int32), _shape(one_chip, (N,), jnp.int32),
        _shape(one_chip, (m, N), jnp.float32),
        _shape(one_chip, (m,), jnp.float32),
        _shape(one_chip, (), jnp.float32), meta=(7, 10, 0)).compile()
    assert _fits_one_chip(compiled) > m * N * 4


SCOPED = ("level_sweep", "delay_planes", "epoch_gather", "ldt_reduce")


def _entry_fusions(compiled, shape):
    """``op_name`` of every fusion of the compiled program's entry
    computation with a result (or a tuple element) of ``shape``."""
    import re

    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return re.findall(
        rf"= \(?{re.escape(shape)}[^\n]* fusion\([^\n]*?"
        rf"op_name=\"([^\"]*)\"", entry)


def test_trace_ldt_ops_carry_their_scopes_on_the_chip(one_chip):
    """On the v5e compiler the named scopes survive fusion: the threefry
    draws fuse into the node-major stacking of the delay planes, whose op
    carries ``delay_planes``, and each stage's scope reaches the compiled
    ops."""
    n = 20_000
    trace = paper_churn_trace(n, 20)
    epochs = compile_trace("snow", trace, 4, trace.all_ids(), 64)
    args, static = ds.trace_ldt_args(epochs, trace, range(SEEDS))
    shapes = jax.tree.map(
        lambda a: _shape(one_chip, a.shape,
                         jax.dtypes.canonicalize_dtype(a.dtype)), args)
    compiled = ds._trace_ldt.lower(*shapes, **static).compile()
    n_bank = shapes[2].shape[0]
    planes = [name for shape in (f"f32[{n_bank},{SEEDS},20]",
                                 f"f32[{static['n_slots']},{n_bank},"
                                 f"{SEEDS * 20}]")
              for name in _entry_fusions(compiled, shape)]
    assert planes and all("delay_planes" in name for name in planes)
    text = compiled.as_text()
    assert all(f"/{scope}/" in text or f"({scope})/" in text
               for scope in SCOPED)


def test_stable_stats_ops_carry_their_scopes_on_the_chip(one_chip):
    n = 20_000
    compiled = ds._stable_stats.lower(
        *_plan_args(one_chip, n, COLORING), meta=COLORING, n_messages=20,
        n_fixed=n).compile()
    text = compiled.as_text()
    for scope in ("level_sweep", "delay_planes", "ldt_reduce"):
        assert f"/{scope}/" in text or f"({scope})/" in text, scope


#: HBM bytes one level pass may move at n = 1M: the node-major pass
#: (take + add + add + where over ``(1M, 128)``) counts 7.2 GB on the v5e
#: compiler; the lane layout ``(5, 20, 1M)`` counts 64.4 GB, and an
#: unpadded row of 55 lanes 67 GB
LEVEL_PASS_BYTES = 12e9


def test_level_sweep_rows_pass_gathers_whole_rows(one_chip):
    from repro.kernels.tree_sweep import level_sweep_rows

    def one_pass(parent, depth, fp, link, t0):
        return level_sweep_rows(parent, depth, fp, link, t0, root=0,
                                height=1)

    idx = _shape(one_chip, (N,), jnp.int32)
    plane = _shape(one_chip, (N, 128), jnp.float32)
    compiled = jax.jit(one_pass).lower(
        idx, idx, plane, plane,
        _shape(one_chip, (128,), jnp.float32)).compile()
    moved = compiled.cost_analysis()["bytes accessed"]
    assert 0 < moved <= LEVEL_PASS_BYTES, moved
