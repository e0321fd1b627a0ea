"""Device-resident sweep engine: reproducibility, statistical pins vs
the DelayBank oracle, Pallas/XLA bit-equality, and engine routing.

The boundary the suite enforces (DESIGN.md §10): everything *inside*
one device configuration is bit-reproducible (same seeds → same rows,
on either ``REPRO_ENGINE_BACKEND``, and the interpret-mode Pallas
kernel is bit-equal to the jitted XLA sweep on the same generated
delays), while device-vs-host is only *statistically* pinned (different
RNG stream, float32 math, Bernoulli stragglers)."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.churn import paper_breakdown_trace, paper_churn_trace
from repro.core.engine import (bank_for_stable, broadcast_times,
                               compile_trace, stable_plans, stable_sweep,
                               trace_sweep)
from repro.core.device_sweep import (stable_stats_device,
                                     stable_times_device,
                                     trace_ldt_device)
from repro.core.planner import depth_levels

SEEDS = tuple(range(8))


# ------------------------------------------------------------------ #
# (a) reproducibility — across calls and across backend settings      #
# ------------------------------------------------------------------ #
def test_device_rows_reproducible_across_calls():
    plans = stable_plans("snow", np.arange(600), 0, 4)
    a = stable_sweep("snow", 600, 4, SEEDS, plans=plans, engine="device")
    b = stable_sweep("snow", 600, 4, SEEDS, plans=plans, engine="device")
    assert [r["ldt"] for r in a] == [r["ldt"] for r in b]
    assert [r["reliability"] for r in a] == [r["reliability"] for r in b]


def test_device_times_reproducible_across_calls():
    plans = stable_plans("coloring", np.arange(500), 0, 4)
    t1 = stable_times_device(plans, 7, 2)
    t2 = stable_times_device(plans, 7, 2)
    assert np.array_equal(t1, t2, equal_nan=True)


def test_device_rows_independent_of_engine_backend_env():
    """REPRO_ENGINE_BACKEND steers the HOST sweep only; the device path
    is always jax, so its rows must be byte-identical under both
    settings.  Checked in subprocesses — the env var is read at import
    time."""
    prog = (
        "import numpy as np\n"
        "from repro.core.engine import stable_plans, stable_sweep\n"
        "plans = stable_plans('snow', np.arange(400), 0, 4)\n"
        "rows = stable_sweep('snow', 400, 4, range(4), plans=plans,\n"
        "                    engine='device')\n"
        "print(repr([(r['ldt'], r['reliability']) for r in rows]))\n"
    )
    outs = []
    for backend in ("numpy", "jax"):
        env = dict(os.environ, REPRO_ENGINE_BACKEND=backend,
                   PYTHONPATH=str(Path(__file__).resolve().parents[1]
                                  / "src"))
        res = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, check=True)
        outs.append(res.stdout.strip())
    assert outs[0] == outs[1]


# ------------------------------------------------------------------ #
# (b) statistical pins vs the DelayBank oracle                        #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n,tol_mean,tol_p99", [
    (500, 0.08, 0.05), (5000, 0.10, 0.08), (50_000, 0.10, 0.08),
])
def test_device_delivery_distribution_pinned(n, tol_mean, tol_p99):
    """Mean and p99 of the per-node delivery-time distribution must
    match the numpy DelayBank oracle within tolerance — straggler-free
    banks, so the pin isolates the §5.2 uniform/lognormal draws (the
    straggler *placement* is an O(1)-per-seed extreme that dominates
    the mean and needs far more seeds to average out; the LDT pins
    below cover it)."""
    from repro.core.engine import DelayBank

    plans = stable_plans("snow", np.arange(n), 0, 4)
    seeds = range(4)
    t0 = np.arange(2, dtype=float)[:, None]
    host = np.concatenate([
        (broadcast_times(plans, DelayBank.sample(s, np.arange(n), set(),
                                                 2), 2, backend="numpy")
         - t0)[:, 1:].ravel() for s in seeds])
    dev = np.concatenate([
        (stable_times_device(plans, s, 2, straggler_frac=0.0)
         - t0)[:, 1:].ravel() for s in seeds])
    assert abs(dev.mean() - host.mean()) / host.mean() < tol_mean
    hp, dp = np.percentile(host, 99), np.percentile(dev, 99)
    assert abs(dp - hp) / hp < tol_p99


@pytest.mark.parametrize("n,n_seeds,tol_mean,tol_p99", [
    # p99 of a max statistic at n=500 is an extreme of extremes —
    # measured drift ~26%, banded accordingly; it tightens fast with n
    (500, 8, 0.08, 0.40), (5000, 8, 0.10, 0.12), (50_000, 4, 0.12, 0.08),
])
def test_device_ldt_pinned_vs_host(n, n_seeds, tol_mean, tol_p99):
    """The ISSUE's pin: mean/p99 LDT vs the DelayBank oracle (stragglers
    on) over seeds × messages, at n ∈ {500, 5000, 50k}."""
    M = 20
    plans = stable_plans("snow", np.arange(n), 0, 4)
    t0 = np.arange(float(M))[:, None]
    host, dev = [], []
    for s in range(n_seeds):
        bank = bank_for_stable(s, n, "snow", M)
        ht = broadcast_times(plans, bank, M, backend="numpy")
        host.append(np.nanmax((ht - t0)[:, 1:], axis=1))
        dev.append(np.nanmax((stable_times_device(plans, s, M)
                              - t0)[:, 1:], axis=1))
    h, d = np.concatenate(host), np.concatenate(dev)
    assert abs(d.mean() - h.mean()) / h.mean() < tol_mean
    hp, dp = np.percentile(h, 99), np.percentile(d, 99)
    assert abs(dp - hp) / hp < tol_p99


def test_device_rows_pinned_vs_host():
    """Row-level pin through the public engine API: seed-averaged LDT
    and bit-identical reliability."""
    n = 5000
    plans = stable_plans("snow", np.arange(n), 0, 4)
    host = stable_sweep("snow", n, 4, SEEDS, plans=plans,
                        backend="numpy")
    dev = stable_sweep("snow", n, 4, SEEDS, plans=plans, engine="device")
    h = np.mean([r["ldt"] for r in host])
    d = np.mean([r["ldt"] for r in dev])
    assert abs(d - h) / h < 0.10
    assert all(r["reliability"] == 1.0 for r in dev)


def test_device_trace_sweep_pinned_and_metrics_exact():
    """Churn/breakdown: LDT statistically pinned; the delay-independent
    metrics (reliability, RMR, redundant bytes) must agree with the
    host engine EXACTLY — both derive from the same reach masks."""
    trace = paper_breakdown_trace(400, 30, 1.0, 7, 10, detect_after=2.5)
    for proto in ("snow", "coloring"):
        epochs = compile_trace(proto, trace, 4, trace.all_ids())
        host = trace_sweep(proto, trace, 4, SEEDS, epochs=epochs)
        dev = trace_sweep(proto, trace, 4, SEEDS, epochs=epochs,
                          engine="device")
        h = np.mean([r["ldt"] for r in host])
        d = np.mean([r["ldt"] for r in dev])
        assert abs(d - h) / h < 0.15
        for rh, rd in zip(host, dev):
            assert rd["reliability"] == rh["reliability"]
            assert rd["rmr"] == pytest.approx(rh["rmr"], abs=1e-9)
            assert rd["rmr_redundant"] == pytest.approx(
                rh["rmr_redundant"], abs=1e-9)


def test_trace_ldt_device_reproducible():
    trace = paper_churn_trace(300, 20, 1.0, 5)
    epochs = compile_trace("snow", trace, 4, trace.all_ids())
    a = trace_ldt_device(epochs, trace, SEEDS)
    b = trace_ldt_device(epochs, trace, SEEDS)
    assert np.array_equal(a, b)


# ------------------------------------------------------------------ #
# (c) Pallas kernel: interpret mode bit-equal to the XLA sweep        #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("protocol", ["snow", "coloring"])
def test_pallas_interpret_bit_equal_xla(protocol):
    plans = stable_plans(protocol, np.arange(700), 0, 4)
    t_xla = stable_times_device(plans, 11, 4)
    t_pal = stable_times_device(plans, 11, 4, impl="pallas_interpret")
    assert np.array_equal(t_xla, t_pal, equal_nan=True)


def test_tree_sweep_kernel_matches_reference_inputs():
    """Kernel-level check on raw operands (no RNG): interpret Pallas ==
    jitted XLA == the numpy closed form, bit for bit where both are
    f32."""
    import jax.numpy as jnp

    from repro.kernels.ops import tree_sweep
    from repro.kernels.tree_sweep import fwd_at_parent

    rng = np.random.default_rng(0)
    plan = stable_plans("snow", np.arange(300), 0, 4)[0]
    parent = jnp.asarray(np.asarray(plan.parent, dtype=np.int32))
    depth = jnp.asarray(np.asarray(plan.depth, dtype=np.int32))
    fwd = jnp.asarray(rng.uniform(0.01, 0.2, (3, 300)).astype(np.float32))
    link = jnp.asarray(rng.uniform(0.0, 0.001, (3, 300))
                       .astype(np.float32))
    t0 = jnp.asarray(np.arange(3, dtype=np.float32))
    height = int(np.asarray(plan.depth).max())
    fp = fwd_at_parent(parent, fwd, plan.root)
    a = np.asarray(tree_sweep(parent, depth, fp, link, t0,
                              root=plan.root, height=height, impl="xla"))
    b = np.asarray(tree_sweep(parent, depth, fp, link, t0,
                              root=plan.root, height=height,
                              impl="pallas_interpret"))
    assert np.array_equal(a, b, equal_nan=True)


# ------------------------------------------------------------------ #
# satellites: levels cache, plan_s accounting, experiments routing    #
# ------------------------------------------------------------------ #
def test_treeplan_levels_cached_and_correct():
    plan = stable_plans("snow", np.arange(400), 0, 4)[0]
    lv1 = plan.levels
    assert lv1 is plan.levels, "cached_property must return one object"
    depth = np.asarray(plan.depth)
    recomputed = depth_levels(depth)
    assert len(lv1) == len(recomputed) == int(depth.max())
    for a, b in zip(lv1, recomputed):
        assert np.array_equal(a, b)
        assert np.array_equal(np.sort(depth[a]), depth[a])  # one level
    covered = np.concatenate(lv1)
    assert np.array_equal(np.sort(covered),
                          np.flatnonzero(depth >= 1))


def test_plan_s_attributed_to_first_row_only():
    rows = stable_sweep("snow", 300, 4, range(4), n_messages=2)
    assert rows[0]["plan_s"] > 0.0
    assert all(r["plan_s"] == 0.0 for r in rows[1:])
    trace = paper_churn_trace(200, 10, 1.0, 5)
    rows = trace_sweep("snow", trace, 4, range(3))
    assert rows[0]["plan_s"] > 0.0
    assert all(r["plan_s"] == 0.0 for r in rows[1:])


def test_stable_stats_device_matches_row_engine():
    """stable_sweep(engine="device") rows are a thin wrapper over
    stable_stats_device — same numbers, full schema."""
    plans = stable_plans("coloring", np.arange(500), 0, 4)
    ldt, rel = stable_stats_device(plans, SEEDS, 2)
    rows = stable_sweep("coloring", 500, 4, SEEDS, plans=plans,
                        engine="device")
    assert [r["ldt"] for r in rows] == [float(v) for v in ldt]
    assert [r["reliability"] for r in rows] == [float(v) for v in rel]
    assert all(r["engine"] == "device" for r in rows)
    assert {"seed", "n", "k", "rmr", "rmr_redundant", "n_messages",
            "wall_s", "plan_s"} <= set(rows[0])


def test_experiments_device_engine_routing():
    from repro.core.experiments import Cell, ExperimentSpec, route, run_cell

    spec = ExperimentSpec(name="t", protocols=("snow",), ns=(200,),
                          ks=(4,), scenes=("stable",),
                          engines=("device",), seeds=(0, 1),
                          n_messages=2)
    cells = list(spec.cells())
    assert route(spec, cells[0]) == "closed-form"
    row = run_cell(spec, cells[0])
    assert row["engine_used"] == "device"
    assert row["reliability"] == 1.0
    # protocols without a device expression are an explicit skip
    g = Cell(protocol="gossip", scene="stable", n=200, k=4, payload=64,
             view_model="oracle", engine="device")
    assert route(spec, g).startswith("skipped:")


# ------------------------------------------------------------------ #
# (d) node-major sweep: bit-equal to the per-seed lane formulation    #
# ------------------------------------------------------------------ #
def _random_plan(rng, n, n_pad):
    """A random recursive tree over ``n`` shuffled labels, root at label
    0, plus ``n_pad`` padded members (``depth = -1``, parent 0)."""
    order = np.concatenate([[0], 1 + rng.permutation(n - 1)])
    parent = np.zeros(n + n_pad, dtype=np.int32)
    depth = np.full(n + n_pad, -1, dtype=np.int32)
    depth[0] = 0
    for i in range(1, n):
        p = order[rng.integers(0, i)]
        parent[order[i]] = p
        depth[order[i]] = depth[p] + 1
    return parent, depth


@pytest.mark.parametrize("width", [100, 128, 200])
@pytest.mark.parametrize("kind", ["random", "planner"])
def test_level_sweep_rows_bit_equal_lane_sweep(kind, width):
    """``level_sweep_rows``/``fwd_at_parent_rows`` on an ``(n, B)`` plane
    equal ``level_sweep_xla``/``fwd_at_parent`` on its transpose, bit for
    bit, NaNs included: padded members, NaN links, any row width."""
    import jax.numpy as jnp

    from repro.kernels.tree_sweep import (fwd_at_parent, fwd_at_parent_rows,
                                          level_sweep_rows, level_sweep_xla)

    rng = np.random.default_rng(width)
    if kind == "random":
        parent, depth = _random_plan(rng, 900, 37)
        root = 0
    else:
        plan = stable_plans("coloring", np.arange(900), 0, 4)[1]
        parent = np.concatenate([np.asarray(plan.parent, np.int32),
                                 np.zeros(37, np.int32)])
        depth = np.concatenate([np.asarray(plan.depth, np.int32),
                                np.full(37, -1, np.int32)])
        root = int(plan.root)
    n = parent.shape[0]
    height = int(depth.max())
    fwd = rng.uniform(0.01, 0.2, (n, width)).astype(np.float32)
    link = rng.lognormal(np.log(4e-4), 0.35, (n, width)).astype(np.float32)
    link[rng.random((n, width)) < 0.02] = np.nan
    t0 = rng.uniform(0.0, 20.0, width).astype(np.float32)
    p, d = jnp.asarray(parent), jnp.asarray(depth)

    fp_rows = fwd_at_parent_rows(p, jnp.asarray(fwd), root)
    fp_lane = fwd_at_parent(p, jnp.asarray(fwd.T), root)
    assert np.array_equal(np.asarray(fp_rows), np.asarray(fp_lane).T,
                          equal_nan=True)
    rows = level_sweep_rows(p, d, fp_rows, jnp.asarray(link),
                            jnp.asarray(t0), root=root, height=height)
    lane = level_sweep_xla(p, d, fp_lane, jnp.asarray(link.T),
                           jnp.asarray(t0), root=root, height=height)
    rows, lane = np.asarray(rows), np.asarray(lane).T
    assert np.array_equal(rows, lane, equal_nan=True)
    assert np.isnan(rows[depth < 0]).all()
    assert np.isfinite(rows[depth >= 0]).any()


def _lane_stable_stats(seeds, parents, depths, rate_s, frac, *, meta,
                       n_messages, n_fixed):
    """The per-seed lane formulation of ``_stable_stats``: a seed ``vmap``
    around ``(messages, n)`` sweeps, reduced per message, then the mean
    over messages of each seed, summed left to right."""
    import jax
    import jax.numpy as jnp

    from repro.core import device_sweep as ds
    from repro.kernels.tree_sweep import fwd_at_parent, level_sweep_xla

    @jax.jit
    def per_message(seeds):
        n = parents[0].shape[0]
        ids = jnp.arange(n, dtype=jnp.int32)
        t0 = jnp.arange(n_messages) * rate_s

        def one(seed):
            base = jax.random.key(seed)
            strag = ds._straggler_mask(base, ids < n_fixed, frac)
            total = None
            for parent, depth, (root, height, slot) in zip(parents, depths,
                                                           meta):
                fwd, link = ds._fwd_link_planes(base, slot, n_messages, n,
                                                strag)
                t = level_sweep_xla(parent, depth,
                                    fwd_at_parent(parent, fwd, root), link,
                                    t0.astype(fwd.dtype), root=root,
                                    height=height)
                total = t if total is None else jnp.fmin(total, t)
            valid = (ids != meta[0][0])[None, :] & ~jnp.isnan(total)
            sub = total - t0[:, None].astype(total.dtype)
            return (jnp.max(jnp.where(valid, sub, -jnp.inf), axis=1),
                    valid.sum(axis=1) / (n - 1))

        return jax.vmap(one)(seeds)

    ldt, rel = per_message(seeds)
    return _mean_left_to_right(ldt), _mean_left_to_right(rel)


def _mean_left_to_right(x):
    """Row means of an ``(S, M)`` array, each summed left to right."""
    import jax

    def mean(x):
        total = x[:, 0]
        for j in range(1, x.shape[1]):
            total = total + x[:, j]
        return total / x.shape[1]

    return jax.jit(mean)(x)


@pytest.mark.parametrize("protocol", ["coloring", "snow"])
def test_stable_stats_bit_equal_lane_formulation(protocol):
    import jax.numpy as jnp

    from repro.core import device_sweep as ds

    n = 5000
    plans = stable_plans(protocol, np.arange(n), 0, 4)
    args = (jnp.asarray(np.arange(40, 45, dtype=np.uint32)),
            tuple(jnp.asarray(np.asarray(p.parent, np.int32))
                  for p in plans),
            tuple(jnp.asarray(np.asarray(p.depth, np.int32))
                  for p in plans),
            jnp.float32(1.0), jnp.float32(ds.STRAGGLER_FRAC))
    kw = dict(meta=ds._plan_meta(plans), n_messages=20, n_fixed=n)
    got = ds._stable_stats(*args, **kw)
    want = _lane_stable_stats(*args, **kw)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def _lane_trace_ldt(seeds, st, fixed_mask, *, q, height, maxp, n_slots,
                    m_total):
    """The per-seed lane formulation of ``_trace_ldt``: ``lax.map`` over
    epochs inside a seed ``vmap``, each epoch's ``(q, P)`` window
    gathered lane by lane; per-message LDTs, then each seed's mean,
    summed left to right over each epoch's messages, then over epochs."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core import device_sweep as ds
    from repro.kernels.tree_sweep import fwd_at_parent, level_sweep_xla

    @jax.jit
    def per_message(seeds, st, fixed_mask):
        n_bank = fixed_mask.shape[0]

        def one(seed):
            base = jax.random.key(seed)
            strag = ds._straggler_mask(base, fixed_mask)
            planes = [ds._fwd_link_planes(base, s, m_total, n_bank, strag)
                      for s in range(n_slots)]
            fwd_all = jnp.stack([p[0] for p in planes])
            link_all = jnp.stack([p[1] for p in planes])

            def ep_fn(e):
                cols = jnp.clip(e["col0"] + jnp.arange(q, dtype=jnp.int32),
                                0, m_total - 1)
                total = jnp.full((q, e["parent"][0].shape[0]), jnp.nan,
                                 dtype=jnp.float32)
                for p in range(maxp):
                    sl = e["slot"][p]
                    fwd = jnp.take(jnp.take(fwd_all, sl, axis=0)[cols],
                                   e["rows"], axis=-1)
                    link = jnp.take(jnp.take(link_all, sl, axis=0)[cols],
                                    e["rows"], axis=-1)
                    parent = e["parent"][p]
                    t = level_sweep_xla(
                        parent, e["depth"][p],
                        fwd_at_parent(parent, fwd, e["root"]), link,
                        e["times"].astype(fwd.dtype), root=e["root"],
                        height=height)
                    total = jnp.fmin(total, jnp.where(e["mask"][p], t,
                                                      jnp.nan))
                sub = total - e["times"][:, None].astype(total.dtype)
                valid = e["sel"][None, :] & ~jnp.isnan(total)
                return (jnp.max(jnp.where(valid, sub, -jnp.inf), axis=1),
                        e["msgmask"] & valid.any(axis=1))

            return lax.map(ep_fn, st)

        ldt, ok = jax.vmap(one)(seeds)                 # (S, E, q)
        return jnp.swapaxes(ldt, 0, 1), jnp.swapaxes(ok, 0, 1)

    ldt, ok = per_message(seeds, st, fixed_mask)

    @jax.jit
    def mean(ldt, ok):
        x = jnp.where(ok, ldt, 0.0)
        sums = None
        for e in range(x.shape[0]):           # epochs, in order
            epoch = x[e, :, 0]
            for j in range(1, x.shape[2]):    # its messages, in order
                epoch = epoch + x[e, :, j]
            sums = epoch if sums is None else sums + epoch
        c = ok.sum(axis=(0, 2))
        return jnp.where(c > 0, sums / jnp.maximum(c, 1), jnp.nan)

    return mean(ldt, ok)


@pytest.mark.parametrize("protocol", ["coloring", "snow"])
def test_trace_ldt_bit_equal_lane_formulation(protocol):
    """A breakdown trace at n = 5,000 with 5 seeds: the node-major
    ``_trace_ldt`` equals the lane formulation bit for bit, including
    the window's clipping past the last message."""
    import jax
    import jax.numpy as jnp

    from repro.core import device_sweep as ds

    trace = paper_breakdown_trace(5000, 20, 1.0, 7, 10, detect_after=2.5)
    epochs = compile_trace(protocol, trace, 4, trace.all_ids())
    args, static = ds.trace_ldt_args(epochs, trace, range(5))
    st = args[1]
    assert (st["col0"] + static["q"] > static["m_total"]).any()
    args = jax.tree.map(jnp.asarray, args)
    got = ds._trace_ldt(*args, **static)
    want = _lane_trace_ldt(*args, **static)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(want))
