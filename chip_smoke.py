#!/usr/bin/env python
"""Smoke test of the Snow repro on a TPU: the device engine's main path
on one chip, or the data-plane collectives on four.

    python chip_smoke.py             # phases 1-5 on one chip
    python chip_smoke.py --chips 4   # the 4-chip data plane, nothing else

One chip drives ``ExperimentSpec`` -> ``experiments.run_cell`` with
``engine="device"`` at cluster sizes a deployment would call real (k = 4,
seeds 0..4):

1. stable: snow and coloring at n = 1M (20 messages), snow at n = 10M
   (2 messages);
2. churn and breakdown: snow, oracle views, the paper's cadences at
   n = 1M (the padded-epoch ``lax.map`` trace program);
3. workload: 8 Poisson publishers, 1 KiB payload, a 20 KB/s egress cap,
   the rho = 0.7 point of ``benchmarks/bench_workload.py`` at n = 1M;
4. the chip against the CPU: the three device programs at n = 50,000,
   once on the TPU and once on the host CPU, same threefry stream;
5. the device engine against the host numpy oracle at n = 5,000
   (printed, not gated: the two draw different RNG streams).

Delay-independent results are checked exactly: reliability 1.0 and the
closed-form RMR on stable rows, and the host engine's reliability and
byte rows on churn/breakdown.  Every phase runs twice; the cold call
includes compilation and the warm one must return the identical row.
Walls are host-clock smoke timings, not benchmark numbers.

``--chips 4`` runs ``snow_broadcast``, ``two_tree_broadcast``,
``snow_allreduce`` and ``distribute_params`` over a (4,) mesh at 1 KiB
to 64 MiB per device, each against ``lax.all_gather`` / ``lax.psum`` on
the same mesh, and checks that the outputs sit on 4 distinct devices.

Exits non-zero when the first device is not a TPU or any check fails.
The last stdout line is the device record
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The compile
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.bench_workload import (EGRESS_BPS, N_PUBLISHERS,  # noqa: E402
                                       PAYLOAD as WL_PAYLOAD, TARGET_MSGS,
                                       _lam)
from benchmarks.run import use_compile_cache  # noqa: E402
from repro.checkpoint.distribution import distribute_params  # noqa: E402
from repro.collectives.tree_collectives import (  # noqa: E402
    snow_allreduce, snow_broadcast, two_tree_broadcast)
from repro.core import device_sweep  # noqa: E402
from repro.core.churn import paper_churn_trace  # noqa: E402
from repro.core.engine import (compile_trace, stable_plans,  # noqa: E402
                               stable_sweep, trace_sweep)
from repro.core.experiments import ExperimentSpec, run_cell  # noqa: E402
from repro.core.messages import Data  # noqa: E402
from repro.core.specs import RunSpec, WorkloadSpec  # noqa: E402

K = 4
SEEDS = (0, 1, 2, 3, 4)
#: chip-vs-CPU agreement bound on per-seed LDT and on absolute delivery
#: times.  Both devices draw the same threefry bits; only the float32
#: transcendentals (exp, erf_inv) and their fusion may round differently,
#: a few ulp per delay.  A time sums 2 * height (<= 24) such delays on
#: top of t0 <= 20 s, so the drift is bounded near 24 * 4 ulp(24 s) /
#: 4 s ~ 5e-5 relative to an LDT.  A wrong stream, gather or queue plane
#: moves a time by a whole forwarding delay (>= 10 ms, ~2e-3 of an LDT).
RTOL_CHIP_VS_CPU = 2e-4
#: per-device payloads of the 4-chip data plane, bytes
PAYLOAD_BYTES = (1 << 10, 1 << 16, 1 << 20, 1 << 24, 1 << 26)
AXIS = "chips"
DATA_ROOT = 1


class SmokeFailure(RuntimeError):
    """A check on the chip's results failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class Sizes:
    """Cluster sizes of the one-chip phases."""

    n: int = 1_000_000
    n_messages: int = 20
    n_big: int = 10_000_000
    n_messages_big: int = 2
    n_chip_vs_cpu: int = 50_000
    n_oracle: int = 5_000


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak:,}"


def twice(label: str, fn):
    """Run ``fn`` cold (compiles) then warm; the two results must agree
    exactly.  Prints the smoke timings and the process's device peak."""
    t = time.perf_counter()
    cold = fn()
    t_cold = time.perf_counter() - t
    t = time.perf_counter()
    warm = fn()
    t_warm = time.perf_counter() - t
    check(json.dumps(cold, sort_keys=True) == json.dumps(warm, sort_keys=True),
          f"{label}: the warm call returned a different row")
    print(f"  {label}: cold {t_cold:.3f} s, warm {t_warm:.3f} s, "
          f"peak_bytes_in_use {peak_bytes()} (smoke timings, not benchmark "
          f"numbers)", flush=True)
    return cold


def device_row(spec: ExperimentSpec, label: str) -> dict:
    (cell,) = spec.cells()
    row = twice(label, lambda: run_cell(spec, cell))
    check("skipped" not in row, f"{label}: skipped: {row.get('skipped')}")
    check(row["engine_used"] == "device",
          f"{label}: ran on {row['engine_used']!r}, not the device engine")
    check(math.isfinite(row["ldt_ms"]) and row["ldt_ms"] > 0,
          f"{label}: LDT {row['ldt_ms']} is not a positive finite value")
    return row


# ------------------------------------------------------------------ #
# Phases on one chip                                                  #
# ------------------------------------------------------------------ #
def phase_stable(sz: Sizes) -> None:
    print("phase 1: stable", flush=True)
    cases = [("snow", sz.n, sz.n_messages), ("coloring", sz.n, sz.n_messages),
             ("snow", sz.n_big, sz.n_messages_big)]
    for proto, n, m in cases:
        spec = ExperimentSpec(name="chip-smoke-stable", protocols=(proto,),
                              ns=(n,), ks=(K,), engines=("device",),
                              seeds=SEEDS, n_messages=m)
        label = f"stable {proto} n={n:,} messages={m}"
        row = device_row(spec, label)
        # closed form: a uniform stable view reaches every non-root
        # member once per tree, so RMR is one frame per tree
        trees = 2 if proto == "coloring" else 1
        frame = Data(0, 0, None, None, row["cell"]["payload"]).size
        check(row["reliability"] == 1.0,
              f"{label}: reliability {row['reliability']} != 1.0")
        check(row["rmr_B"] == float(frame * trees),
              f"{label}: rmr {row['rmr_B']} != closed form {frame * trees}")
        check(row["redundant_B"] == float(frame * (trees - 1)),
              f"{label}: redundant {row['redundant_B']} != "
              f"{frame * (trees - 1)}")
        print(f"    ldt_ms {row['ldt_ms']!r} ci95 {row['ldt_ms_ci95']!r} "
              f"reliability {row['reliability']!r} rmr_B {row['rmr_B']!r} "
              f"(closed form {frame * trees})", flush=True)


#: churn/breakdown row keys that come from host reach masks and must
#: match the host engine exactly
EXACT_TRACE_KEYS = ("reliability", "rmr_B", "redundant_B", "payload_B",
                    "control_Bps_node", "data_Bps_node")


def phase_trace(sz: Sizes) -> None:
    print("phase 2: churn / breakdown", flush=True)
    for scene in ("churn", "breakdown"):
        kw = dict(name="chip-smoke-trace", scenes=(scene,), ns=(sz.n,),
                  ks=(K,), seeds=SEEDS, n_messages=sz.n_messages)
        label = f"{scene} snow n={sz.n:,} messages={sz.n_messages}"
        row = device_row(ExperimentSpec(engines=("device",), **kw), label)
        host_spec = ExperimentSpec(engines=("vectorized",), **kw)
        t = time.perf_counter()
        host = run_cell(host_spec, host_spec.cells()[0])
        t_host = time.perf_counter() - t
        for key in EXACT_TRACE_KEYS:
            check(row[key] == host[key],
                  f"{label}: {key} {row[key]!r} != host {host[key]!r}")
        print(f"    ldt_ms device {row['ldt_ms']!r} host {host['ldt_ms']!r} "
              f"reliability {row['reliability']!r} rmr_B {row['rmr_B']!r} "
              f"(== host engine, {t_host:.3f} s on the host)", flush=True)


def phase_workload(sz: Sizes) -> None:
    print("phase 3: workload", flush=True)
    lam = _lam(sz.n, 0.7)
    wl = WorkloadSpec(kind="poisson", rate_hz=lam, horizon_s=TARGET_MSGS / lam,
                      n_publishers=N_PUBLISHERS, payload=WL_PAYLOAD,
                      egress_bytes_per_s=EGRESS_BPS)
    spec = ExperimentSpec(name="chip-smoke-workload", ns=(sz.n,), ks=(K,),
                          engines=("device",), seeds=SEEDS, payloads=(WL_PAYLOAD,),
                          workload=wl)
    label = f"workload rho=0.7 n={sz.n:,} lambda={lam:.4f}/s"
    row = device_row(spec, label)
    check(row["reliability"] == 1.0,
          f"{label}: reliability {row['reliability']} != 1.0")
    tails = [row[f"{q}_ldt_ms"] for q in ("p50", "p99", "p999")]
    check(all(math.isfinite(v) for v in tails) and tails == sorted(tails),
          f"{label}: LDT tails {tails} are not finite and ordered")
    print(f"    messages {row['n_messages']!r} ldt_ms {row['ldt_ms']!r} "
          f"p50/p99/p999 {tails} reliability {row['reliability']!r}",
          flush=True)


def _on_both(fn):
    """``fn()`` on the default device, then on the host CPU."""
    chip = fn()
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = fn()
    return chip, cpu


def _agree(label: str, chip: np.ndarray, cpu: np.ndarray) -> None:
    chip, cpu = np.asarray(chip), np.asarray(cpu)
    check(chip.shape == cpu.shape, f"{label}: shapes {chip.shape} {cpu.shape}")
    check(np.array_equal(np.isnan(chip), np.isnan(cpu)),
          f"{label}: the unreached sets differ")
    ok = ~np.isnan(cpu)
    rel = np.abs(chip[ok] - cpu[ok]) / np.maximum(np.abs(cpu[ok]), 1e-30)
    worst = float(rel.max()) if rel.size else 0.0
    check(worst <= RTOL_CHIP_VS_CPU,
          f"{label}: chip and CPU differ by {worst:.3e} relative")
    print(f"    {label}: max relative difference {worst:.3e} "
          f"(bound {RTOL_CHIP_VS_CPU})", flush=True)


def phase_chip_vs_cpu(sz: Sizes) -> None:
    n = sz.n_chip_vs_cpu
    print(f"phase 4: chip against CPU at n={n:,}", flush=True)
    members = np.arange(n)
    for proto in ("snow", "coloring"):
        plans = stable_plans(proto, members, 0, K)
        (l_chip, r_chip), (l_cpu, r_cpu) = _on_both(
            lambda: device_sweep.stable_stats_device(plans, SEEDS,
                                                     sz.n_messages))
        check(np.array_equal(r_chip, r_cpu),
              f"stable {proto}: reliability {r_chip} != CPU {r_cpu}")
        _agree(f"stable_stats_device {proto} per-seed LDT", l_chip, l_cpu)
    trace = paper_churn_trace(n, sz.n_messages)
    epochs = compile_trace("snow", trace, K, trace.all_ids(), 64)
    _agree("trace_ldt_device churn per-seed LDT",
           *_on_both(lambda: device_sweep.trace_ldt_device(epochs, trace,
                                                           SEEDS)))
    plan = stable_plans("snow", members, 7, K)[0]
    rng = np.random.default_rng(0)
    t0 = np.sort(rng.uniform(0.0, 5.0, size=6))
    qadd = rng.uniform(0.0, 0.05, size=(t0.size, n)).astype(np.float32)
    _agree("workload_times_device delivery times",
           *_on_both(lambda: device_sweep.workload_times_device(
               plan, 3, 1, t0, qadd=qadd)))


def phase_oracle(sz: Sizes) -> None:
    n = sz.n_oracle
    print(f"phase 5: device engine against the host numpy oracle at "
          f"n={n:,} (printed, not gated)", flush=True)
    for proto in ("snow", "coloring"):
        ldt = {}
        for eng in ("host", "device"):
            rows = stable_sweep(proto, n, K, SEEDS, n_messages=sz.n_messages,
                                run=RunSpec(engine=eng, backend="numpy"))
            ldt[eng] = float(np.mean([r["ldt"] for r in rows]))
        drift = abs(ldt["device"] - ldt["host"]) / ldt["host"]
        print(f"    stable {proto}: mean LDT device {ldt['device']!r} s, "
              f"host {ldt['host']!r} s, drift {drift:.4f}", flush=True)
    trace = paper_churn_trace(n, sz.n_messages)
    ldt = {eng: float(np.mean([r["ldt"] for r in trace_sweep(
        "snow", trace, K, SEEDS, run=RunSpec(engine=eng, backend="numpy"))]))
        for eng in ("host", "device")}
    drift = abs(ldt["device"] - ldt["host"]) / ldt["host"]
    print(f"    churn snow: mean LDT device {ldt['device']!r} s, "
          f"host {ldt['host']!r} s, drift {drift:.4f}", flush=True)


def one_chip(sz: Sizes) -> None:
    phase_stable(sz)
    phase_trace(sz)
    phase_workload(sz)
    phase_chip_vs_cpu(sz)
    phase_oracle(sz)


# ------------------------------------------------------------------ #
# Four chips: the data plane                                          #
# ------------------------------------------------------------------ #
def _per_device(mesh, fn):
    """Jitted shard_map of ``fn`` over one row per device."""
    return jax.jit(jax.shard_map(
        lambda xx: fn(xx[0])[None], mesh=mesh, in_specs=P(AXIS),
        out_specs=P(AXIS), check_vma=False))


def _best_of(fn, x, reps: int = 3) -> float:
    best = math.inf
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t)
    return best


def _on_distinct_devices(label: str, out, devices) -> None:
    got = [s.device for s in out.addressable_shards]
    check(len(set(got)) == len(devices) and set(got) == set(devices),
          f"{label}: output shards on {got}, not on {len(devices)} "
          f"distinct devices")


def data_plane(devices, payloads=PAYLOAD_BYTES) -> None:
    nd = len(devices)
    mesh = jax.make_mesh((nd,), (AXIS,), axis_types=(AxisType.Auto,),
                         devices=devices)
    rows = NamedSharding(mesh, P(AXIS))
    replicated = NamedSharding(mesh, P())
    kw = dict(axis_size=nd, root=DATA_ROOT, k=2)
    cases = [
        ("snow_broadcast", lambda v: snow_broadcast(v, AXIS, **kw),
         lambda v: lax.all_gather(v, AXIS)[DATA_ROOT], True),
        ("two_tree_broadcast", lambda v: two_tree_broadcast(v, AXIS, **kw),
         lambda v: lax.all_gather(v, AXIS)[DATA_ROOT], True),
        ("snow_allreduce", lambda v: snow_allreduce(v, AXIS, **kw),
         lambda v: lax.psum(v, AXIS), False),
    ]
    equal = jax.jit(jnp.array_equal)
    close = jax.jit(lambda a, b: jnp.allclose(a, b, rtol=1e-6, atol=0.0))
    print(f"data plane over a ({nd},) mesh of {[d.id for d in devices]}, "
          f"root {DATA_ROOT}, k 2", flush=True)
    for nbytes in payloads:
        length = nbytes // 4
        # device i holds (j % 4096) / 4 + i: distinct rows, exact sums
        x = jax.jit(lambda: (jnp.arange(length, dtype=jnp.int32) % 4096)
                    .astype(jnp.float32)[None, :] * 0.25
                    + jnp.arange(nd, dtype=jnp.float32)[:, None],
                    out_shardings=rows)()
        _on_distinct_devices("input", x, devices)
        line = []
        for name, fn, ref_fn, exact in cases:
            snow, ref = _per_device(mesh, fn), _per_device(mesh, ref_fn)
            out, want = snow(x), ref(x)
            _on_distinct_devices(f"{name} {nbytes} B", out, devices)
            if exact:
                check(bool(equal(want, jnp.broadcast_to(x[DATA_ROOT],
                                                        x.shape))),
                      f"all_gather reference at {nbytes} B is wrong")
                check(bool(equal(out, want)),
                      f"{name} at {nbytes} B differs from all_gather")
            else:
                check(bool(close(want, jnp.broadcast_to(x.sum(0), x.shape))),
                      f"psum reference at {nbytes} B is wrong")
                check(bool(close(out, want)),
                      f"{name} at {nbytes} B differs from psum")
            line.append(f"{name} {_best_of(snow, x) * 1e3:.3f} ms vs "
                        f"{'all_gather' if exact else 'psum'} "
                        f"{_best_of(ref, x) * 1e3:.3f} ms")
        # checkpoint fan-out: every device starts with its own buffer
        # under a replicated sharding, as each host holds its own copy
        # before the reader's is fanned out
        leaf = jax.make_array_from_single_device_arrays(
            (1, length), replicated, [s.data for s in x.addressable_shards])
        out = distribute_params({"w": leaf}, mesh, AXIS, root=DATA_ROOT,
                                k=2)["w"]
        _on_distinct_devices(f"distribute_params {nbytes} B", out, devices)
        want = np.asarray(x.addressable_shards[DATA_ROOT].data)
        for s in out.addressable_shards:
            check(np.array_equal(np.asarray(s.data), want),
                  f"distribute_params at {nbytes} B: device {s.device.id} "
                  f"does not hold the root's buffer")
        print(f"  {nbytes:>10,} B/device: all match on {nd} distinct "
              f"devices; " + "; ".join(line) + " (best of 3, smoke timings, "
              f"not benchmark numbers)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: the data plane "
                         "across four chips and nothing else")
    args = ap.parse_args(argv)
    # the host engines are the exact references; a jax host backend
    # would put them on the chip
    os.environ["REPRO_ENGINE_BACKEND"] = "numpy"
    cache = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first JAX device is {dev.platform!r}, not a "
              f"TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache}", flush=True)
    t = time.perf_counter()
    if args.chips == 4:
        data_plane(devices[:4])
    else:
        one_chip(Sizes())
    print(f"all phases passed in {time.perf_counter() - t:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
