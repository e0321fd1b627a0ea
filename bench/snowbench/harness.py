"""One run of one cell: set-up, a measured window, a check, one result.

``run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

* Set-up starts with the process: loading the configuration and traffic,
  JAX, the persistent compile cache, and one unit of work (a query or a
  rollout) at the cell's own shapes.  ``setup_s`` ends where the first
  timed unit starts.
* The window runs whole units back to back, one client, until
  ``--seconds`` have passed; a rate divides all their work by all their
  time.  Compilations inside the window are counted and printed.
* ``--trace 1`` runs the same window under the JAX profiler, with host
  spans around each unit and the program's entry points, and reports the
  per-layer metrics instead of the end-to-end ones.
* After the window: device memory is read, the program's state freed,
  and the check compares what the window produced with the benchmark's
  plain reference.  Each number compared is printed beside its limit, as
  the last lines on standard error and under ``checks``, the last key of
  the result line.

The last line on standard output is the result, one JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path
from typing import List, Optional

from .manifest import Bench

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def since_start() -> float:
    """Seconds since this process started, from the kernel's own record
    (clock ticks since boot) rather than a clock read after imports."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


class CompileCounter:
    """Counts executables built (compiled, or loaded from the persistent
    cache), the loads among them, and functions traced while ``on``."""

    def __init__(self):
        self.on = False
        self.compiles: Counter = Counter()
        self.traces = 0
        self.cache_hits = 0

    def hit(self, event: str, **kw) -> None:
        if self.on and event == CACHE_HIT:
            self.cache_hits += 1

    def __call__(self, event: str, duration: float, **kw) -> None:
        if not self.on:
            return
        if event == BACKEND_COMPILE:
            self.compiles[str(kw.get("fun_name"))] += 1
        elif event == JAXPR_TRACE:
            self.traces += 1


def generator(traffic: dict):
    return importlib.import_module(
        f"snowbench.{traffic['generator']}").Generator


def device_record(devices) -> dict:
    peaks, lines = [], []
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(stats.get("peak_bytes_in_use", 0),
                   stats.get("peak_bytes_reserved", 0),
                   stats.get("bytes_reserved", 0))
        peaks.append(peak)
        lines.append({k: stats[k] for k in sorted(stats)
                      if k.endswith(("bytes_in_use", "bytes_reserved",
                                     "bytes_limit"))})
    print(f"memory per chip (memory_stats; memory_peak_bytes is the "
          f"largest of peak_bytes_in_use, peak_bytes_reserved and "
          f"bytes_reserved, since XLA temp shows only in the reserved "
          f"bytes): {json.dumps(lines)}", flush=True)
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run(bench: Bench, workload: str, seed: int, seconds: float,
        trace: bool, devices) -> dict:
    """The run itself, on the given devices; returns the result object."""
    import jax

    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(workload)
    gen = generator(traffic)(cfg, traffic, seed, devices)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter.hit)
    try:
        counter.on = True
        gen.setup()
        print(f"set-up built {sum(counter.compiles.values())} executables, "
              f"{counter.cache_hits} of them loaded from the persistent "
              f"cache; traced {counter.traces} functions", flush=True)
        counter.compiles.clear()
        counter.traces = counter.cache_hits = 0
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        unit_span = f"bench.{gen.unit}"
        with contextlib.ExitStack() as stack:
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
                stack.enter_context(gen.spans())
                span = jax.profiler.TraceAnnotation
            else:
                span = lambda name: contextlib.nullcontext()  # noqa: E731
            setup_s = since_start()
            units, work, ends = 0, 0.0, []
            t0 = time.perf_counter()
            with span("bench.window"):
                while True:
                    with span(unit_span):
                        work += gen.step(units)
                    units += 1
                    ends.append(time.perf_counter() - t0)
                    if ends[-1] >= seconds:
                        break
            window_s = time.perf_counter() - t0
            if trace:
                jax.profiler.stop_trace()
        counter.on = False
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
        jax.monitoring.unregister_event_listener(counter.hit)
    print(f"window: {units} units ({gen.unit}) in {window_s!r} s; compilations "
          f"inside the window: {sum(counter.compiles.values())} "
          f"{dict(counter.compiles)}, functions traced: {counter.traces}; "
          f"seconds of each unit: {[b - a for a, b in zip([0.0] + ends, ends)]}",
          flush=True)
    device = device_record(devices)
    result = {"attempted": units, "metrics": {}, "device": device}
    if trace:
        from . import trace as tr

        paths = list(Path(tdir).rglob("*.xplane.pb"))
        view = tr.TraceView(tr.collect(paths[0]), units)
        shutil.rmtree(tdir, ignore_errors=True)
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"])(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = view.mean_busy_s()
        device["window_s"] = view.window_s
        result["breakdown"] = {"device_ops": view.top_ops(),
                               "idle_gaps": view.idle_gaps()}
    else:
        values = dict(gen.e2e(work, window_s), setup_s=setup_s)
        for m in bench.end_to_end(workload):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    gen.release()
    t = time.perf_counter()
    numbers, failed = gen.check()
    print(f"check: {time.perf_counter() - t!r} s", flush=True)
    for why in gen.failed:
        print(f"failed: {why}", flush=True)
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in sorted(limits) if name in numbers}
    correct = (failed == 0 and set(numbers) == set(limits)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct}
    out.update(result)
    out["failed"] = failed
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="run.py", description=__doc__.split(
        "\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    bench = Bench.load(root)
    chips = bench.workload(args.workload)["chips"]
    # the host engines the program falls back to stay on the host
    os.environ["REPRO_ENGINE_BACKEND"] = "numpy"
    from .cache import use_compile_cache

    cache = use_compile_cache(root)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: the first JAX device is {devices[0].platform!r}, "
              f"not a TPU; this benchmark measures only on the chip",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"device {devices[0].device_kind} x{chips} of {len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)
    out = run(bench, args.workload, args.seed, args.seconds,
              bool(args.trace), devices[:chips])
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
