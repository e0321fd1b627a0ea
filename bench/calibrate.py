#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <name> --seeds 12 --controls 3

For each of ``--seeds`` seeds (``--first``, ``--first + 1``, ...) the
program answers the cell's first query (or rollout) at the cell's own
size, exactly as a run's window does, and the run's check compares it
with the plain reference: the largest of these is each number's lower
reading.  For ``--controls`` of those seeds the control takes the
program's place: for a sweep, the reference itself in bfloat16 (the
configuration states float32); for the fan-out, the program's output
rounded through float8 (the configuration states bfloat16).  The
smallest control reading is each number's upper reading.

Needs the chip the cell asks for, like ``run.py``.  The benchmark's own
runs never run this.  Prints one JSON object last.
"""
import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from snowbench import reference, sweep  # noqa: E402
from snowbench.harness import generator  # noqa: E402
from snowbench.manifest import Bench  # noqa: E402


def sweep_control(gen) -> dict:
    """The bfloat16 reference in the program's place, against the float64
    reference, on the query the program answered."""
    import ml_dtypes

    q, _ = gen.done[0]
    low = reference.answer(q, gen.cfg, gen.traffic, dtype=ml_dtypes.bfloat16)
    return sweep.compare(low, reference.answer(q, gen.cfg, gen.traffic))


def rollout_control(gen) -> dict:
    """The program's fan-out with its output rounded through float8."""
    import jax
    import jax.numpy as jnp

    gen.out = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), gen.out)
    return gen.check()[0]


def readings(bench: Bench, workload: str, seeds, controls: int,
             devices) -> dict:
    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    lower, upper = [], []
    for i, seed in enumerate(seeds):
        gen = generator(traffic)(cfg, traffic, seed, devices)
        if traffic["generator"] == "rollout":
            gen.setup()
        gen.step(0)
        gen.release()
        numbers, failed = gen.check()
        lower.append({"seed": seed, "failed": failed, **numbers})
        print(json.dumps(lower[-1]), flush=True)
        if i < controls:
            ctl = (rollout_control(gen) if traffic["generator"] == "rollout"
                   else sweep_control(gen))
            upper.append({"seed": seed, **ctl})
            print("control", json.dumps(upper[-1]), flush=True)
    names = [k for k in lower[0] if k not in ("seed", "failed")]
    return {"workload": workload,
            "lower": {k: max(r[k] for r in lower) for k in names},
            "upper": {k: min(r[k] for r in upper) for k in names}
            if upper else {},
            "failed": sum(r["failed"] for r in lower),
            "program": lower, "control": upper}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first", type=int, default=2_500_000_000)
    args = ap.parse_args()
    root = HERE.parent
    bench = Bench.load(root)
    chips = bench.workload(args.workload)["chips"]
    os.environ["REPRO_ENGINE_BACKEND"] = "numpy"
    from snowbench.cache import use_compile_cache

    use_compile_cache(root)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("calibrate.py: needs a TPU with the cell's chips",
              file=sys.stderr)
        return 2
    out = readings(bench, args.workload,
                   range(args.first, args.first + args.seeds),
                   args.controls, devices[:chips])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
