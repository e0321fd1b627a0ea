"""What the harness finds by name, and what ``run.py`` refuses."""
import json
import os
import shutil
import subprocess
import sys

import tinybench
from snowbench import harness
from snowbench.manifest import Bench, problems


def _run_py(root, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "breakdown-1m",
         "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        cwd=root, capture_output=True, text=True, env=env, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_run_refuses_a_cpu_and_prints_no_result():
    p = _run_py(tinybench.REPO)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_run_without_the_program_fails_and_prints_no_result(tmp_path):
    shutil.copy(tinybench.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tinybench.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_new_config_traffic_and_metric_are_found_as_new_files(tmp_path):
    """A later change adds a configuration, a traffic mix, a per-layer
    metric and a cell as new files and new manifest entries only."""
    root = tinybench.make(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = tinybench.tiny_configs()["snow-tiny"]
    cfg.update(name="snow-dummy", n=1500, reduced=["n"],
               source="https://arxiv.org/abs/2504.02676 at 1,500 members")
    (root / "bench/configs/snow-dummy.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "bench/traffic/stable-coloring.json")
                         .read_text())
    traffic["why"] = "a dummy mix added as a file"
    (root / "bench/traffic/dummy-mix.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/dummy_units.py").write_text(
        "def read(view):\n    return float(view.units)\n")
    (root / "bench/limits/dummy-cell.json").write_text(
        (root / "bench/limits/stable-coloring-1m.json").read_text())
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "snow-dummy", "source": cfg["source"],
                           "file": "bench/configs/snow-dummy.json",
                           "reduced": ["n"], "why": "a dummy fleet"})
    doc["workloads"].append({"name": "dummy-cell", "config": "snow-dummy",
                             "traffic": "dummy-mix", "chips": 1,
                             "why": "added with no edit to a file"})
    doc["end_to_end"][0]["workloads"].append("dummy-cell")
    doc["per_layer"].append({"name": "dummy_units", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "harness", "moves": "sweep_rate",
                             "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert [p for p, b in before.items() if p.read_bytes() != b] == []
    bench = Bench.load(root)
    assert problems(bench.doc, root) == []
    import jax

    cpu = jax.devices("cpu")[:1]
    out = harness.run(bench, "dummy-cell", 1234, 0.1, False, cpu)
    assert out["correct"] and set(out["metrics"]) == {"sweep_rate",
                                                      "setup_s"}
    traced = harness.run(bench, "dummy-cell", 1234, 0.1, True, cpu)
    assert traced["metrics"]["dummy_units"]["value"] == traced["attempted"]


def test_since_start_counts_from_process_start():
    assert 0 < harness.since_start() < 24 * 3600
