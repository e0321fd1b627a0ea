"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell sits in a file of its own, found by name:

* ``bench/configs/<config>.json`` — the configuration as it is run (the
  manifest's ``file`` key names it);
* ``bench/traffic/<traffic>.json`` — the traffic mix, read by the
  generator module its ``generator`` key names (``snowbench.sweep`` or
  ``snowbench.rollout``);
* ``bench/metrics/<metric>.py`` — a reader with ``read(view)`` that
  returns the metric's value from a :class:`~snowbench.trace.TraceView`,
  or None where the trace holds nothing for it;
* ``bench/limits/<workload>.json`` — the limit of every number the
  cell's correctness check compares, with the readings it was set from.

A later change adds a cell, a configuration, a traffic mix or a metric as
new files and new manifest entries, and edits none of these.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
#: the keys of each entry; a metric may add ``workloads``
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@dataclass
class Bench:
    """The manifest and the directory that holds the benchmark's files."""

    root: Path            #: the checkout: where ``BENCHMARK.json`` lies
    home: Path            #: the benchmark's own directory
    doc: dict

    @classmethod
    def load(cls, root: Path) -> "Bench":
        root = Path(root)
        doc = json.loads((root / "BENCHMARK.json").read_text())
        return cls(root, root / "bench", doc)

    # -- lookups by name ------------------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.home / "traffic" / f"{name}.json").read_text())

    def limits(self, workload: str) -> Dict[str, float]:
        doc = json.loads((self.home / "limits" / f"{workload}.json")
                         .read_text())
        return {k: float(v["limit"]) for k, v in doc.items()}

    def reader(self, metric: str):
        path = self.home / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "snowbench_metric_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    # -- which metrics a cell reports ----------------------------------
    def end_to_end(self, workload: str) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[dict]:
        reported = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.doc["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]


def problems(doc: dict, root: Path) -> List[str]:
    """What keeps ``doc`` from the manifest contract's shape: the keys of
    each entry, names and units from their character sets, a source of
    its own for each configuration, unique names, files where they are
    said to be, every per-layer metric's ``moves`` reported in its
    cells, and at most one cell in two on four chips."""
    out: List[str] = []
    if set(doc) != TOP_KEYS:
        out.append(f"top-level keys {sorted(doc)}")
    seen = set()
    for group, keys in ENTRY_KEYS.items():
        for e in doc.get(group, []):
            extra = set(e) - keys - ({"workloads"} if group in (
                "end_to_end", "per_layer") else set())
            if keys - set(e) or extra:
                out.append(f"{group} entry {e.get('name')!r} has keys "
                           f"{sorted(e)}")
            if not NAME.match(e["name"]):
                out.append(f"{group} name {e['name']!r}")
            key = "metric" if group in ("end_to_end", "per_layer") else group
            if (key, e["name"]) in seen:
                out.append(f"duplicate {key} name {e['name']!r}")
            seen.add((key, e["name"]))
            if "unit" in e and not UNIT.match(e["unit"]):
                out.append(f"unit {e['unit']!r} of {e['name']}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                out.append(f"better {e['better']!r} of {e['name']}")
    names = {c["name"] for c in doc["configs"]}
    for c in doc["configs"]:
        if not (root / c["file"]).is_file():
            out.append(f"config file {c['file']} missing")
        for key in c["reduced"]:
            if not NAME.match(key):
                out.append(f"reduced key {key!r}")
    if len({c["source"] for c in doc["configs"]}) < len(doc["configs"]):
        out.append("two configurations share a source")
    used = {w["config"] for w in doc["workloads"]}
    if names - used:
        out.append(f"configs used by no cell: {sorted(names - used)}")
    pairs = set()
    for w in doc["workloads"]:
        if w["config"] not in names:
            out.append(f"cell {w['name']} names unknown config")
        if not NAME.match(w["traffic"]):
            out.append(f"traffic name {w['traffic']!r}")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"config and traffic of {w['name']} repeat")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"chips {w['chips']} of {w['name']}")
        if len(w["why"]) > 200 or "\n" in w["why"]:
            out.append(f"why of {w['name']}")
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    if four > max(1, len(doc["workloads"]) // 2):
        out.append(f"{four} cells on four chips")
    cells = [w["name"] for w in doc["workloads"]]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for m in doc["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"source {m['source']} of {m['name']}")
        if not 0.01 <= m["bound"] <= 0.25:
            out.append(f"bound {m['bound']} of {m['name']}")

    def reports(cell: str, metric: str) -> bool:
        return cell in e2e[metric].get("workloads", cells)

    for cell in cells:
        if sum(reports(cell, m) for m in e2e if m != "setup_s") < 1:
            out.append(f"cell {cell} reports no end-to-end metric")
    for m in doc["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        for cell in m.get("workloads", cells):
            if cell not in cells or not reports(cell, m["moves"]):
                out.append(f"{m['name']}: cell {cell} does not report "
                           f"{m['moves']}")
        if "\n" in m["layer"] or not 1 <= len(m["layer"]) <= 200:
            out.append(f"layer of {m['name']}")
    for cell in cells:
        if not any(cell in m.get("workloads", cells)
                   for m in doc["per_layer"]):
            out.append(f"cell {cell} reports no per-layer metric")
    return out
