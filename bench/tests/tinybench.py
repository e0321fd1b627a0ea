"""A copy of the benchmark with tiny cells added as new files, for CPU tests.

``make(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp`` and
adds, as new files and new manifest entries only, one small Snow fleet
configuration, one small RWKV-6 tree, a cell for each traffic mix and the
limits of the matching full-size cell.  Nothing existing is edited.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: tiny cell -> (configuration, traffic, chips, full-size cell whose
#: limits it takes)
CELLS = {
    "tiny-breakdown": ("snow-tiny", "breakdown", 1, "breakdown-1m"),
    "tiny-stable-coloring": ("snow-tiny", "stable-coloring", 1,
                             "stable-coloring-1m"),
    "tiny-fanout": ("rwkv6-tiny", "ckpt-rollout", 4, "ckpt-fanout-4chip"),
}


def tiny_configs() -> dict:
    snow = json.loads((BENCH / "configs/snow-s5-1m.json").read_text())
    snow.update(name="snow-tiny", n=3000, n_messages=12, seeds_per_query=3,
                reduced=["n", "n_messages", "seeds_per_query"],
                source="https://arxiv.org/abs/2504.02676 section 5, at "
                "3,000 members for a CPU rehearsal")
    rwkv = json.loads(
        (BENCH / "configs/rwkv6-ckpt-fanout-4chip.json").read_text())
    rwkv.update(name="rwkv6-tiny", num_hidden_layers=2, hidden_size=64,
                attention_hidden_size=64, intermediate_size=224,
                vocab_size=512, head_size=16, time_mix_extra_dim=4,
                time_decay_extra_dim=8,
                source="https://huggingface.co/RWKV/v6-Finch-1B6-HF, cut to "
                "2 layers of width 64 for a CPU rehearsal",
                reduced=["num_hidden_layers", "hidden_size",
                         "attention_hidden_size", "intermediate_size",
                         "vocab_size", "head_size", "time_mix_extra_dim",
                         "time_decay_extra_dim"])
    return {"snow-tiny": snow, "rwkv6-tiny": rwkv}


def make(tmp: Path) -> Path:
    """The copy, with the tiny cells added; returns its root."""
    tmp = Path(tmp)
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, cfg in tiny_configs().items():
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(cfg))
        doc["configs"].append({"name": name, "source": cfg["source"],
                               "file": path, "reduced": cfg["reduced"],
                               "why": "a CPU rehearsal at a tiny size"})
    for cell, (cfg, traffic, chips, full) in CELLS.items():
        doc["workloads"].append({"name": cell, "config": cfg,
                                 "traffic": traffic, "chips": chips,
                                 "why": "a CPU rehearsal at a tiny size"})
        shutil.copy(tmp / f"bench/limits/{full}.json",
                    tmp / f"bench/limits/{cell}.json")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if full in m.get("workloads", []):
                m["workloads"].append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return tmp
