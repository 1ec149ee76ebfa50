"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix; a configuration names a
model family.  Each is a file of its own (``configs/<name>.json``,
``jobs/<mix>.json``, ``families/<family>.py``, ``layers/<metric>.py``),
so a later PR adds one by adding files and an entry and edits nothing
that is here.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Callable, List, Optional

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_file() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration as it is run
    mix: dict  # the traffic mix
    family: Any  # the family's adapter module
    end_to_end: List[dict]  # this cell's entries of BENCHMARK.json
    per_layer: List[dict]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, rehearsal: bool = False,
              bench: Optional[dict] = None) -> Cell:
    bench = bench or benchmark_file()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = _load_json(os.path.join(HERE, "jobs", entry["traffic"] + ".json"))
    if rehearsal:
        # The tiny sizes of the CPU rehearsal live beside the real ones.
        config = {**config, **config.get("rehearsal", {})}
        mix = {**mix, **mix.get("rehearsal", {})}
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    return Cell(
        name=name, chips=entry["chips"], config=config, mix=mix, family=family,
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=_for_cell(bench["per_layer"], name),
    )


def layer_reader(metric: str) -> Callable[[dict], Optional[float]]:
    """``benchmarks/layers/<metric>.py:read``, found by the metric's name."""
    return importlib.import_module(f"benchmarks.layers.{metric}").read
