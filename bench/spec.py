"""Find a cell's files by name: ``BENCHMARK.json``'s entry, then
``bench/cells/<cell>.json``, ``bench/configs/<config>.json`` and
``bench/traffic/<mix>.json``."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    cell: dict        # engine settings, rate, correctness limit
    mc: dict          # model configuration
    mix: dict         # traffic mix
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    read = lambda p: json.loads((root / p).read_text())  # noqa: E731
    return Spec(
        name=workload, chips=entry["chips"],
        cell=read(f"bench/cells/{workload}.json"),
        mc=read(config["file"]),
        mix=read(f"bench/traffic/{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])
