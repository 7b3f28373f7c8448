"""BENCHMARK.json holds what the harness finds by name: for every cell its
engine settings and traffic mix, for every configuration its file, for
every metric its reader; and every per-layer metric's cells report the
end-to-end metric it moves."""
import json
import re

import pytest

from bench import arch, report, spec
from bench.spec import BENCH, ROOT

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KEYS = {"workload": {"name", "config", "traffic", "chips", "why"},
        "config": {"name", "source", "file", "reduced", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert 1 <= B["run_seconds"] <= 51
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in B["end_to_end"])


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_found_by_name(w):
    assert set(w) == KEYS["workload"] and NAME.match(w["name"])
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    s = spec.load(w["name"])
    for key in ("n_slots", "block_size", "max_len", "mean_logit_gap_limit"):
        assert key in s.cell
    assert s.cell["max_len"] % s.cell["block_size"] == 0
    if s.mix["arrival"] == "poisson":
        assert s.cell["rate_per_s"] > 0
    assert any(m["name"] == "setup_s" for m in s.end_to_end)
    assert len(s.end_to_end) >= 2 and s.per_layer
    reported = {m["name"] for m in s.end_to_end}
    for m in s.per_layer:
        assert m["moves"] in reported


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    assert set(c) == KEYS["config"] and c["source"].startswith("https://")
    mc = json.loads((ROOT / c["file"]).read_text())
    assert mc["source"] == c["source"]
    assert sorted(mc["reduced"]) == sorted(c["reduced"])
    assert any(w["config"] == c["name"] for w in B["workloads"])
    assert (BENCH / "arch" / f"{mc['model_type']}.py").is_file()
    assert arch.load(mc).program_fields(mc)


@pytest.mark.parametrize("m", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    kind = "end_to_end" if "bound" in m else "per_layer"
    assert set(m) - {"workloads"} == KEYS[kind] and NAME.match(m["name"])
    assert m["better"] in ("lower", "higher")
    assert callable(report.module("metrics", m["name"]).read)
    for w in m.get("workloads", []):
        assert any(x["name"] == w for x in B["workloads"])
    if m["name"].endswith("_roofline"):
        kernel = m["name"][:-len("_roofline")]
        assert (BENCH / "counts" / f"{kernel}.py").exists()
        assert m["unit"] == "%"
