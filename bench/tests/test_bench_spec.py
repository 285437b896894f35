"""BENCHMARK.json against the files the harness finds by name, and the
names the contract allows."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, traffic

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_whys_and_sources_fit_one_line():
    for entry in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in SPEC["configs"]:
        assert 1 <= len(c["source"]) <= 200


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_loads(w):
    cell = harness.load_cell(w["name"], ROOT)
    assert isinstance(cell.mix, traffic.Mix)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert cell.limits
    for name, limit in cell.limits.items():
        assert name in harness.gap_stats([[0.0]])
        # set between the sound runs' largest reading and the control's
        # smallest, which is at least three times it
        assert limit["lower"] < limit["limit"] < limit["upper"]
        assert limit["upper"] >= 3 * limit["lower"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_each_metric_has_a_reader_and_a_legal_name(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(harness.reader(m["name"]))
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_each_config_file_states_its_cut(c):
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["source"] == c["source"]
    assert conf["reduced"] == c["reduced"]
    assert (ROOT / "bench" / "references" /
            f"{conf['reference']}.py").exists()
