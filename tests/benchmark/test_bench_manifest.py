"""BENCHMARK.json: every name it gives is found, and its shape holds."""
import json
import re
from pathlib import Path

import pytest

from benchmarks.chip import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert (ROOT / p).is_dir()
    for word in MANIFEST["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_names_units_and_entry_keys():
    names = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in names


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in MANIFEST["workloads"]
                                    if w["name"] == cell)
    assert harness._code(c, "reference", c.config["reference"]).exists()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s",
                                                  "train_tokens_per_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert harness._code(c, "metrics", m["name"]).exists()
    assert set(c.limits) == {"change1_gap", "changeN_gap",
                             "changeN_median_gap", "inactive_moved"}


def test_config_files_are_unique_and_under_paths():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in MANIFEST["paths"])
        assert (ROOT / f).exists()
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}


def test_per_layer_metrics_list_cells_that_report_what_they_move():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        reporting = moved.get("workloads", CELLS)
        assert set(m["workloads"]) <= set(reporting), m["name"]
        assert m["layer"] and "\n" not in m["layer"]


def test_a_fixture_cell_is_found_by_name(fixtures):
    """A cell is files and entries: the fixture's own manifest, config,
    traffic and limits, with the harness's reference and readers."""
    c = harness.load_cell("tiny.t2", fixtures / "BENCHMARK.json", fixtures)
    assert c.traffic["seq"] == 32 and c.config["model"]["d_model"] == 64
    assert [m["name"] for m in c.per_layer] == ["step_mfu"]
    assert harness._code(c, "metrics", "step_mfu") == (
        harness.BENCH / "metrics" / "step_mfu.py")
    with pytest.raises(KeyError):
        harness.load_cell("no.such", fixtures / "BENCHMARK.json", fixtures)
