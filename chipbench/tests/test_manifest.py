"""BENCHMARK.json against the benchmark's contract, and the harness's
lookup of a cell's files by name."""

import json
import re
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def test_top_level():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "chipbench/run.py"]
    assert MANIFEST["paths"] == ["chipbench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 2 + 14 * 24 * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_entries_follow_the_rules(group):
    for e in MANIFEST[group]:
        assert set(e) <= ENTRY_KEYS[group], e["name"]
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["name"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        if group == "end_to_end":
            assert 0.01 <= e["bound"] <= 0.25
            assert e["source"] in ("host_clock", "device_trace")
        if group == "workloads":
            assert e["chips"] in (1, 4)
            assert NAME.match(e["config"]) and NAME.match(e["traffic"])
        if group == "configs":
            assert (ROOT / e["file"]).is_file()
            assert all(NAME.match(k) for k in e["reduced"])
            assert json.loads((ROOT / e["file"]).read_text())["reduced"] \
                == e["reduced"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve_cell(MANIFEST, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert set(c.readers) == e2e | {m["name"] for m in c.per_layer}
    for attr in ("setup", "unit", "compare", "wrap", "trace_count",
                 "UNIT_SPAN"):
        assert hasattr(c.driver, attr), attr
    assert c.traffic["limits"]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_each_cell_of_a_metric_reports_what_it_moves(metric):
    for cell in metric["workloads"]:
        assert cell in CELLS
        e2e, layer = harness.cell_metrics(MANIFEST, cell)
        assert metric["moves"] in {m["name"] for m in e2e}
        assert metric in layer


def test_every_config_is_used_by_a_cell():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}


FIXTURE_DRIVER = '''
UNIT_SPAN = "unit"


def wrap(spans):
    pass


def trace_count():
    return 0


def setup(config, traffic, seed, warm_seed):
    return dict(width=config["width"])


def unit(state, seed0):
    return dict(seed0=seed0, items=state["width"])


def compare(config, traffic, records, seed, control=False):
    return dict(item_gap=float(sum(r["items"] != config["width"]
                                   for r in records)))
'''


def test_a_cell_added_as_files_alone_runs(tmp_path):
    """A new cell needs new files only: config, traffic, driver and metric
    live in a directory of their own and are found by name."""
    for d in ("configs", "traffic", "drivers", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "toy.json").write_text('{"width": 3}')
    (tmp_path / "traffic" / "toy_mix.json").write_text(
        '{"kind": "toy", "limits": {"item_gap": 0}}')
    (tmp_path / "drivers" / "toy.py").write_text(FIXTURE_DRIVER)
    (tmp_path / "metrics" / "items_per_s.py").write_text(
        "def read(run):\n"
        "    return sum(r['items'] for r in run.records) / run.window_s\n")
    (tmp_path / "metrics" / "setup_s.py").write_text(
        "def read(run):\n    return run.setup_s\n")
    manifest = dict(
        workloads=[dict(name="toy.cell", config="toy", traffic="toy_mix",
                        chips=1, why="fixture")],
        end_to_end=[dict(name="items_per_s", unit="items/s",
                         better="higher", bound=0.1, source="host_clock"),
                    dict(name="setup_s", unit="s", better="lower",
                         bound=0.25, source="host_clock")],
        per_layer=[])
    cell = harness.resolve_cell(manifest, "toy.cell", tmp_path)
    import time
    out = harness.measure(cell, 2**31 + 3, 0.05, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          say=lambda line: None)
    assert out["correct"] is True
    assert out["metrics"]["items_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["item_gap"] == dict(value=0.0, limit=0.0)
