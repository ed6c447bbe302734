"""BENCHMARK.json against the benchmark contract, and the files it names."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source", "bound"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            yield group, entry["name"]


@pytest.mark.parametrize("group,name", list(_names()))
def test_name_characters(group, name):
    assert NAME.match(name), (group, name)


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter",
                                "host_clock")
    if "bound" in metric:
        assert set(metric) <= METRIC_KEYS | {"workloads"}
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= LAYER_KEYS


def test_end_to_end_metrics():
    assert [m["name"] for m in MANIFEST["end_to_end"]] == [
        "tokens_per_s", "mfu", "peak_hbm_gib", "setup_s"]


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_files_found(cell):
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert (ROOT / configs[cell["config"]]["file"]).is_file()
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    assert (ROOT / "bench" / "limits" / f"{cell['name']}.json").is_file()
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200


def test_at_most_one_four_chip_cell():
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four in ([], ["granite-8b.dp2tp2.muonbp"])


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    assert metric["moves"] == "tokens_per_s"
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric["workloads"]) <= cells
    # Every listed cell reports the end-to-end metric this one moves.
    for e in MANIFEST["end_to_end"]:
        if e["name"] == metric["moves"]:
            assert set(metric["workloads"]) <= set(e.get("workloads", cells))


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in body["published"], key
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    widths = {"hidden_size": None, "intermediate_size": None, "head_dim": None}
    assert not set(widths) & set(config["reduced"])


def test_every_cell_reports_setup_and_a_layer_metric():
    for cell in MANIFEST["workloads"]:
        layer = [m for m in MANIFEST["per_layer"] if cell["name"] in m["workloads"]]
        assert layer, cell["name"]
