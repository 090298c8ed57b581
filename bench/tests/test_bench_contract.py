"""BENCHMARK.json against the rules the benchmark's file keeps to, and
every name in it found on disk."""
import json
import re
from pathlib import Path

import pytest

from bench import loadgen

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT_KEYS = ("why", "layer", "source")
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_top_level_keys_and_command():
    assert list(BM) == ["command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


def test_a_full_check_fits_its_time_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BM["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_text():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[group]]
        assert len(set(names)) == len(names)
        for e in BM[group]:
            assert NAME.match(e["name"]), e["name"]
            for k in TEXT_KEYS:
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], (e["name"], k)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_configurations():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]
        assert c["reduced"] == []
        assert any(w["config"] == c["name"] for w in BM["workloads"])


def test_workloads():
    configs = {c["name"] for c in BM["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(
        1, len(BM["workloads"]) // 2)
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        loadgen.validate(json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()))


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in \
        e2e["setup_s"]
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    cells = {w["name"] for w in BM["workloads"]}
    for cell in cells:
        reported = [m for m in BM["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2


def test_per_layer_metrics():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m["workloads"] for m in BM["per_layer"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    from bench.harness import load_module

    reader = load_module(ROOT / "bench" / "metrics" / f"{metric}.py",
                         f"reader_{metric}")
    assert callable(reader.read)
