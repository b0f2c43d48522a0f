"""BENCHMARK.json held to the rules of form the driver refuses it by before
any run (PR 41 was refused once for a configuration's `why` of 217
characters): names, units, one-line texts of at most 200 characters, the keys
an entry may have, and that what an entry points at exists."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def one_line(text):
    return 1 <= len(text) <= 200 and all(32 <= ord(c) < 127 for c in text)


def entries(key):
    return [pytest.param(e, id=e["name"]) for e in BENCH[key]]


@pytest.mark.parametrize("config", entries("configs"))
def test_a_configuration_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert one_line(config["source"]) and one_line(config["why"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(ROOT, config["file"]))


@pytest.mark.parametrize("cell", entries("workloads"))
def test_a_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    assert cell["chips"] in (1, 4)
    assert one_line(cell["why"])
    end_to_end = [m for m in BENCH["end_to_end"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in {m["name"] for m in end_to_end} and len(end_to_end) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", entries("end_to_end") + entries("per_layer"))
def test_a_metric_entry(metric):
    per_layer = "layer" in metric
    keys = ({"name", "unit", "better", "source", "layer", "moves"} if per_layer
            else {"name", "unit", "better", "bound", "source"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (SOURCES if per_layer else SOURCES[:1] + SOURCES[3:])
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if per_layer:
        assert one_line(metric["layer"])
        moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric.get("workloads", [])) <= set(moved.get("workloads", cells))


def test_the_file_as_a_whole():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics)) and len(BENCH["per_layer"]) <= 128
    assert all(one_line(word) for word in BENCH["command"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    cells = len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, cells // 4)
    seconds = BENCH["run_seconds"] + 60
    assert (2 + 14 * cells) * seconds + 2 * 90 * cells + 1200 <= 43200
