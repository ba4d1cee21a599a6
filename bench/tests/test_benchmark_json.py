"""``BENCHMARK.json`` keeps the shape the harness reads, and every name in it
has its file: configuration, generator, traffic, metric reader, least count."""

import json
import os
import re

import pytest

from bench import harness

BM = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) <= 64 * 1024


@pytest.mark.parametrize("conf", BM["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and all(NAME.match(k) for k in conf["reduced"])
    cfg = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    assert cfg["name"] == conf["name"] and cfg["source"] == conf["source"]
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    gen = harness.load_module("configs", conf["name"])
    assert callable(gen.coefficients) and callable(gen.draw)
    assert os.path.exists(os.path.join(harness.BENCH, "counts", cfg["solver"] + ".py"))
    assert 0 < cfg["limits"]["true_residual"] < 1


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda c: c["name"])
def test_cells(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    c = harness.load_cell(cell["name"], BM)
    fx, fy = c.traffic["fabric"]
    assert fx * fy == cell["chips"]
    assert c.end_to_end and c.per_layer
    assert "setup_s" in {m["name"] for m in c.end_to_end}


def test_metrics():
    cells = {c["name"] for c in BM["workloads"]}
    e2e = {m["name"] for m in BM["end_to_end"]}
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert set(m.get("workloads", cells)) <= cells
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(harness.load_module("metrics", m["name"]).read)
