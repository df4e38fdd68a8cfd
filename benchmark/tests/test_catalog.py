"""Every file the harness finds by name loads and says what the
contract allows; BENCHMARK.json and the files agree."""
import json
import os
import re

import pytest

from benchmark import catalog as cat

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
C = cat.Catalog()


def test_benchmark_json_keys_and_limits():
    doc = C.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cat.ROOT, "BENCHMARK.json")) < 65536
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 2)
    assert "setup_s" in C.end_to_end
    for m in C.end_to_end.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}


@pytest.mark.parametrize("entry", C.doc["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and len(entry["source"]) <= 200
    assert entry["file"].startswith("benchmark/")
    conf = C.config(entry["name"])
    assert conf["name"] == entry["name"]
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    for key in ("dataset", "entry", "chips", "rows", "segments",
                "guarantees", "assumed", "deployment"):
        assert key in conf
    assert conf["rows"] % conf["segments"] == 0
    cat.entry(conf["entry"])
    cat.dataset(conf["dataset"])


@pytest.mark.parametrize("cell", C.doc["workloads"], ids=lambda w: w["name"])
def test_cell_and_its_mix(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    conf = C.config(cell["config"])
    assert conf["chips"] == cell["chips"]
    mix = C.traffic(cell["traffic"])
    assert mix["clients"] >= 1
    shapes = cat.dataset(conf["dataset"])["statements"].load_shapes(
        os.path.join(cat.HERE, mix["statements"]))
    assert set(mix.get("shapes") or shapes) <= set(shapes)
    reported = {m["name"] for m in C.metrics_for(cell["name"], False)}
    assert "setup_s" in reported and len(reported) >= 2
    assert C.metrics_for(cell["name"], True)


ALL_METRICS = list(C.end_to_end.values()) + list(C.per_layer.values())


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert callable(C.reader(m["name"]))
    for w in m.get("workloads", []):
        assert w in C.cells
    if m["name"] in C.per_layer:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        moved = C.end_to_end[m["moves"]]
        # every cell that reports this metric reports what it moves
        for w in m.get("workloads", C.cells):
            assert "workloads" not in moved or w in moved["workloads"]


def test_every_file_under_the_paths_is_named_from_allowed_characters():
    for base, _dirs, files in os.walk(cat.HERE):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), cat.ROOT)
            assert PATH.match(rel), rel


def test_every_metric_file_has_an_entry_and_peaks_have_a_source():
    listed = set(C.end_to_end) | set(C.per_layer)
    on_disk = {f[:-5] for f in os.listdir(os.path.join(cat.HERE, "metrics"))}
    assert listed == on_disk
    with open(os.path.join(cat.HERE, "peaks.json")) as f:
        for kind, p in json.load(f)["peaks"].items():
            assert p["source"] and p["hbm_bytes_per_s"] > 0, kind
    with pytest.raises(RuntimeError):
        cat.peak("no such device")
