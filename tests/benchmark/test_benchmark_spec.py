"""``BENCHMARK.json`` against the contract it was written to: the driver
refuses the file before any run for most of these, so they are checked here
first. Also that every name in it resolves to a file of the benchmark."""

import json
import os
import re

import pytest

from benchmark import harness

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def _under_paths(path):
    return any(path == p or path.startswith(p + "/") for p in SPEC["paths"])


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells must fit: 2 + 14 * cells runs
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PLAIN_PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(isinstance(c, str) for c in cmd)
    for c in cmd:
        assert not c.startswith("/") and ".." not in c
        if os.path.exists(os.path.join(ROOT, c)):
            assert _under_paths(c), f"the command names {c}, outside paths"


def test_names_are_plain_and_used_once():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in SPEC[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(len(e["why"]) <= 200 for key in ("configs", "workloads")
               for e in SPEC[key])


def test_every_file_under_paths_has_a_plain_name():
    for p in SPEC["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PLAIN_PATH.match(rel), rel


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert _under_paths(config["file"])
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    assert any(w["config"] == config["name"] for w in SPEC["workloads"])
    files = [c["file"] for c in SPEC["configs"]]
    assert files.count(config["file"]) == 1
    for key in config["reduced"]:  # never a width
        assert not re.search(r"(_dim$|_rank$|_size$|experts_per_tok|expansion)",
                             key), key
    # the plain reference and the shape function the file names exist
    harness.find_file(ROOT, "benchmark", "reference", body["reference"] + ".py")
    harness.find_file(ROOT, "benchmark", "flops", body["flops"]["module"] + ".py")


def test_workloads_table():
    cells = SPEC["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}


def test_metric_tables():
    e2e, layers = SPEC["end_to_end"], SPEC["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1 and m["better"] in ("higher", "lower")
    setup = [m for m in e2e if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] == 0.1 and "workloads" not in setup[0]
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["better"] in ("higher", "lower")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layers:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_and_reports_enough(name):
    """Every name of the cell is a file the harness finds, every ``moves`` is
    an end-to-end metric this cell reports, and the cell reports set-up,
    another end-to-end metric and a per-layer metric."""
    cell = harness.load_cell(name)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    harness.find_file(ROOT, "benchmark", "jobs", cell.job + ".py")
    for m in cell.per_layer:
        assert m["moves"] in reported
        reader = harness.load_module(ROOT, "layer_metrics", m["name"])
        assert callable(reader.read) and reader.__doc__
    applicable = [m for m in SPEC["per_layer"]
                  if name in m.get("workloads", CELLS)]
    assert [m["name"] for m in applicable] == [m["name"] for m in cell.per_layer]


def test_every_reader_file_is_listed():
    listed = {m["name"] for m in SPEC["per_layer"]}
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    on_disk = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    assert on_disk == listed


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        harness.peaks_for("TPU v9 imaginary")
