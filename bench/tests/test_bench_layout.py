"""Every cell and metric of BENCHMARK.json resolves to its files, and the
file keeps the benchmark contract's shape.  Reads files only: no JAX."""

import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [c["name"] for c in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert _one_line(word) and not word.startswith("/") \
            and ".." not in word
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_command_names_files_under_paths():
    files = [w for w in SPEC["command"] if os.sep in w or w.endswith(".py")]
    assert files
    for w in files:
        assert any(w.startswith(p.rstrip("/") + "/") for p in SPEC["paths"])
        assert os.path.isfile(os.path.join(ROOT, w))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert set(c) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(c["name"]) and NAME.match(c["traffic"])
    assert c["chips"] in (1, 4) and _one_line(c["why"])
    conf = next(x for x in SPEC["configs"] if x["name"] == c["config"])
    path = os.path.join(ROOT, conf["file"])
    with open(path) as f:
        cfg = json.load(f)
    assert cfg["chips"] == c["chips"]
    assert set(cfg["limits"]) == {"residual", "greedy_gap"}
    with open(os.path.join(BENCH, "traffic", c["traffic"] + ".json")) as f:
        kind = json.load(f)["kind"]
    assert os.path.isfile(os.path.join(BENCH, "drivers", kind + ".py"))
    # every cell reports setup_s, one more end-to-end metric and one
    # per-layer metric
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if cell in m.get("workloads", CELLS)]
    assert "setup_s" in e2e and len(e2e) >= 2
    # a per-layer metric with no list reads in every cell that reports
    # the metric it moves
    assert any(cell in m.get("workloads", []) or
               ("workloads" not in m and m["moves"] in e2e)
               for m in SPEC["per_layer"])


@pytest.mark.parametrize("conf", [c["name"] for c in SPEC["configs"]])
def test_config_resolves(conf):
    c = next(x for x in SPEC["configs"] if x["name"] == conf)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and _one_line(c["source"])
    assert c["file"].startswith("bench/") and c["file"].endswith(".json")
    with open(os.path.join(ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == conf and cfg["source"] == c["source"]
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    assert all(NAME.match(k) for k in c["reduced"])
    assert any(conf == w["config"] for w in SPEC["workloads"])
    files = [x["file"] for x in SPEC["configs"]]
    assert files.count(c["file"]) == 1


@pytest.mark.parametrize("conf", [c["name"] for c in SPEC["configs"]])
def test_config_names_a_family_file(conf):
    """The solve driver builds the family that the configuration's
    ``family`` names, ``bench/families/<family>.py``, which defines
    ``table``."""
    c = next(x for x in SPEC["configs"] if x["name"] == conf)
    with open(os.path.join(ROOT, c["file"])) as f:
        family = json.load(f)["family"]
    assert NAME.match(family)
    with open(os.path.join(BENCH, "families", family + ".py")) as f:
        assert re.search(r"^def table\(", f.read(), re.M)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_shape(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _one_line(m["layer"])
        moved = next(x for x in SPEC["end_to_end"] if x["name"] == m["moves"])
        for cell in m.get("workloads", moved.get("workloads", CELLS)):
            assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_reader_resolves_and_reads_nothing_from_nothing(metric):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read({}) is None


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
