"""Lint of BENCHMARK.json against the contract's character and
cross-reference rules, and of the files it names."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[kind]]
        assert len(names) == len(set(names)), kind
        assert all(NAME.match(n) for n in names), kind
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(m)) < 64 * 1024


def test_cross_references():
    m = manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"] for c in m["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    e2e = {e["name"]: set(e.get("workloads", cells)) for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == set(cells)
    for name, w in cells.items():
        assert any(name in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(name in p.get("workloads", cells) for p in m["per_layer"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        for c in p.get("workloads", cells):
            assert c in e2e[p["moves"]], (p["name"], c)


def test_files_found_by_name():
    m = manifest()
    for c in m["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cf = json.load(f)
        assert cf["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "reference", cf["reference"]["model"] + ".py"))
    for w in m["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "runners", cell["runner"] + ".py"))
    for p in m["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", p["name"] + ".py")), p["name"]
    for dirpath, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in dirpath or "/out" in dirpath:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
