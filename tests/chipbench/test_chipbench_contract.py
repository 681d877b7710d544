"""BENCHMARK.json against the contract's rules for names, units and
shapes, and the benchmark's own layout: every name it gives resolves to
a file, and nothing under its paths imports the program's side of the
measurement."""

import ast
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(BENCH["command"]) <= 32 and all(line_ok(w) for w in BENCH["command"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in ("source", "cluster", "pod_classes", "wire", "guarantees",
                    "expect_tier", "assumed", "layout", "rehearsal"):
            assert key in body, (c["file"], key)
        assert len(body["source"]) <= 200


def test_workloads():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(names)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        mix = json.loads(
            (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").read_text()
        )
        assert (ROOT / "chipbench/generators" / f"{mix['generator']}.py").is_file()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(names) // 2)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line_ok(m["layer"])
        spec = json.loads(
            (ROOT / "chipbench/layer_metrics" / f"{m['name']}.json").read_text()
        )
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert (ROOT / "chipbench/readers" / f"{spec['reader']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:  # setup_s, one more end-to-end metric, one per layer
        mine = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in BENCH["per_layer"])


FORBIDDEN = re.compile(
    r"^\s*(from|import)\s+(bench|benchmarks|chip_smoke|tools|"
    r"kubernetes_tpu\.(ops|tensors|streaming))\b", re.M
)


def test_nothing_under_paths_imports_the_programs_side():
    for base in BENCH["paths"]:
        for path in (ROOT / base).rglob("*.py"):
            text = path.read_text()
            assert not FORBIDDEN.search(text), path
            # no run calls sched.warmup(): the only warmup() calls are the
            # traffic generators' own (generator.warmup / waves.warmup)
            for node in ast.walk(ast.parse(text)):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "warmup"):
                    owner = node.func.value
                    assert isinstance(owner, ast.Name) and owner.id in (
                        "generator", "waves"
                    ), (path, node.lineno)


@pytest.mark.parametrize("name", sorted(
    p.name for p in (ROOT / "chipbench").rglob("*") if p.is_file()
))
def test_file_names_are_made_of_name_characters(name):
    assert re.match(r"^[A-Za-z0-9_.\-]+$", name)
