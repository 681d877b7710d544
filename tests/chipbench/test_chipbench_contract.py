"""BENCHMARK.json against the contract's rules for names, units and
shapes, and the benchmark's own layout: every name it gives resolves to
a file, and nothing under its paths imports the program's side of the
measurement. The rules themselves are ``benchmark_rules.py``'s, which
``test_chipbench_cells.py`` also holds a grown copy to."""

import ast
import json
import re
from pathlib import Path

import benchmark_rules as rules
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("rule", rules.STRUCTURE, ids=lambda f: f.__name__)
def test_benchmark_json_keeps(rule):
    rule(BENCH, ROOT)


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
