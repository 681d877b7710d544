"""The deployment ``priority-5000`` and its cell
``priority-5000.preempt-wave`` added to a copy of the benchmark as the
next ``model_config`` PR will add them to the real one: new files in
the directories the harness looks in, entries appended to
``BENCHMARK.json``, no file that was there edited. The files are this
directory's; ``tests/chipbench/test_chipbench_checks.py`` grows a copy
in tier-1 at rehearsal size, ``run.py`` grows one on the chip at
``Preemption/5000``'s size. Nothing of this is in the benchmark: the
cell waits for its reference (PERF.md section 7)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIG, MIX = "priority-5000", "preempt-wave"
CELL = f"{CONFIG}.{MIX}"
#: this directory's file -> where the harness looks for it
PLACES = {
    f"{CONFIG}.json": f"chipbench/configs/{CONFIG}.json",
    f"{MIX}.json": f"chipbench/traffic/{MIX}.json",
    "preempt_waves.py": "chipbench/generators/preempt_waves.py",
    "evictions.py": "chipbench/checks/evictions.py",
}


def copy_benchmark(root: Path, copy: Path) -> dict:
    """``BENCHMARK.json`` and the directories under its ``paths`` into
    ``copy``; returns the copy's files as they were, for
    ``edited_files``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    copy.mkdir(parents=True)
    shutil.copy(root / "BENCHMARK.json", copy)
    for base in bench["paths"]:
        shutil.copytree(root / base, copy / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return {
        p: p.read_bytes() for p in copy.rglob("*")
        if p.is_file() and p.name != "BENCHMARK.json"
    }


def edited_files(before: dict) -> list:
    return [str(p) for p, body in before.items()
            if not p.is_file() or p.read_bytes() != body]


def cannot_report(copy: Path) -> set:
    """The per-layer metrics whose file says that only the chip's trace
    has their kernel (``on_chip_only``: their pattern and bytes are of
    other cells' kernels) or what a cell must have (``needs``)."""
    specs = (json.loads(p.read_text())
             for p in (copy / "chipbench/layer_metrics").glob("*.json"))
    return {s["name"] for s in specs if s.get("on_chip_only") or "needs" in s}


def add_cell(copy: Path) -> dict:
    """Place the files, append the configuration and the cell, and
    append the cell to every ``workloads`` list but those of the metrics
    it ``cannot_report``. Returns the copy's ``BENCHMARK.json`` as
    written."""
    skip = cannot_report(copy)
    for name, place in PLACES.items():
        assert not (copy / place).exists(), place
        shutil.copy(HERE / name, copy / place)
    config = json.loads((HERE / f"{CONFIG}.json").read_text())
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": CONFIG, "source": config["source"],
        "file": PLACES[f"{CONFIG}.json"], "reduced": config["reduced"],
        "why": "a full cluster and waves that fit only by eviction: the "
               "preemption kernel, its waves and the victims' deletes",
    })
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": "closed waves of 1,000 priority-100 pods of 3000m / 6Gi onto "
               "5,000 nodes filled by 50,000 priority-0 pods: one eviction "
               "a preemptor, fillers put back between waves",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] not in skip:
            m["workloads"].append(CELL)
    (copy / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return bench
