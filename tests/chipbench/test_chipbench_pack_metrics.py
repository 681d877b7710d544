"""The three per-layer metrics of pack's incremental inputs (PR 25):
``pack_snapshot_ms_per_batch``, ``pack_families_ms_per_batch`` and
``pack_mask_rows_reused_share`` are files under ``layer_metrics/`` read
by readers the benchmark had, and since PR 26 entries of
``BENCHMARK.json``, appended after PR 24's block, for the three cells
they were read in. A traced rehearsal reads all three; a program from
before the spans gives the readers nothing, and they raise nothing."""

import functools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench.readers import span_stat_ratio, stage_per_batch

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = ["pack_snapshot_ms_per_batch", "pack_families_ms_per_batch",
         "pack_mask_rows_reused_share"]


spec_of = functools.partial(rules.spec_of, ROOT)


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_declared_for_the_cells_it_was_read_in(name):
    """Not a file that waits for its entry, as in PR 25."""
    listed = [m["name"] for m in BENCH["per_layer"]]
    assert listed.index(name) > listed.index(rules.NEW[-1])  # appended
    entry = BENCH["per_layer"][listed.index(name)]
    assert set(rules.FIRST_CELLS) <= set(entry["workloads"])
    spec = spec_of(name)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in
        ("name", "unit", "better", "source", "layer", "moves")
    }


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_a_file_for_a_reader_that_was_there(name):
    spec = spec_of(name)
    assert spec["name"] == name and spec["layer"] == "pack"
    assert spec["moves"] == "pod_to_bind_p50_ms"
    assert spec["source"] == "program_span"
    assert spec["reader"] in ("stage_per_batch", "span_stat_ratio")
    assert (ROOT / "chipbench" / "readers" / f"{spec['reader']}.py").is_file()
    assert "roofline" not in name and "mfu" not in name


def test_a_program_without_the_spans_gives_nothing_and_raises_nothing():
    sample = {
        "start": {"batches": 0, "stage_seconds": {"pack": 0.0}},
        "end": {"batches": 10, "stage_seconds": {
            "pack": 1.5, "pack.state": 0.4, "pack.masks": 0.2}},
    }
    for name in NAMES[:2]:
        assert stage_per_batch.read(sample, spec_of(name)["args"]) is None
    sample["end"]["stage_seconds"].update(
        {"pack.snapshot": 0.03, "pack.families": 0.002}
    )
    assert stage_per_batch.read(
        sample, spec_of(NAMES[0])["args"]) == pytest.approx(3.0)
    assert stage_per_batch.read(
        sample, spec_of(NAMES[1])["args"]) == pytest.approx(0.2)
    # PR 24's ``sched/pack.masks`` spans carry a batch id and no rows
    args = spec_of(NAMES[2])["args"]
    old = {"window": (0, 100), "spans": [
        {"name": "sched/pack.masks", "start": 10 * k, "end": 10 * k + 5,
         "line": ("/host:CPU", 0), "stats": {"batch": k}} for k in range(5)
    ]}
    assert span_stat_ratio.ratio(old, args) is None
    for k, sp in enumerate(old["spans"]):
        sp["stats"].update(rows=2, rows_reused=2 if k else 0)
    assert span_stat_ratio.ratio(old, args) == pytest.approx(0.8)


def test_a_traced_rehearsal_reads_all_three(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(ROOT / "chipbench", copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", copy)  # a root of its own to trace in
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload",
         "basic-5000.burst-10k", "--seed", str(2**31 + 25), "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert set(NAMES) <= set(metrics)
    assert metrics[NAMES[0]]["unit"] == metrics[NAMES[1]]["unit"] == "ms"
    assert metrics[NAMES[0]]["value"] > 0 and metrics[NAMES[1]]["value"] > 0
    parts = sum(metrics[name]["value"] for name in (
        NAMES[0], NAMES[1], "pack_state_ms_per_batch",
        "pack_pods_ms_per_batch", "pack_masks_ms_per_batch",
    ))
    assert parts <= metrics["pack_ms_per_batch"]["value"]
    # plain pods on nodes nothing relabels: after the first batch every
    # row is handed out again
    assert metrics[NAMES[2]]["unit"] == "ratio"
    assert 0.5 < metrics[NAMES[2]]["value"] <= 1.0
