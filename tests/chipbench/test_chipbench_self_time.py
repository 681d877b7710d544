"""PR 53's twelve per-layer metrics (the issue's nine and one for each
child the chip's reading gave ``sched/dispatch``): the reader
``span_self_ms_per_span`` on hand-made traces, the entries held as one
contiguous run of ``per_layer`` with their cells by name, and a traced
rehearsal of a plain burst and of the gang cell whose kept trace has to
account for every parent span whole: what the reader calls its own time
and the spans directly inside it, counted another way, make its wall
clock."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench import program_spans
from chipbench.readers import span_self_ms_per_span as self_time

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
GANG = "gang-train-5000.gang-half-8k"
BURST = "basic-5000.burst-10k"
#: the twelve, in the order ``per_layer`` holds them: the total or the
#: span each reads, and its reader
NAMES = {
    "pack_aggregates_ms_per_batch": ("stage_per_batch", "pack.aggregates"),
    "pack_cluster_terms_ms_per_batch": (
        "stage_per_batch", "pack.cluster_terms"),
    "pack_overlay_ms_per_batch": ("stage_per_batch", "pack.overlay"),
    "pack_order_ms_per_batch": ("stage_per_batch", "pack.order"),
    "pack_self_ms_per_batch": ("span_self_ms_per_span", "sched/pack"),
    "dispatch_self_ms_per_batch": (
        "span_self_ms_per_span", "sched/dispatch"),
    "gang_siblings_ms_per_batch": ("stage_per_batch", "gang_siblings"),
    "gang_download_ms_per_batch": ("stage_per_batch", "gang_fixup.download"),
    "gang_fixup_self_ms_per_batch": (
        "span_self_ms_per_span", "sched/gang_fixup"),
    "dispatch_begin_ms_per_batch": (
        "span_ms_per_span", "sched/dispatch.begin"),
    "dispatch_handshake_ms_per_batch": (
        "span_ms_per_span", "sched/dispatch.handshake"),
    "dispatch_landed_ms_per_batch": (
        "span_ms_per_span", "sched/dispatch.landed"),
}
GANGS_OWN = [name for name in NAMES if name.startswith("gang_")]
#: the ten cells the benchmark had when they were declared; a later
#: cell may join an entry's list
TEN_CELLS = rules.SIX_CELLS + [
    "rolling-upgrade-5000.arrivals-roll-4",
    "gpu-binpack-5000.binpack-burst-6k",
    "image-locality-5000.arrivals-apps-48",
    "services-5000.rollout-5k",
]

# -- the reader ---------------------------------------------------------------

MS = 1_000_000  # the trace's clock is in ns


def span(name, start, end, line=0, **stats):
    return {"name": name, "start": start * MS, "end": end * MS,
            "line": ("/host:CPU", line), "stats": stats}


def trace(*spans, window=(0, 1000)):
    return {"window": (window[0] * MS, window[1] * MS), "spans": list(spans)}


PACK = {"span": "sched/pack", "per": "sched/dispatch"}


def test_a_parent_less_its_children_is_its_own_time():
    got = self_time.per_span(trace(
        span("sched/dispatch", 0, 120),
        span("sched/pack", 10, 110),
        span("sched/pack.state", 20, 50),
        span("sched/pack.families", 60, 80),
    ), PACK)
    assert got == pytest.approx(50.0)


def test_a_grandchild_inside_a_child_changes_nothing():
    spans = [
        span("sched/dispatch", 0, 120),
        span("sched/pack", 10, 110),
        span("sched/pack.state", 20, 50),
        span("sched/pack.families", 60, 80),
    ]
    nested = spans + [
        span("sched/pack.score", 62, 78),
        span("sched/pack.score.ipa", 65, 70),
        # a mark has no length and is no child
        span("sched/mark/recompile", 90, 90),
    ]
    assert self_time.per_span(trace(*nested), PACK) == pytest.approx(
        self_time.per_span(trace(*spans), PACK))


def test_a_span_of_another_line_inside_the_interval_is_not_taken_off():
    got = self_time.per_span(trace(
        span("sched/dispatch", 0, 120),
        span("sched/pack", 10, 110),
        span("sched/pack.state", 20, 50),
        span("sched/commit", 30, 100, line=1),  # the committer's thread
        # on the line, and not inside: it began before the parent
        span("sched/pop", 5, 15),
    ), PACK)
    assert got == pytest.approx(70.0)


def test_two_parents_over_three_dispatches_divide_by_three():
    got = self_time.per_span(trace(
        span("sched/dispatch", 0, 100),
        span("sched/pack", 0, 60),
        span("sched/pack.pods", 0, 30),
        span("sched/dispatch", 100, 200),
        span("sched/pack", 100, 160),
        span("sched/dispatch", 200, 210),  # routed before it packed
    ), PACK)
    assert got == pytest.approx((30.0 + 60.0) / 3)


def test_a_dispatch_inside_a_dispatch_counts_once():
    """A re-dispatch's span is the outer one's child like any other, and
    a parent of its own: nothing is counted twice."""
    args = {"span": "sched/dispatch", "per": "sched/dispatch"}
    got = self_time.per_span(trace(
        span("sched/dispatch", 0, 100),
        span("sched/pack", 10, 40),
        span("sched/dispatch", 50, 90),  # the same batch, packed afresh
        span("sched/pack", 55, 85),
    ), args)
    assert got == pytest.approx(((100 - 30 - 40) + (40 - 30)) / 2)


def test_no_dispatch_reads_nothing_and_no_parent_reads_zero():
    assert self_time.per_span(trace(span("sched/pack", 0, 10)), PACK) is None
    assert self_time.per_span(trace(
        span("sched/dispatch", 0, 10, line=0)), PACK) == 0
    # a parent that began before the slice is not this slice's
    assert self_time.per_span(trace(
        span("sched/dispatch", 120, 130),
        span("sched/pack", 90, 110),
        window=(100, 200),
    ), PACK) == 0


def test_read_finds_nothing_without_a_traced_slice(tmp_path):
    sample = {"root": tmp_path, "cell": {"name": "x"}}
    assert self_time.read(sample, PACK) is None


# -- the entries, by the benchmark's own rules -------------------------------


def test_the_twelve_are_declared_as_one_run_in_their_order():
    rules.contiguous_run(BENCH, list(NAMES))
    for rule in rules.STRUCTURE:
        rule(BENCH, ROOT)


@pytest.mark.parametrize("name", list(NAMES))
def test_an_entry_names_its_cells_and_its_file_says_what_it_reads(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = rules.spec_of(ROOT, name)
    cells = {w["name"] for w in BENCH["workloads"]}
    if name in GANGS_OWN:
        assert spec["needs"] == "pod_groups"
        assert GANG in entry["workloads"]
        assert entry["layer"] == "gang fixup"
    else:
        assert "needs" not in spec
        assert set(TEN_CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= cells
    for cell in entry["workloads"]:
        rules.cell_named(BENCH, cell)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("ms", "lower", "program_span",
                                "pod_to_bind_p50_ms")
    reader, reads = NAMES[name]
    assert spec["reader"] == reader
    assert spec["args"].get("stage", spec["args"].get("span")) == reads
    assert reads in spec["what"] and len(spec["what"]) > 100
    assert "on_chip_only" not in spec
    if reader != "stage_per_batch":
        assert spec["args"]["per"] == "sched/dispatch"
        assert (entry["layer"] == "device solve, host side") == (
            "dispatch" in name)
    else:
        # the total the file names is one the program keeps
        source = (ROOT / "kubernetes_tpu/scheduler/batch.py").read_text()
        assert f'"{reads}"' in source


def test_the_older_metrics_of_pack_and_the_gang_fixup_stay():
    have = {m["name"] for m in BENCH["per_layer"]}
    assert {
        "pack_ms_per_batch", "pack_drain_ms_per_batch",
        "pack_snapshot_ms_per_batch", "pack_state_ms_per_batch",
        "pack_pods_ms_per_batch", "pack_masks_ms_per_batch",
        "pack_families_ms_per_batch", "gang_fixup_ms_per_batch",
        "gang_census_ms_per_batch", "solve_dispatch_ms_per_batch",
    } <= have


# -- a traced rehearsal: the kept trace accounts for every parent whole ------


def _directly_inside(parent, line_spans):
    """The intervals of the spans of ``parent``'s line that lie inside
    it and inside no other span inside it, found pair by pair: not the
    reader's way."""
    inside = {
        (sp["start"], sp["end"]) for sp in line_spans if sp is not parent
        and parent["start"] <= sp["start"] and sp["end"] <= parent["end"]
    }
    return sorted(
        one for one in inside
        if not any(other != one and other[0] <= one[0] and one[1] <= other[1]
                   for other in inside)
    )


@pytest.mark.parametrize("cell", [BURST, GANG])
def test_children_and_self_make_the_parents_wall_clock(tmp_path, cell):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", copy)
    for base in BENCH["paths"]:
        shutil.copytree(ROOT / base, copy / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu",
               PYTHONHASHSEED="0")
    kept = tmp_path / "kept"
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.proving.run", "--workload", cell,
         "--seed", str(2**31 + 53), "--seconds", "1", "--trace", "1",
         "--rehearsal", "--keep-trace", str(kept)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    mine = [m for m in BENCH["per_layer"]
            if m["name"] in NAMES and cell in m["workloads"]]
    assert len(mine) == (12 if cell == GANG else 9)
    for m in mine:
        got = line["metrics"][m["name"]]
        assert got["unit"] == "ms" and got["value"] >= 0, m["name"]
    metrics = {m["name"]: line["metrics"][m["name"]]["value"] for m in mine}
    assert metrics["pack_aggregates_ms_per_batch"] > 0
    assert metrics["pack_order_ms_per_batch"] > 0
    assert metrics["pack_self_ms_per_batch"] > 0
    assert metrics["dispatch_self_ms_per_batch"] > 0
    assert metrics["dispatch_begin_ms_per_batch"] > 0
    # the four new children and the six older ones are parts of pack
    parts = sum(
        line["metrics"][name]["value"] for name in (
            "pack_aggregates_ms_per_batch", "pack_cluster_terms_ms_per_batch",
            "pack_overlay_ms_per_batch", "pack_order_ms_per_batch",
            "pack_drain_ms_per_batch", "pack_snapshot_ms_per_batch",
            "pack_state_ms_per_batch", "pack_pods_ms_per_batch",
            "pack_masks_ms_per_batch", "pack_families_ms_per_batch",
        )
    )
    assert parts <= line["metrics"]["pack_ms_per_batch"]["value"]

    (path,) = kept.glob("*.xplane.pb")
    kept_trace = program_spans.read_trace(str(path))
    by_line = self_time.by_line(kept_trace)
    parents = ["sched/pack", "sched/dispatch"]
    if cell == GANG:
        parents.append("sched/gang_fixup")
    for name in parents:
        found = [sp for sp in kept_trace["spans"] if sp["name"] == name]
        assert found, name
        for parent in found:
            line_spans = by_line[parent["line"]]
            children = _directly_inside(parent, line_spans)
            # siblings on one thread's line never overlap
            for before, after in zip(children, children[1:]):
                assert before[1] <= after[0]
            own = self_time.self_ns(parent, line_spans)
            assert own >= 0
            assert own + sum(end - start for start, end in children) == (
                parent["end"] - parent["start"])
    names = {sp["name"] for sp in kept_trace["spans"]}
    assert {"sched/pack.aggregates", "sched/pack.cluster_terms",
            "sched/pack.overlay", "sched/pack.order"} <= names
    gangs_own = {"sched/gang_siblings", "sched/gang_fixup.members",
                 "sched/gang_fixup.verdict"}
    assert gangs_own <= names if cell == GANG else not gangs_own & names
