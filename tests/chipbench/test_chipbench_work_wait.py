"""PR 37's work / wait metrics: the reader ``span_cpu_share`` on
hand-made traces, each of the thirteen metric files held to the
benchmark's own rules (``benchmark_rules.py``), and traced rehearsals
whose lines have to hold a number for every one of them.

The thirteen have files and a reader but, as PR 37 leaves them, no entry
in ``BENCHMARK.json``: ``test_chipbench_gang.py`` holds the gang cell's
six to the end of ``per_layer`` (PERF.md section 7.7), so declaring them
takes a ``benchmark`` PR. Until then an entry is built from the metric's
own file (``chipbench/proving/entries37.json`` through
``proving/declare.py``); once ``BENCHMARK.json`` declares a name, its
entry there is the one tested, and this file needs no edit."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench.proving import declare
from chipbench.readers import span_cpu_share

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
WAITING = json.loads(
    (ROOT / "chipbench/proving/entries37.json").read_text()
)
NAMES = WAITING["metrics"]
# ``BENCHMARK.json`` with an entry for each of the thirteen: its own
# where it has one, else one built from the metric's file
DECLARED = declare.declared(BENCH, ROOT, NAMES, WAITING["not_in"])
ENTRIES = [m for m in DECLARED["per_layer"] if m["name"] in set(NAMES)]
# the six cells the benchmark had when the thirteen were written (a PR
# appends, so they stay the first six); later cells may join an entry
CELLS = [w["name"] for w in BENCH["workloads"]][:6]
STEADY = "basic-5000.arrivals-steady"
SHARES = {"ingest_cpu_share", "pack_snapshot_cpu_share",
          "pack_state_cpu_share", "commit_cpu_share", "bind_cpu_share"}

# -- the reader ---------------------------------------------------------------

MS = 1_000_000  # the trace's clock is in ns


def span(name, start, end, line=0, **stats):
    return {"name": name, "start": start * MS, "end": end * MS,
            "line": ("/host:CPU", line), "stats": stats}


def trace(*spans, window=(0, 1000)):
    return {"window": (window[0] * MS, window[1] * MS), "spans": list(spans)}


def test_share_is_the_sum_of_cpu_over_the_sum_of_wall():
    got = span_cpu_share.share(trace(
        span("sched/ingest", 10, 20, cpu_ms=10.0),   # all work
        span("sched/ingest", 30, 60, cpu_ms=0.0),    # all waiting
        span("sched/pack", 30, 60, cpu_ms=30.0),     # another stage
    ), {"span": "sched/ingest"})
    assert got == pytest.approx(10.0 / 40.0)


def test_a_span_without_the_stat_is_not_counted_and_none_is_nothing():
    args = {"span": "sched/commit"}
    old = span("sched/commit", 0, 50)  # a program from before the clock
    assert span_cpu_share.share(trace(old), args) is None
    assert span_cpu_share.share(trace(), args) is None
    got = span_cpu_share.share(
        trace(old, span("sched/commit", 50, 60, cpu_ms=5.0)), args
    )
    assert got == pytest.approx(0.5)


def test_a_span_that_crosses_the_slices_edge():
    """Began before the slice: not counted. Began in it and ends after
    it: counted whole, as its ``cpu_ms`` is for the whole."""
    args = {"span": "sched/bind"}
    before = span("sched/bind", 90, 110, cpu_ms=20.0)
    after = span("sched/bind", 190, 230, cpu_ms=10.0)
    got = span_cpu_share.share(
        trace(before, after, window=(100, 200)), args
    )
    assert got == pytest.approx(10.0 / 40.0)
    assert span_cpu_share.share(
        trace(before, window=(100, 200)), args
    ) is None


def test_waits_by_design_are_taken_out_of_the_wall_clock():
    commit = span("sched/commit", 0, 100, line=2, cpu_ms=40.0)
    spans = (
        commit,
        span("sched/victim_wait", 10, 40, line=2, cpu_ms=0.0),
        span("sched/preempt_wave.pack_wait", 50, 60, line=2, cpu_ms=0.0),
        # the same name on another thread's line, and outside the span:
        # neither is this commit's wait
        span("sched/victim_wait", 10, 40, line=3, cpu_ms=0.0),
        span("sched/victim_wait", 120, 150, line=2, cpu_ms=0.0),
    )
    plain = span_cpu_share.share(trace(*spans), {"span": "sched/commit"})
    assert plain == pytest.approx(0.4)
    less = span_cpu_share.share(trace(*spans), {
        "span": "sched/commit",
        "less": ["sched/victim_wait", "sched/preempt_wave.pack_wait"],
    })
    assert less == pytest.approx(40.0 / 60.0)
    # nothing left but waits: nothing to read, not a division by zero
    assert span_cpu_share.share(
        trace(span("sched/commit", 0, 10, cpu_ms=0.0),
              span("sched/victim_wait", 0, 10, cpu_ms=0.0)),
        {"span": "sched/commit", "less": ["sched/victim_wait"]},
    ) is None


def test_read_finds_nothing_without_a_traced_slice(tmp_path):
    sample = {"root": tmp_path, "cell": {"name": "x"}}
    assert span_cpu_share.read(sample, {"span": "sched/ingest"}) is None


# -- the files, by the benchmark's own rules ---------------------------------


def test_the_thirteen_keep_every_rule_declared_or_waiting():
    assert len(NAMES) == len(set(NAMES)) == 13
    assert sorted(m["name"] for m in ENTRIES) == sorted(NAMES)
    # what is missing is appended: nothing that was there moves, and a
    # name that has its entry is not given a second
    have = len(BENCH["per_layer"])
    assert DECLARED["per_layer"][:have] == BENCH["per_layer"]
    assert declare.declared(DECLARED, ROOT, NAMES, {}) == DECLARED
    for rule in rules.STRUCTURE:
        rule(DECLARED, ROOT)
    for name in rules.NEW:
        rules.declared_since_pr24(DECLARED, ROOT, name)


def test_a_benchmark_that_declares_some_gains_the_rest_alone():
    """As when a ``benchmark`` PR declares a few of them, perhaps with
    more cells: those entries stay as they are, wherever they stand."""
    own = [dict(m, workloads=m["workloads"] + ["a-later.cell"])
           for m in ENTRIES[:5]]
    some = dict(BENCH, per_layer=own + BENCH["per_layer"])
    got = declare.declared(some, ROOT, NAMES, WAITING["not_in"])
    assert got["per_layer"][:len(some["per_layer"])] == some["per_layer"]
    rest = got["per_layer"][len(some["per_layer"]):]
    assert [m["name"] for m in rest] == NAMES[5:]
    assert all("a-later.cell" not in m["workloads"] for m in rest)


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_a_metrics_file_says_what_it_reads(entry):
    spec = rules.spec_of(ROOT, entry["name"])
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert set(spec) == set(entry) - {"workloads"} | {"what", "reader", "args"}
    assert entry["source"] == "program_span"
    assert entry["moves"] == "pod_to_bind_p50_ms"
    assert "on_chip_only" not in spec
    if entry["name"] in SHARES:
        # the chip's host ticks its CPU clock in 10 ms, and the open
        # loop's sub-millisecond stages start on a timer's tick: a share
        # read 1.12 there, so that cell is left out and the file says why
        # (not under ``needs``: ``proving/preempt/grow.py`` reads that
        # key off every file, declared or not)
        assert STEADY in spec["what"]
        assert set(CELLS) - set(entry["workloads"]) == {STEADY}
    else:
        assert set(CELLS) <= set(entry["workloads"])
    assert "needs" not in spec
    assert (ROOT / "chipbench/readers" / f"{spec['reader']}.py").is_file()
    # the file says which span or stage total and which stat it reads
    what = spec["what"]
    for key in ("span", "stat", "numerator", "stage"):
        if key in spec["args"]:
            assert spec["args"][key] in what, key
    for stage in spec["args"].get("stages", ()):
        assert stage in what
    if entry["name"] in SHARES:
        assert spec["reader"] == "span_cpu_share"
        assert (entry["unit"], entry["better"]) == ("ratio", "higher")
        assert "cpu_ms" in what and "wait for the GIL" in what
        assert "not-work" in what


def test_every_work_wait_file_is_there_declared_or_not():
    files = {
        p.stem for p in (ROOT / "chipbench/layer_metrics").glob("*.json")
    }
    assert set(NAMES) <= files


# -- a traced rehearsal of the copy ------------------------------------------


@pytest.mark.parametrize("cell", [
    "basic-5000.burst-10k",          # the pipelined path
    "gang-train-5000.gang-half-8k",  # the synchronous path
])
def test_a_traced_line_holds_a_number_for_each_of_the_thirteen(
    tmp_path, cell
):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", copy)
    for base in BENCH["paths"]:
        shutil.copytree(ROOT / base, copy / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    subprocess.run(
        [sys.executable, "-m", "chipbench.proving.declare",
         "chipbench/proving/entries37.json"],
        cwd=copy, env=env, check=True, capture_output=True, timeout=60,
    )
    assert json.loads((copy / "BENCHMARK.json").read_text()) == DECLARED
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell,
         "--seed", str(2**31 + 37), "--seconds", "1", "--trace", "1",
         "--rehearsal"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {m["name"]: line["metrics"][m["name"]] for m in ENTRIES}
    for m in ENTRIES:
        assert got[m["name"]]["unit"] == m["unit"]
        assert got[m["name"]]["value"] >= 0
    for name in SHARES | {"ingest_bind_echo_share"}:
        assert got[name]["value"] <= 1.05, name
    # read 0, not nothing, where the dispatcher never waited so
    if cell.startswith("basic"):
        assert got["pack_drain_ms_per_batch"]["value"] == 0
    assert got["bind_api_ms_per_batch"]["value"] > 0
    assert got["ingest_bind_echo_share"]["value"] > 0
