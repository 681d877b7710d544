"""The cell ``gang-train-5000.gang-half-8k``: its run at rehearsal size,
its control (the reference reading no pod groups, which has to fail
``gang_guarantees``), a broken twin (the quorum fix-up as it stood before
PR 34, which has to fail maximality), the six per-layer metrics and the
spans they read, and the generator's accounting."""

import json
import os
import subprocess
import sys
import time
import types
from pathlib import Path

import benchmark_rules as rules
import numpy as np
import pytest

from chipbench import gang_reference, harness, program_spans, reference
from chipbench.checks import gang_guarantees
from chipbench.generators import gang_waves

ROOT = Path(__file__).resolve().parents[2]
CELL = "gang-train-5000.gang-half-8k"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIB = 1 << 20
SIX = {
    "gang_fixup_ms_per_batch", "gang_census_ms_per_batch",
    "permit_ms_per_batch", "gang_solves_per_batch", "gang_requeued_share",
    "idle_under_gang_fixup_pct",
}


def run_cell(capsys, trace, mix_over=None, keep_trace="", seed=2**31 + 34):
    args = harness.public_arguments("test").parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "1",
        "--trace", str(trace), "--rehearsal",
    ])
    with rules.one_traced_run_at_a_time(ROOT):
        rc = harness.run_one(
            args, time.perf_counter(), mix_over=mix_over,
            keep_trace=keep_trace,
        )
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    return json.loads(out[-1]), out


def compared(out, start):
    (line,) = [l for l in out if l.startswith("compare " + start)]
    return int(line.split(": ")[-1].split(" ")[0]), line


# -- the files ---------------------------------------------------------------


def test_the_deployment_and_the_mix_at_the_sizes_the_issue_gives():
    config = json.loads(
        (ROOT / "chipbench/configs/gang-train-5000.json").read_text()
    )
    mix = json.loads((ROOT / "chipbench/traffic/gang-half-8k.json").read_text())
    assert config["reduced"] == [] and config["layout"]["chips"] == 1
    assert config["wire"] == {"tpuSolver": {"maxBatch": 4096}}
    assert config["checks"] == [
        "replay", "gang_guarantees", "window_gang_reference",
    ]
    assert config["expect_tier"] == "pallas" and "expect_tiers" not in config
    cluster = config["cluster"]
    worker = config["pod_classes"]["worker"]
    # eight workers fill a node exactly, on cpu and on memory
    assert 8 * worker["cpu_milli"] == int(cluster["node"]["cpu"]) * 1000
    assert 8 * worker["memory_mib"] == 64 * 1024
    slots = cluster["nodes"] * 8
    assert slots - cluster["init_pods"]["count"] == 4000
    params = mix["params"]
    assert params["expect_free_slots"] == 4000
    sizes = gang_waves.sizes_of(params)
    assert sorted(set(sizes)) == [8, 32, 128, 512]
    assert [sizes.count(s) for s in (8, 32, 128, 512)] == [256, 64, 16, 4]
    assert sum(sizes) == 8192 and len(sizes) == 340
    assert (params["creators"], params["chunk"]) == (4, 256)
    assert (params["deadline_s"], params["warmup_waves"]) == (10, 2)
    for name in ("limit_bound_in_part", "limit_unbound_that_fit",
                 "limit_probe_unbound", "limit_probe_overcommitted"):
        assert config["gang_guarantees"][name] == 0
    assert config["window_gang_reference"]["limit_gangs"] == 0


def test_the_cell_and_its_metrics_as_declared():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and BENCH["workloads"][-1] == cell
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == SIX
    assert BENCH["per_layer"][-6:] == mine
    for m in mine:
        assert m["moves"] == "pod_to_bind_p50_ms"
        assert rules.spec_of(ROOT, m["name"])["needs"] == "pod_groups"
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in rules.cells_of(BENCH, m)}
    assert {"solve_kernel_ms_per_batch", "solve_kernel_roofline",
            "wave_drain_pods_per_s", "burst_pod_to_bind_p99_ms"} <= declared
    # what other cells' traffic alone can report stays theirs
    assert not any(n.startswith(("preempt_", "carry_", "shard_", "mesh_"))
                   for n in declared)
    assert "pack_family_node_rows_reused_share" not in declared


# -- the run -----------------------------------------------------------------


def test_the_cell_is_correct_and_both_comparisons_read_0(capsys):
    line, out = run_cell(capsys, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for start in ("gangs: workers of gangs bound in part",
                  "gangs: unbound gangs that the slots left would hold",
                  "gangs: plain pods that did not bind into the slots",
                  "gangs: plain pods bound beyond the slots left",
                  "window against the reference: gangs the reference admits "
                  "otherwise"):
        value, text = compared(out, start)
        assert value == 0 and text.endswith("-> ok")
    (probe,) = [l for l in out if l.startswith("probe: ")]
    assert "settled True" in probe
    # half of a wave is rightly left: fewer pods attempted than created
    (notes,) = [l for l in out if l.startswith("window: ")]
    waves = sum(l.count("*") for l in out if l.startswith("waves (drain"))
    assert line["attempted"] < waves * 48


def test_the_control_fails_gang_guarantees():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.proving.run", "--workload", CELL,
         "--seed", "34", "--seconds", "1", "--trace", "0", "--rehearsal",
         "--control"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = proc.stdout.splitlines()
    assert json.loads(out[-1])["correct"] is True
    (gangs,) = [l for l in out if l.startswith("control gangs: ")]
    assert int(gangs.split(" leaves ")[1].split(" ")[0]) > 0
    (window,) = [l for l in out if l.startswith("control window: ")]
    assert int(window.split(" decides ")[1].split(" ")[0]) > 0


def test_the_control_at_the_timed_size_breaks_gangs_over_a_windows_waves():
    """The reference reading no pod groups at the cell's own cluster and
    wave: the first 4,000 pods created fill the slots."""
    mix = json.loads((ROOT / "chipbench/traffic/gang-half-8k.json").read_text())
    n = 5000
    used = np.full(n, 7, dtype=np.int64)
    used[:1000] = 8
    nodes = reference.Nodes(
        cap_cpu=np.full(n, 32000), cap_mem=np.full(n, 64 << 30),
        cap_pods=np.full(n, 110), used_cpu=used * 4000,
        used_mem=used * (8192 * MIB), used_pods=used.copy(),
        zone=np.arange(n) % 10,
    )
    pod = reference.PodClass(4000, 8192 * MIB)
    assert gang_reference.slots(nodes, pod) == 4000
    sizes = gang_waves.sizes_of(mix["params"])
    parts = []
    for seed in range(12):
        order = np.random.default_rng(seed).permutation(len(sizes))
        gangs = {f"g{k}": sizes[int(k)] for k in order}
        bound = gang_reference.ignoring_groups(nodes, pod, gangs, list(gangs))
        wave = {"gangs": {g: [None] * s for g, s in gangs.items()}}
        part, fits = gang_guarantees.count(wave, bound, 4000)
        assert sum(bound.values()) == 4000 and fits == 0
        parts.append(part)
    # every size is a multiple of 8 and so are the slots: a shuffle in
    # which the slots end where a gang ends is a sound outcome by luck,
    # which is why the control reads the worst of a window's waves
    assert max(parts) >= 8 and sum(1 for p in parts if p) >= 6, parts


def test_the_fix_up_as_it_stood_fails_maximality(capsys, monkeypatch):
    """The broken twin: every gang a pass left short of its quorum is
    masked with the first, the gangs that only failed beside it too, and
    nothing wakes them. Put under the program once warm-up is over."""
    from kubernetes_tpu.scheduler.batch import BatchScheduler

    def as_before(pending, assignments, at, masked, taken, quorum,
                  templates):
        failed, _ = sound(pending, assignments, at, masked, taken,
                          quorum, templates)
        return failed, {key: "first" for key in failed}

    sound = BatchScheduler._gang_census
    real_prepare = gang_waves.prepare

    def prepare(run, params, seconds):
        monkeypatch.setattr(
            BatchScheduler, "_gang_census", staticmethod(as_before)
        )
        return real_prepare(run, params, seconds)

    monkeypatch.setattr(gang_waves, "prepare", prepare)
    line, out = run_cell(
        capsys, 0, mix_over={"params": {"wave_timeout_s": 2}}, seed=7,
    )
    assert line["correct"] is False and line["failed"] > 0
    value, text = compared(
        out, "gangs: unbound gangs that the slots left would hold")
    assert value > 0 and text.endswith("FAILED")
    # all-or-nothing itself still holds there
    assert compared(out, "gangs: workers of gangs bound in part")[0] == 0


def test_the_six_metrics_read_the_spans_of_a_traced_rehearsal(capsys,
                                                              tmp_path):
    line, out = run_cell(capsys, 1, keep_trace=str(tmp_path))
    assert line["correct"] is True
    assert SIX <= set(line["metrics"])
    assert line["metrics"]["gang_solves_per_batch"]["value"] >= 1.0
    assert line["metrics"]["gang_requeued_share"]["value"] == 0.0
    assert line["metrics"]["gang_fixup_ms_per_batch"]["value"] > (
        line["metrics"]["gang_census_ms_per_batch"]["value"]) > 0.0
    assert line["metrics"]["permit_ms_per_batch"]["value"] > 0.0
    (path,) = list(tmp_path.glob("*.xplane.pb"))
    trace = program_spans.read_trace(str(path))
    by_name: dict = {}
    for sp in trace["spans"]:
        by_name.setdefault(sp["name"], []).append(sp)
    fixups = by_name["sched/gang_fixup"]
    for sp in fixups:
        assert {"pods", "groups", "passes", "masked_groups", "masked_pods",
                "requeued_pods", "carry"} <= set(sp["stats"])
        assert int(sp["stats"]["passes"]) >= 1
    assert any(sp["stats"]["carry"] in ("rewound", "dropped")
               for sp in fixups)
    for child in ("download", "census", "resolve"):
        assert by_name[f"sched/gang_fixup.{child}"]
    for sp in by_name["sched/commit.permit"]:
        assert {"pods", "groups", "waiting", "released", "rejected"} <= set(
            sp["stats"])
    # the always-on totals the three stage metrics read
    (stages,) = [l for l in out if l.startswith("gang stages")]
    for name in ("gang_fixup ", "gang_fixup.census ", "commit.permit "):
        assert name in stages


# -- the generator's accounting ---------------------------------------------


def fake_run(bound):
    return types.SimpleNamespace(
        watcher=types.SimpleNamespace(bind_time={n: 1.0 for n in bound})
    )


GANGS = {
    "a": ["a-0", "a-1", "a-2", "a-3"], "b": ["b-0", "b-1"],
    "c": ["c-0", "c-1", "c-2"], "d": ["d-0", "d-1", "d-2", "d-3", "d-4"],
}


def test_a_pod_rightly_left_is_not_attempted():
    # 6 slots: a and b bound whole, none left: c and d are rightly left
    state = gang_waves.tally(fake_run(GANGS["a"] + GANGS["b"]), GANGS, 6)
    assert state["left"] == 0 and gang_waves.settled(state, GANGS)
    assert sorted(gang_waves.admitted(state, GANGS)) == sorted(
        GANGS["a"] + GANGS["b"])


def test_a_pod_wrongly_left_is_attempted_and_so_failed():
    # 9 slots, a and b bound: 3 are left, c fits them and d does not
    state = gang_waves.tally(fake_run(GANGS["a"] + GANGS["b"]), GANGS, 9)
    assert state["left"] == 3 and not gang_waves.settled(state, GANGS)
    names = gang_waves.admitted(state, GANGS)
    assert sorted(names) == sorted(GANGS["a"] + GANGS["b"] + GANGS["c"])
    # a gang bound in part is attempted whole
    state = gang_waves.tally(fake_run(GANGS["a"] + ["d-0"]), GANGS, 5)
    assert state["part"] == ["d"] and not gang_waves.settled(state, GANGS)
    assert set(GANGS["d"]) <= set(gang_waves.admitted(state, GANGS))


def test_a_tally_reads_a_whole_gang_once():
    run = fake_run(GANGS["a"])
    first = gang_waves.tally(run, GANGS, 9)
    del run.watcher.bind_time["a-0"]  # would read as bound in part
    assert gang_waves.tally(run, GANGS, 9, first)["whole"] == ["a"]
