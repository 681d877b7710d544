"""How ``correct`` is put together: one loop over the comparisons a
configuration names (``chipbench/check.py``, ``chipbench/checks/``).
With ``checks`` absent a cell prints today's ``compare`` lines, in
today's order and wording; a name with no file and a pod-class key
nobody reads each end the run with the name in the message; the watch
tells the harness's own deletes from evictions, by name; and a copy of
the benchmark gains a priority deployment with a comparison, a
generator and a second tier ledger of its own as new files and appended
entries only (``chipbench/proving/preempt/``: what the next
``model_config`` PR adds for real), runs ``correct: true`` at rehearsal
size, and its broken twins do not."""

import json
import os
import re
import subprocess
import sys
import threading
import time
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench import check, harness
from chipbench.proving.preempt import grow
from chipbench.watcher import BindWatcher

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

#: the ``compare`` lines of a run whose configuration names no checks,
#: numbers taken out, in the order they are printed
REPLAY = [
    "compare replay of # snapshot(s), # placements: nodes over "
    "allocatable: # (limit #) -> ok",
    "compare replay: zone skew beyond maxSkew, worst spread app: # "
    "(limit #) -> ok",
    "compare replay: pods sharing a host inside an anti app: # (limit #) "
    "-> ok",
    "compare replay: pods whose node in the apiserver differs from the "
    "watch's: # (limit #) -> ok",
    "compare watch history: pods bound more than once: # (limit #) -> ok",
    "compare window against the reference: pods of the worst wave outside "
    "what the scoring rule allows their node (# wave(s), # pods, # outside "
    "in all, no node selector): # (limit #) -> ok",
]


def check_wave(cls):
    return (f"compare check wave {cls}: pods no tie-break of the reference "
            "explains (# pods, # bound, node selector to the ballast pool): "
            "# (limit #) -> ok")


TIER = ("compare tier: batches by tier {'pallas': #, 'xla': #, "
        "'host_greedy': #, 'sequential': #}, below 'xla': # (limit #) -> ok")
LINES = {
    "basic-5000.burst-10k": REPLAY + [check_wave("plain"), TIER],
    "spread-anti-5000.burst-5k":
        REPLAY + [check_wave("spread"), check_wave("anti"), TIER],
    "basic-5000.arrivals-steady": REPLAY + [check_wave("plain"), TIER],
    "basic-50000.mesh-burst-20k": REPLAY + [check_wave("plain"), TIER],
}


@pytest.mark.parametrize("workload", sorted(LINES))
def test_without_checks_a_cell_prints_the_lines_it_always_printed(
        capsys, monkeypatch, workload):
    cell = harness.load_cell(ROOT, workload, rehearsal=True)
    assert "checks" not in cell["config"]
    assert "expect_tiers" not in cell["config"]
    counted = []
    real = harness.Run.counters

    def counters(self):
        counted.append(real(self))
        return counted[-1]

    monkeypatch.setattr(harness.Run, "counters", counters)
    rc = harness.main(["--workload", workload, "--seed", "2147483659",
                       "--seconds", "1", "--trace", "0", "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and json.loads(out[-1])["correct"] is True
    compared = [re.sub(r"\d+", "#", l) for l in out if l.startswith("compare ")]
    assert compared == LINES[workload]
    # every run's counters carry the preemptor's, 0 where nothing preempts
    for counters in counted:
        for key in harness.PREEMPTOR_COUNTERS:
            assert counters[key] == 0
        assert set(counters["tiers"]) == set(harness.LEDGERS)


def test_the_default_comparisons_and_the_tier_close_every_list():
    assert check.names_of({}) == ["replay", "window_reference", "check_wave",
                                  "tier"]
    assert check.names_of({"checks": ["replay", "mine"]}) == [
        "replay", "mine", "tier"]
    assert check.expected_tiers({"expect_tier": "pallas"}) == {"batch": "pallas"}


def basic_config(**over):
    config = json.loads((ROOT / "chipbench/configs/basic-5000.json").read_text())
    return harness._overlay(config, over)


def test_a_pod_class_key_nobody_reads_ends_the_load_with_its_name():
    check.validate(basic_config())
    with pytest.raises(harness.BenchError, match="'tolerations'"):
        check.validate(basic_config(
            pod_classes={"plain": {"tolerations": [{"key": "spot"}]}}))
    # ``check`` is the check wave's key: a configuration that does not
    # name that comparison states it to nobody
    with pytest.raises(harness.BenchError, match="'check'"):
        check.validate(basic_config(checks=["replay"]))


def test_a_comparison_or_a_ledger_that_is_not_there_ends_the_load():
    with pytest.raises(harness.BenchError, match="'victims'.*victims.py"):
        check.validate(basic_config(checks=["replay", "victims"]))
    with pytest.raises(harness.BenchError, match="'gang_wave'"):
        check.validate(basic_config(expect_tiers={"gang_wave": "pallas"}))
    with pytest.raises(harness.BenchError, match="'fast'"):
        check.validate(basic_config(expect_tiers={"preempt_wave": "fast"}))


def test_a_class_may_state_a_priority_and_without_one_the_pod_is_todays():
    run = harness.Run.__new__(harness.Run)
    run.config = {"pod_classes": {
        "filler": {"cpu_milli": 3000, "memory_mib": 6144},
        "zero": {"cpu_milli": 3000, "memory_mib": 6144, "priority": 0},
        "high": {"cpu_milli": 3000, "memory_mib": 6144, "priority": 100},
    }}
    run.created, run._serial = {}, 0
    from kubernetes_tpu.testing import make_pod

    (filler,) = run.make_pods("filler", 1, "a")
    (high,) = run.make_pods("high", 1, "a")
    plain = make_pod("a-1-0").container(cpu="3000m", memory="6144Mi").labels(
        app="a").obj()
    assert high.spec.priority == 100
    assert filler.spec.priority == plain.spec.priority
    # but for what the API stamps on every object, the pod of a class
    # without the key is the pod the harness always made
    plain.metadata.uid = filler.metadata.uid
    plain.metadata.creation_timestamp = filler.metadata.creation_timestamp
    assert repr(filler) == repr(plain)


class FakeWatch:
    def __init__(self):
        self.batches, self.cond = [], threading.Condition()

    def feed(self, *events):
        with self.cond:
            self.batches.append(list(events))
            self.cond.notify_all()

    def next_batch(self, timeout):
        with self.cond:
            if not self.batches:
                self.cond.wait(timeout)
            return self.batches.pop(0) if self.batches else []

    def stop(self):
        pass


class FakeServer:
    def __init__(self):
        self.stream = FakeWatch()

    def current_rv(self):
        return 0

    def watch(self, kind, since_rv):
        return self.stream


def deleted(name):
    from kubernetes_tpu.apiserver.server import WatchEvent
    from kubernetes_tpu.testing import make_pod

    return WatchEvent("DELETED", make_pod(name).obj(), 1)


def test_the_wait_for_deletes_goes_by_name():
    """An eviction that lands during a harness delete ends no wait
    early: a count of events would have been reached, the names are
    not."""
    server = FakeServer()
    watcher = BindWatcher(server)
    try:
        server.stream.feed(deleted("victim"), deleted("mine-0"))
        assert watcher.wait_deleted(["mine-0"], time.perf_counter() + 5)
        assert not watcher.wait_deleted(
            ["mine-0", "mine-1"], time.perf_counter() + 0.3)
        server.stream.feed(deleted("mine-1"))
        assert watcher.wait_deleted(
            ["mine-0", "mine-1"], time.perf_counter() + 5)
        assert set(watcher.deleted_time) == {"victim", "mine-0", "mine-1"}
        # the harness's own deletes apart from what left otherwise
        run = harness.Run.__new__(harness.Run)
        run.watcher, run.harness_deleted = watcher, {"mine-0", "mine-1"}
        assert list(run.evicted()) == ["victim"]
    finally:
        watcher.stop()


# -- the grown copy ----------------------------------------------------------


def grown_copy(tmp_path, config_over=None):
    """The benchmark with ``priority-5000.preempt-wave`` added as the
    next PR will add it; ``config_over`` is laid over the new
    configuration's file before it is placed (the broken twins)."""
    copy = tmp_path / "checkout"
    before = grow.copy_benchmark(ROOT, copy)
    bench = grow.add_cell(copy)
    listed = [m["name"] for m in BENCH["per_layer"]]
    assert grow.cannot_report(copy) == (
        rules.on_chip_only(copy, listed) | rules.needs_something(copy, listed))
    if config_over:
        path = copy / grow.PLACES[f"{grow.CONFIG}.json"]
        path.write_text(json.dumps(
            harness._overlay(json.loads(path.read_text()), config_over)))
    return copy, bench, before


def run_in(copy, trace):
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", grow.CELL,
         "--seed", "2147483777", "--seconds", "1", "--trace", str(trace),
         "--rehearsal"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300,
    )
    return proc, proc.stdout.strip().splitlines()


def test_a_priority_deployment_is_added_by_new_files_and_entries_only(tmp_path):
    """A configuration whose classes are ``filler`` (no priority) and
    ``high`` (priority 100) on nodes the init pods fill, so that no
    ``high`` pod fits without an eviction; a generator that lands waves
    of them; the comparison ``evictions``, counted from the client's
    side; ``expect_tiers`` for the preemption wave's ledger. The copy
    keeps every rule of the real file, runs correct, prints the new
    ``compare`` lines and holds no edited file."""
    copy, bench, before = grown_copy(tmp_path)
    for rule in rules.STRUCTURE:
        rule(bench, copy)
    for name in rules.NEW:
        rules.declared_since_pr24(bench, copy, name)
    config = json.loads((copy / grow.PLACES[f"{grow.CONFIG}.json"]).read_text())
    assert "priority" not in config["pod_classes"]["filler"]
    assert config["pod_classes"]["high"]["priority"] == 100
    assert config["checks"] == ["replay", "evictions"]
    assert config["rehearsal"]["expect_tiers"] == {"preempt_wave": "xla"}

    proc, out = run_in(copy, trace=1)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    compared = [l for l in out if l.startswith("compare ")]
    assert all(l.endswith("-> ok") for l in compared)
    assert [re.sub(r"\d+", "#", l.split(":")[0]) for l in compared] == [
        "compare replay of # snapshot(s), # placements", "compare replay",
        "compare replay", "compare replay", "compare watch history",
        "compare evictions", "compare evictions", "compare evictions",
        "compare tier", "compare tier preempt_wave",
    ]
    # one victim a preemptor, none of them of the preemptors' priority,
    # counted from the watch and the harness's own deletes
    victims = re.search(
        r"victims beyond 1 a preemptor \((\d+) victims, (\d+) preemptors\)",
        proc.stdout)
    assert victims and int(victims[1]) == int(victims[2]) > 0
    assert "host_preemptions 0, solves by tier {'pallas': 0, 'xla': " in compared[-1]
    preemptor = re.search(
        r"preemptor over the window: device_preemptions (\d+), "
        r"host_preemptions 0, preempt_waves (\d+), budget_denials 0", proc.stdout)
    # every preemptor went through the device's victim search, some of
    # them again before their victim's delete had reached the cache
    assert preemptor and int(preemptor[1]) >= line["attempted"]
    declared = {m["name"] for m in bench["per_layer"]
                if grow.CELL in rules.cells_of(bench, m)}
    assert set(line["metrics"]) == declared
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert grow.edited_files(before) == []


BROKEN = {
    # the rule says no victim at all: the wave's real evictions exceed it
    "a_limit_the_wave_exceeds": (
        {"evictions": {"victims_per_preemptor": 0}},
        "compare evictions: victims beyond 0 a preemptor"),
    # the wave runs on the jnp twin on a CPU: below the tier stated
    "a_ledger_below_its_tier": (
        {"rehearsal": {"expect_tiers": {"preempt_wave": "pallas"}}},
        "compare tier preempt_wave"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_the_broken_twin_is_not_correct(tmp_path, fault):
    over, failing = BROKEN[fault]
    copy, _, _ = grown_copy(tmp_path, over)
    proc, out = run_in(copy, trace=0)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert json.loads(out[-1])["correct"] is False
    failed = [l for l in out if l.startswith("compare ") and l.endswith("FAILED")]
    assert len(failed) == 1 and failed[0].startswith(failing)


@pytest.mark.parametrize("over, named", [
    ({"checks": ["replay", "evictions", "budgets"]}, "'budgets'"),
    ({"pod_classes": {"high": {"preemption_policy": "Never"}}},
     "'preemption_policy'"),
])
def test_the_twin_that_names_what_is_not_there_does_not_start(
        tmp_path, over, named):
    copy, _, _ = grown_copy(tmp_path, over)
    proc, out = run_in(copy, trace=0)
    assert proc.returncode == 2
    assert named in proc.stderr and "correct" not in proc.stdout
