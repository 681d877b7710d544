"""The deployment ``rolling-upgrade-5000`` and its cell
``rolling-upgrade-5000.arrivals-roll-4`` (PR 41): the files as ISSUE 41
gives them, the cell's entries, the generator at rehearsal size through
the real harness, its comparison ``window_rolling_reference`` sound on
the program and failing on each of three broken twins (a scheduler deaf
to Node events, one that ignores the not-ready taint, one that never
hears of a re-joined node), the bfloat16 control through the check wave,
and the six per-layer metrics and the spans they read."""

import copy
import json
import time
import types
from pathlib import Path

import benchmark_rules as rules
import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.checks import window_rolling_reference as rolling
from chipbench.generators import arrivals_roll
from chipbench.readers import (
    node_ready_to_first_bind, pod_to_bind_quantile_of_app,
)
from test_chipbench_reference import MIB, ballast_pool

ROOT = Path(__file__).resolve().parents[2]
CELL = "rolling-upgrade-5000.arrivals-roll-4"
CONFIG = "rolling-upgrade-5000"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: the cell's own six, in the order ``per_layer`` holds them
SIX_IN_ORDER = [
    "node_event_ms_per_event", "node_spec_changed_share",
    "pack_node_epoch_moved_share", "carry_member_rows_per_batch",
    "node_ready_to_first_bind_ms", "drained_pod_to_bind_p50_ms",
]
NODE_LINE = ("watch history: pods bound to a node that had not taken pods "
             "for longer than node_settle_s")
UNTOUCHED = "window against the reference: pods on the nodes no op touched"
REJOINED = "window against the reference: re-joined nodes outside their band"
DRAINED = "drained pods: replacements missing or not bound"


def load(name):
    return json.loads((ROOT / "chipbench" / name).read_text())


# -- the files ---------------------------------------------------------------


def test_the_deployment_is_basic_5000s_cluster_with_guarantees_of_its_own():
    config = load(f"configs/{CONFIG}.json")
    basic = load("configs/basic-5000.json")
    for key in ("cluster", "pod_classes", "wire", "expect_tier",
                "score_precision"):
        assert config[key] == basic[key], key
    # the basic kernel's shapes and, beyond them, the score family's: a
    # rolled node's image makes every batch of the window a constrained one
    shape = dict(config["kernel_shape"])
    assert (shape.pop("family_rows"), shape.pop("families")) == (157, 50)
    assert shape == {k: v for k, v in basic["kernel_shape"].items()
                     if k in shape}
    assert "pallas_constrained_solve" in config["layout"]["what"]
    assert config["reduced"] == [] and len(config["source"]) <= 200
    assert config["checks"] == [
        "replay", "window_rolling_reference", "check_wave"]
    assert 0 < config["node_settle_s"] <= 1.0
    assert "chip" in config["node_settle_s_why"]
    guarantees = " ".join(config["guarantees"])
    for words in ("bound exactly once", "cordoned, not Ready or absent",
                  "node_settle_s", "drained node is offered again",
                  "re-joined node", "pods_fallback is 0"):
        assert words in guarantees, words
    for key in ("roll rate 4 nodes a second", "1 s from join to Ready",
                "no interval from remove to join", "first image",
                "17 kubelet status reports a second",
                "drained pods are recreated by the generator",
                "rolled nodes keep their names"):
        assert key in config["assumed"], key
    # nothing of basic-5000's own assumptions is dropped
    assert set(basic["assumed"]) <= set(config["assumed"])


def test_the_mix_holds_the_issues_table_letter_for_letter():
    mix = load("traffic/arrivals-roll-4.json")
    steady = load("traffic/arrivals-steady.json")
    assert mix["generator"] == "arrivals_roll"
    assert "window_check" not in mix and mix["trace_seconds"] == 4
    params = mix["params"]
    # the pods: arrivals-steady's schedule exactly
    for key, value in steady["params"].items():
        assert params[key] == value, key
    assert (params["rate"], params["tick_ms"], params["gap_seed"],
            params["creators"], params["deadline_s"]) == (
        2600, 5, 20260927, 1, 10)
    roll = params["roll"]
    assert roll["nodes_per_s"] == 4 and roll["start_s"] == 1.0
    assert roll["quiet_last_s"] == 3.0 and roll["ready_after_s"] == 1.0
    assert roll["first_image"] == {
        "name": roll["first_image"]["name"], "size_mib": 50, "after_s": 1.0}
    # remove and join follow each other at once, as the table has them
    assert set(roll) == {"nodes_per_s", "start_s", "quiet_last_s",
                         "ready_after_s", "first_image", "drain_timeout_s"}
    assert params["kubelet_reports_per_s"] == 17
    assert params["warmup_rounds"] == 2
    # warm-up: two rounds, each rolling 4 nodes through every step at the
    # window's own pace; what departs from that is named, one by one, in
    # ``why_warmup``, each for compiles_in_window 0
    assert params["warmup_roll"] == {
        "nodes": 4, "start_s": 0.0, "rejoin_after_s": 0.1,
        "first_before_arrivals": True}
    assert set(params) - set(steady["params"]) == {
        "roll", "kubelet_reports_per_s", "warmup_roll",
        "warmup_burst_pods", "warmup_timeout_s"}
    for name in ("first_before_arrivals", "warmup_roll.rejoin_after_s",
                 "warmup_burst_pods", "warmup_timeout_s"):
        assert name in mix["why_warmup"], name
    # 189 rolls in the benchmark's 51 s: one every 250 ms, 1.0 .. 48.0
    run = types.SimpleNamespace(
        rolls=[], node_rows={f"node-{i}": i for i in range(5000)},
        in_ballast_pool=lambda i: i // 10 < 64,
        rng=np.random.default_rng(7),
    )
    starts = arrivals_roll.roll_starts(
        run, roll, float(BENCH["run_seconds"]))
    offsets = [offset for offset, _ in starts]
    assert len(starts) == 189 and offsets[0] == 1.0 and offsets[-1] == 48.0
    assert np.allclose(np.diff(offsets), 0.25)
    names = [name for _, name in starts]
    assert len(set(names)) == 189  # without repeats
    assert all(int(n.split("-")[1]) >= 640 for n in names)  # general pool
    assert len(arrivals_roll.general_pool(run)) == 4360


def test_the_cell_and_its_metrics_as_declared():
    """Held as the benchmark's own rule has it (``chipbench/README.md``,
    "Adding things"): the cell and its configuration by name, its six as
    one contiguous run of ``per_layer`` in their order, and of a metric's
    list only that the cell is in it."""
    cell = rules.cell_named(BENCH, CELL)
    assert cell == dict(cell, config=CONFIG, traffic="arrivals-roll-4",
                        chips=1)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == load(f"configs/{CONFIG}.json")["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"pod_to_bind_p50_ms", "setup_s"}
    rules.contiguous_run(BENCH, SIX_IN_ORDER)
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SIX_IN_ORDER:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "pod_to_bind_p50_ms"
        # only a cell whose traffic writes nodes has them to report
        assert rules.spec_of(ROOT, name)["needs"] == "node_ops"
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in rules.cells_of(BENCH, m)}
    # every list its pair is in, and what the rolled cluster adds
    steady = {m["name"] for m in BENCH["per_layer"]
              if "basic-5000.arrivals-steady" in rules.cells_of(BENCH, m)}
    assert steady <= declared
    assert {"carry_full_uploads", "carry_rows_per_batch",
            "burst_pod_to_bind_p99_ms", "solve_kernel_ms_per_batch",
            "solve_kernel_roofline", "pack_mask_rows_reused_share"} <= declared
    # what other cells' traffic alone can report stays theirs
    assert not any(n.startswith(("preempt_", "gang_", "shard_", "mesh_"))
                   for n in declared)
    assert "wave_drain_pods_per_s" not in declared


# -- the run -----------------------------------------------------------------


def rolling_cell(**roll_over):
    cell = harness.load_cell(ROOT, CELL, rehearsal=True)
    cell = copy.deepcopy(cell)
    assert cell["config"]["cluster"]["nodes"] == 48
    cell["config"]["node_settle_s"] = 0.25
    cell["mix"]["params"]["roll"].update(roll_over)
    return cell


def run_rolling(capsys, seed, seconds=2.0, **roll_over):
    line = harness.run_cell(
        rolling_cell(**roll_over), seed, seconds, False, time.perf_counter(),
        harness.find_device(1, rehearsal=True), rehearsal=True,
    )
    out = capsys.readouterr().out.splitlines()
    compared = [l for l in out if l.startswith("compare ")]
    return line, out, compared


def starts_with(lines, start):
    return [l for l in lines if l.startswith(start)]


@pytest.mark.parametrize("seed", [2**31 + 4100, 41, 998244353])
def test_the_rolled_cluster_is_correct_and_every_line_reads_0(capsys, seed):
    """The program against ``chipbench/reference.py`` on the rehearsal's
    48 nodes with six of them rolled, whatever the seed draws."""
    line, out, compared = run_rolling(capsys, seed)
    assert line["correct"] is True and line["failed"] == 0, out[-30:]
    assert all(l.endswith("-> ok") for l in compared), compared
    for start in (NODE_LINE, UNTOUCHED, REJOINED, DRAINED,
                  "check wave plain"):
        (mine,) = starts_with(compared, "compare " + start)
        assert ": 0 (limit 0) -> ok" in mine
    (rolled,) = [l for l in out if l.startswith("nodes rolled (window)")]
    assert rolled.startswith("nodes rolled (window): 6 replaced")
    drained = int(rolled.split(", ")[1].split(" ")[0])
    assert drained >= 6 and line["attempted"] == 120 + drained
    (reading,) = [l for l in out if l.startswith("node line reading: ")]
    assert "ms after it closed" in reading


def after_warmup(monkeypatch, break_it):
    """``break_it(run)`` once warm-up is over, as the window is built."""
    real = arrivals_roll.prepare

    def prepare(run, params, seconds):
        break_it(run)
        return real(run, params, seconds)

    monkeypatch.setattr(arrivals_roll, "prepare", prepare)


def test_a_scheduler_deaf_to_node_events_fails_the_node_line(
        capsys, monkeypatch):
    def deafen(run):
        for handler in ("add_node", "update_node", "remove_node"):
            monkeypatch.setattr(run.sched.cache, handler,
                                lambda *a, **k: None)

    after_warmup(monkeypatch, deafen)
    line, out, compared = run_rolling(
        capsys, 2**31 + 4101, rejoin_after_s=0.4, ready_after_s=0.8)
    assert line["correct"] is False, out[-30:]
    (mine,) = starts_with(compared, "compare " + NODE_LINE)
    assert mine.endswith("FAILED")
    assert int(mine.split(": ")[-1].split(" ")[0]) > 0


def test_a_scheduler_that_ignores_the_not_ready_taint_fails_the_node_line(
        capsys, monkeypatch):
    def blind(run):
        cache = run.sched.cache
        real = cache.add_node

        def add_node(node):
            node = copy.copy(node)
            node.spec = copy.copy(node.spec)
            node.spec.taints = []
            return real(node)

        monkeypatch.setattr(cache, "add_node", add_node)
        monkeypatch.setattr(cache, "update_node",
                            lambda old, new: add_node(new))

    after_warmup(monkeypatch, blind)
    line, out, compared = run_rolling(
        capsys, 2**31 + 4102, ready_after_s=0.8)
    assert line["correct"] is False, out[-30:]
    (mine,) = starts_with(compared, "compare " + NODE_LINE)
    assert mine.endswith("FAILED")
    # it hears of cordons, removals and joins: every pod fits, every
    # drained pod is offered again, the untouched nodes are as the rule
    # has them
    for start in (UNTOUCHED, DRAINED, "replay of"):
        (other,) = starts_with(compared, "compare " + start)
        assert other.endswith("-> ok"), other


def test_a_scheduler_that_never_hears_of_a_rejoined_node_fails_the_band(
        capsys, monkeypatch):
    def forget(run):
        cache = run.sched.cache
        gone = set()
        real_add, real_remove = cache.add_node, cache.remove_node

        def remove_node(node):
            gone.add(node.metadata.name)
            return real_remove(node)

        def add_node(node):
            if node.metadata.name in gone:
                return False
            return real_add(node)

        monkeypatch.setattr(cache, "remove_node", remove_node)
        monkeypatch.setattr(cache, "add_node", add_node)
        monkeypatch.setattr(cache, "update_node",
                            lambda old, new: add_node(new))

    after_warmup(monkeypatch, forget)
    line, out, compared = run_rolling(capsys, 2**31 + 4103)
    assert line["correct"] is False, out[-30:]
    failed = [l for l in compared if l.endswith("FAILED")]
    (mine,) = starts_with(compared, "compare " + REJOINED)
    assert failed == [mine], failed  # by that line alone
    assert int(mine.split(": ")[-1].split(" ")[0]) > 0
    assert "over by 0" in mine and "short by 0" not in mine


# -- the comparison on hand-made runs -----------------------------------------


def fake_run(per_node, log, window_end=100.0, grace=2.0, rolls=()):
    """A ``Run`` as the comparison reads it: 8 nodes of the cluster's
    shape, ``per_node[i]`` pods of the window on node ``i``."""
    names, snapshot = [], {}
    for i, count in enumerate(per_node):
        for k in range(count):
            names.append(f"arrive-1-{i}-{k}")
            snapshot[names[-1]] = f"node-{i}"
    return types.SimpleNamespace(
        config={
            "cluster": {"nodes": 8, "zones": 2,
                        "node": {"cpu": "32", "memory": "64Gi", "pods": 110}},
            "pod_classes": {"plain": {"cpu_milli": 250, "memory_mib": 512}},
            "rolling_check": {"ready_before_close_s": grace},
        },
        node_rows={f"node-{i}": i for i in range(8)}, node_log=log,
        window_names=names, created={n: "plain" for n in names},
        snapshots=[snapshot], window_end=window_end, rolls=list(rolls),
        watcher=types.SimpleNamespace(
            bind_time={n: 1.0 for n in names}, bind_node=dict(snapshot),
            node_time={}),
    )


def lines_of(capsys, run):
    ok = rolling.run(run, False)
    out = capsys.readouterr().out.splitlines()
    return ok, {start: int(starts_with(out, "compare " + start)[0]
                           .split(": ")[-1].split(" ")[0])
                for start in (UNTOUCHED, REJOINED, DRAINED)}


ROLL = [(10.0, "node-7", "cordon"), (10.1, "node-7", "remove"),
        (10.3, "node-7", "join"), (11.3, "node-7", "ready"),
        (11.4, "node-7", "report_status"), (20.0, "node-2", "report_status")]


def test_the_comparison_holds_level_nodes_and_tells_each_fault(capsys):
    # 8 empty nodes alike, 40 pods: five each is the rule's only answer
    ok, read = lines_of(capsys, fake_run([5] * 8, ROLL))
    assert ok and read == {UNTOUCHED: 0, REJOINED: 0, DRAINED: 0}
    # a status report touches nothing: node-2 is held like the others
    ok, read = lines_of(capsys, fake_run([5, 5, 7, 3, 5, 5, 5, 5], ROLL))
    assert not ok and read[UNTOUCHED] == 2 and read[REJOINED] == 0
    # the re-joined node left behind, the others level among themselves
    ok, read = lines_of(capsys, fake_run([5] * 7 + [0], ROLL))
    assert not ok and read == {UNTOUCHED: 0, REJOINED: 1, DRAINED: 0}
    # ... or ahead of them
    ok, read = lines_of(capsys, fake_run([5] * 7 + [9], ROLL))
    assert not ok and read == {UNTOUCHED: 0, REJOINED: 1, DRAINED: 0}
    # made Ready too late to be held to the band: left out, not failed
    ok, read = lines_of(capsys, fake_run([5] * 7 + [0], ROLL, window_end=12.0))
    assert ok and read == {UNTOUCHED: 0, REJOINED: 0, DRAINED: 0}
    # never made Ready again: the same
    ok, read = lines_of(capsys, fake_run([5] * 7 + [0], ROLL[:3]))
    assert ok and read[REJOINED] == 0


def test_a_drained_pod_without_a_bound_replacement_is_counted(capsys):
    rolls = [{"node": "node-7", "warmup": False,
              "drained": ["init-1-0", "init-1-1", "init-1-2"],
              "replacements": ["redo-2-0", "redo-2-1"]}]
    run = fake_run([5] * 8, ROLL, rolls=rolls)
    run.watcher.bind_time["redo-2-0"] = 12.0
    ok, read = lines_of(capsys, run)
    # one pod got no replacement, one replacement was never bound
    assert not ok and read == {UNTOUCHED: 0, REJOINED: 0, DRAINED: 2}


def test_the_reading_node_settle_s_is_set_from():
    watcher = types.SimpleNamespace(
        node_time={"a": [(0.0, True), (10.0, False), (12.0, True)],
                   "b": [(0.0, True)]},
        bind_node={"p": "a", "q": "a", "r": "a", "s": "b"},
        bind_time={"p": 9.0, "q": 10.03, "r": 10.2, "s": 10.5},
    )
    longest, count = rolling.closed_to_bind_ms(
        types.SimpleNamespace(watcher=watcher))
    assert count == 2 and longest == pytest.approx(200.0)


# -- the control --------------------------------------------------------------


def test_the_bfloat16_control_fails_this_deployments_check_wave():
    """``check_wave`` runs on the ballast pool, which is never rolled:
    the reference in bfloat16 in the program's place leaves pods that no
    tie-break explains, float32 none (the configuration's own limit)."""
    cls = load(f"configs/{CONFIG}.json")["pod_classes"]["plain"]
    pool = ballast_pool(640, zones=10, grid=8)
    pod = reference.PodClass(cls["cpu_milli"], cls["memory_mib"] * MIB)
    count, limit = cls["check"]["count"], cls["check"]["limit_pods"]
    lo, hi = reference.bands(pool, pod, count)
    sound, _ = reference.schedule(pool, pod, count, "float32")
    assert reference.outside(sound, lo, hi) <= limit
    control, _ = reference.schedule(pool, pod, count, "bfloat16")
    assert reference.outside(control, lo, hi) > 3 * max(limit, 1)


# -- the two host-clock readers ------------------------------------------------


def test_the_readers_of_what_a_rolled_nodes_owner_feels():
    run = types.SimpleNamespace(
        window_start=10.0, window_end=60.0,
        node_log=[(5.0, "w", "ready"), (20.0, "a", "ready"),
                  (30.0, "b", "ready"), (40.0, "c", "ready"),
                  (41.0, "a", "report_status")],
        watcher=types.SimpleNamespace(
            bind_node={"p1": "a", "p2": "a", "p3": "b", "p4": "w",
                       "old": "a"},
            bind_time={"p1": 20.05, "p2": 20.02, "p3": 30.04, "p4": 6.0,
                       "old": 12.0}),
        window_names=["arrive-1-0", "redo-2-0", "redo-2-1", "redo-3-0"],
        due={"arrive-1-0": 11.0, "redo-2-0": 20.0, "redo-2-1": 20.0,
             "redo-3-0": 30.0},
    )
    sample = {"run": run}
    # a: 20 ms (its first bind after Ready, not the one before the roll),
    # b: 40 ms, c took no pod, w was made Ready in warm-up
    assert node_ready_to_first_bind.read(
        sample, {"quantile": 50}) == pytest.approx(30.0)
    run.watcher.bind_time.update(
        {"arrive-1-0": 11.5, "redo-2-0": 20.01, "redo-2-1": 20.03})
    assert pod_to_bind_quantile_of_app.read(
        sample, {"quantile": 50, "app": "redo"}) == pytest.approx(20.0)
    run.node_log, run.window_names = [], ["arrive-1-0"]
    assert node_ready_to_first_bind.read(sample, {"quantile": 50}) is None
    assert pod_to_bind_quantile_of_app.read(
        sample, {"quantile": 50, "app": "redo"}) is None
