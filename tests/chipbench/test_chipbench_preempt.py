"""The deployment ``priority-tiers-5000`` and its cell
``priority-tiers-5000.preempt-1k``: the configuration and the mix letter
for letter, the plain reference ``preempt`` rule by rule, the window's
comparison and its control at the rehearsal pool's size and at the timed
one, the new readers and the preemption kernel's bytes on small samples.
The program's wave against the reference is ``tests/
test_preemption_reference.py`` (it imports the program's side)."""

import json
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench import harness, preempt_reference as ref, program_spans
from chipbench.checks.window_preempt_reference import (
    count as unexplained_count,
)
from chipbench.preempt_kernel_bytes import preempt_call_bytes
from chipbench.readers import (
    counter_ratio, kernel_time_per_span, preempt_kernel_roofline,
    stage_per_counter,
)

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "priority-tiers-5000.preempt-1k"
MIB, GIB = 1 << 20, 1 << 30
WAVE_METRICS = {
    "preempt_wave_ms_per_wave", "preempt_pack_ms_per_wave",
    "preempt_solve_ms_per_wave", "victim_wait_ms_per_wave",
    "preempt_requeue_ms_per_wave", "preempt_searches_per_preemptor",
    "preempt_carry_uploads_per_wave", "idle_under_preempt_wave_pct",
}
KERNEL_METRICS = {"preempt_kernel_ms_per_wave", "preempt_kernel_roofline"}


def load(path):
    return json.loads((ROOT / path).read_text())


CONFIG = load("chipbench/configs/priority-tiers-5000.json")
MIX = load("chipbench/traffic/preempt-1k.json")


def pool_counts(cluster):
    """(mid, upper) pods of the pool as ``Run.ballast_pods`` lays them."""
    spec, zones = cluster["ballast"], cluster["zones"]
    mid = upper = 0
    for i in range(zones * spec["per_zone"]):
        j = i // zones
        mid += j % spec["grid"]
        upper += (j // spec["grid"]) % spec["grid"]
    return mid, upper


def test_the_deployment_and_the_mix_letter_for_letter():
    cluster = CONFIG["cluster"]
    assert cluster["nodes"] == 5000 and cluster["zones"] == 10
    assert cluster["node"] == {"cpu": "32", "memory": "64Gi", "pods": 110}
    assert cluster["node"] == load(
        "chipbench/configs/basic-5000.json")["cluster"]["node"]
    # the source's 50,000 residents of 3000m / 6Gi, ten a node
    mid, upper = pool_counts(cluster)
    assert (mid, upper) == (1560, 1420)
    assert cluster["init_pods"] == {"count": 50000 - 2980, "class": "filler"}
    assert cluster["ballast"] == {
        "per_zone": 64, "grid": 6, "classes": ["mid", "upper"]}
    size = {"cpu_milli": 3000, "memory_mib": 6144}
    assert CONFIG["pod_classes"] == {
        "filler": size, "mid": dict(size, priority=10),
        "upper": dict(size, priority=50), "high": dict(size, priority=100),
    }
    assert 10 * 3000 <= 32000 < 11 * 3000  # ten fit, and one must leave
    assert CONFIG["wire"] == {"tpuSolver": {"maxBatch": 4096}}
    assert CONFIG["reduced"] == []
    assert CONFIG["checks"] == [
        "replay", "preemptor_guarantees", "window_preempt_reference"]
    assert CONFIG["expect_tier"] == "pallas"
    assert CONFIG["expect_tiers"] == {"preempt_wave": "pallas"}
    assert CONFIG["score_precision"] == "float32"
    assert CONFIG["window_preempt_reference"]["limit_preemptors"] == 0
    assert CONFIG["kernel_shape"] == load(
        "chipbench/configs/basic-5000.json")["kernel_shape"]
    for what in ("tier pool", "10 zones", "maxBatch 4096", "waves"):
        assert what in CONFIG["assumed"]
    # the rehearsal keeps the tiers: a node a zone with no filler at all
    small = harness._overlay(CONFIG, CONFIG["rehearsal"])["cluster"]
    mid, upper = pool_counts(small)
    assert small["init_pods"]["count"] == small["nodes"] * 2 - mid - upper
    assert CONFIG["rehearsal"]["expect_tiers"] == {"preempt_wave": "xla"}
    params = MIX["params"]
    assert MIX["generator"] == "preempt_refill_waves"
    assert params["preemptors"] == {"class": "high", "count": 1000}
    assert (params["creators"], params["chunk"]) == (4, 256)
    assert (params["warmup_waves"], params["deadline_s"]) == (2, 10)
    # what the generator waits for a wave: a cold compile of the
    # preemption kernel holds the first one 19 s
    assert params["wave_timeout_s"] == 60
    assert (params["delete_timeout_s"], params["refill_timeout_s"]) == (60, 60)
    assert MIX["trace_seconds"] == 8


class WaveRun:
    """What ``preempt_refill_waves.one_wave`` calls of a ``Run``, with a
    scheduler that binds a wave ``binds_after`` seconds after it began
    (all at once, as a wave that waits for a compile does) on a clock
    that only the waits move."""

    def __init__(self, binds_after):
        self.binds_after, self.t = binds_after, 0.0
        self.waited, self.waves, self.created = [], [], {}

    def now(self):
        return self.t

    def phase(self, name):
        import contextlib
        return contextlib.nullcontext()

    def make_pods(self, cls, count, app):
        from types import SimpleNamespace as NS
        base = len(self.created)
        names = [f"{app}-{base + i}" for i in range(count)]
        self.created.update(dict.fromkeys(names, cls))
        return [NS(metadata=NS(name=n)) for n in names]

    def create(self, pods, **how):
        pass

    def wait_bound(self, names, timeout_s):
        self.waited.append(timeout_s)
        self.t += min(timeout_s, self.binds_after)
        return self.binds_after <= timeout_s

    def record_wave(self, start, names):
        # as Run.record_wave: a wave none of whose pods is bound has no
        # last bind (the driver's first check ended here, PERF.md 6)
        times = [self.t] if self.t - start >= self.binds_after else []
        self.waves.append({"drain_s": times[-1] - start})
        return self.waves[-1]

    def snapshot(self):
        return {}

    def delete(self, names, timeout_s):
        pass

    def evicted(self):
        return {}


@pytest.mark.parametrize("binds_after, warm", [
    (19.0, True),    # the cold compile the driver's first check met
    (0.7, True),
    (0.7, False),
    (12.0, False),   # a stalled wave of the window still ends whole
])
def test_a_wave_is_waited_for_beyond_a_pods_deadline(binds_after, warm):
    from chipbench.generators import preempt_refill_waves as generator

    params = MIX["params"]
    assert params["deadline_s"] < 19.0 < params["wave_timeout_s"]
    run = WaveRun(binds_after)
    generator.one_wave(run, params, warm=warm)
    assert run.waited == [params["wave_timeout_s"],
                          params["refill_timeout_s"]]
    assert run.waves[0]["drain_s"] == binds_after


def test_a_warmup_wave_never_bound_ends_the_run_by_name():
    from chipbench.generators import preempt_refill_waves as generator

    run = WaveRun(binds_after=500)
    with pytest.raises(harness.BenchError, match="warm-up wave of 1000"):
        generator.warmup(run, MIX["params"])
    assert run.waited == [MIX["params"]["wave_timeout_s"]]
    assert run.waves == []


def test_the_cell_and_its_metrics_as_declared():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="priority-tiers-5000",
                        traffic="preempt-1k", chips=1)
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "priority-tiers-5000"]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    for word in ("scheduler_perf", "performance-config.yaml", "Preemption",
                 "5000 nodes", "Preemption/5000"):
        assert word in entry["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in WAVE_METRICS | KERNEL_METRICS:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "pod_to_bind_p50_ms"
        # a cell whose traffic preempts nothing gives them nothing to read
        assert rules.spec_of(ROOT, name)["needs"] == "preemptors"
    assert rules.on_chip_only(ROOT, WAVE_METRICS | KERNEL_METRICS) == KERNEL_METRICS
    for name in ("burst_pod_to_bind_p99_ms", "wave_drain_pods_per_s",
                 "solve_kernel_ms_per_batch", "solve_kernel_roofline",
                 *rules.NEW):
        assert CELL in per_layer[name]["workloads"], name
    for name in ("carry_full_uploads", "carry_rows_per_batch",
                 "shard_kernel_roofline", "pack_family_node_rows_reused_share"):
        assert CELL not in per_layer[name]["workloads"], name


# -- the reference, rule by rule ---------------------------------------------


def pod(name, priority, cpu=3000, mem=6 * GIB, start=0.0, labels=()):
    return ref.Pod(name, priority, cpu, mem, start, labels)


def node(name, pods, cpu=32000, mem=64 * GIB, count=110):
    return ref.Node(name, cpu, mem, count, pods)


def names(pods):
    return [p.name for p in pods]


def test_select_victims_reprieves_the_more_important_first():
    residents = [pod("f0", 0), pod("u0", 50), pod("m0", 10), pod("f1", 0),
                 pod("m1", 10)] + [pod(f"f{k}", 0) for k in range(2, 7)]
    full = node("n", residents)
    found = ref.select_victims(full, pod("h", 100))
    assert names(found.pods) == ["f6"] and found.violations == 0
    # an equal priority is no victim; a pod that needs three evicts the
    # three least important, later starts first among equals
    assert ref.select_victims(full, pod("z", 0)) is None
    big = ref.select_victims(full, pod("h", 100, cpu=9000))
    assert names(big.pods) == ["f4", "f5", "f6"]
    early = [pod(f"p{k}", 0, start=10.0 - k) for k in range(10)]
    assert names(ref.select_victims(
        node("n", early), pod("h", 100)).pods) == ["p0"]
    # below the preemptor but above the others: the upper pods stay
    mid_only = ref.select_victims(full, pod("q", 10))
    assert names(mid_only.pods) == ["f6"]
    # nothing below it is enough: no candidate
    assert ref.select_victims(
        node("n", [pod(f"u{k}", 50) for k in range(10)]), pod("q", 10)) is None


def test_nominated_pods_of_equal_or_higher_priority_count():
    full = node("n", [pod(f"f{k}", 0) for k in range(10)])
    one = ref.select_victims(full, pod("h", 100), nominated=[pod("a", 100)])
    assert names(one.pods) == ["f8", "f9"]
    lower = ref.select_victims(full, pod("h", 100), nominated=[pod("a", 99)])
    assert names(lower.pods) == ["f9"]
    crowded = ref.select_victims(
        full, pod("h", 100), nominated=[pod(f"a{k}", 100) for k in range(10)])
    assert crowded is None


def test_budgets_put_violating_pods_first_and_count_them():
    web = (("app", "web"),)
    residents = [pod("w0", 0, labels=web), pod("w1", 0, labels=web)] + [
        pod(f"f{k}", 0) for k in range(8)]
    budget = [ref.Budget(selector=web, allowed=1)]
    found = ref.select_victims(node("n", residents), pod("h", 100),
                               budgets=budget)
    # w1 finds the budget spent: it is reprieved first, and f7 leaves
    assert names(found.pods) == ["f7"] and found.violations == 0
    tight = ref.select_victims(
        node("n", residents[:2], cpu=3000), pod("h", 100), budgets=budget)
    assert names(tight.pods) == ["w1", "w0"] and tight.violations == 1
    inert = ref.select_victims(node("n", residents), pod("h", 100))
    assert names(inert.pods) == ["f7"]


def victims(*pods, violations=0):
    return ref.Victims(list(pods), violations)


def test_pick_node_rule_by_rule():
    pick = ref.pick_node
    assert pick({}) == []
    # a node that needs no victim wins, and every such node is tied
    assert pick({"a": victims(pod("f", 0)), "b": victims(), "c": victims()}) \
        == ["b", "c"]
    # 1: fewest budget violations
    assert pick({"a": victims(pod("f", 0), violations=1),
                 "b": victims(pod("u", 50))}) == ["b"]
    # 2: the lowest highest-priority victim
    assert pick({"a": victims(pod("m", 10)), "b": victims(pod("f", 0)),
                 "c": victims(pod("f2", 0))}) == ["b", "c"]
    # 3: the smallest sum of priorities, each offset by 2**31
    assert pick({"a": victims(pod("m", 10), pod("m2", 10)),
                 "b": victims(pod("m", 10), pod("f", 0))}) == ["b"]
    # 4: fewest victims, where a victim of the lowest priority adds nothing
    lowest = -(1 << 31)
    assert pick({"a": victims(pod("m", 10), pod("l", lowest)),
                 "b": victims(pod("m", 10))}) == ["b"]
    # 5: the latest start of the highest-priority victims
    assert pick({"a": victims(pod("m", 10, start=5.0)),
                 "b": victims(pod("m", 10, start=9.0))}) == ["b"]
    # the sum is exact where float32 is not
    near = {"a": victims(pod("m", 10), pod("f", 1)),
            "b": victims(pod("m", 10), pod("f", 0))}
    assert pick(near) == ["b"] and pick(near, "float32") == ["a", "b"]


def test_a_wave_sees_the_nominations_before_it():
    nodes = [node("a", [pod(f"m{k}", 10) for k in range(5)]
                  + [pod(f"u{k}", 50) for k in range(5)]),
             node("b", [pod("u9", 50)] + [pod(f"f{k}", 0) for k in range(9)]),
             node("c", [pod(f"g{k}", 0) for k in range(10)])]
    wave = [pod(f"h{k}", 100) for k in range(4)]
    stay = ref.wave(nodes, wave)
    assert [(d.node, names(d.victims)) for d in stay] == [
        ("b", ["f8"]), ("c", ["g9"]), ("b", ["f7", "f8"]), ("c", ["g8", "g9"])]
    assert stay[0].tied == ["b", "c"] and stay[1].tied == ["c"]
    gone = ref.wave(nodes, wave, evict=True)
    assert [(d.node, names(d.victims)) for d in gone] == [
        ("b", ["f8"]), ("b", ["f7"]), ("b", ["f6"]), ("b", ["f5"])]
    for decisions, evict in ((stay, False), (gone, True)):
        landed, left = ref.tally(decisions, evict)
        assert ref.unexplained(nodes, wave[0], landed, left, 4) == {
            "nodes": 0, "victims": 0, "unplaced": 0}
    assert names(nodes[1].pods)[-1] == "f8"  # the caller's nodes are untouched
    # a selector keeps a preemptor off the nodes it names not
    only_a = ref.wave(nodes, wave[:1], eligible=[["a"]])
    assert (only_a[0].node, names(only_a[0].victims)) == ("a", ["m4"])


def test_what_the_comparison_counts():
    nodes = [node("a", [pod("m", 10)] + [pod(f"f{k}", 0) for k in range(9)]),
             node("b", [pod(f"u{k}", 50) for k in range(10)]),
             node("c", [pod(f"g{k}", 0) for k in range(10)])]
    h = pod("h", 100)
    filler, upper, mid = pod("f", 0), pod("u", 50), pod("m", 10)
    ok = ref.unexplained(nodes, h, {"a": 1, "c": 1}, {"a": [filler],
                                                      "c": [filler]}, 2)
    assert ok == {"nodes": 0, "victims": 0, "unplaced": 0}
    # both on one node, their victims gone one after the other: an order
    both = ref.unexplained(nodes, h, {"c": 2}, {"c": [filler, filler]}, 2)
    assert both == {"nodes": 0, "victims": 0, "unplaced": 0}
    # an upper pod left while fillers were left
    wrong = ref.unexplained(nodes, h, {"b": 1, "c": 1},
                            {"b": [upper], "c": [filler]}, 2)
    assert wrong["nodes"] == 1 and wrong["victims"] == 0
    # the right node, the wrong resident
    swapped = ref.unexplained(nodes, h, {"a": 1}, {"a": [mid]}, 1)
    assert swapped == {"nodes": 0, "victims": 1, "unplaced": 0}
    # a victim and no preemptor; a preemptor that landed nowhere
    lost = ref.unexplained(nodes, h, {"c": 1}, {"a": [filler], "c": [filler]}, 2)
    assert lost == {"nodes": 0, "victims": 1, "unplaced": 1}


# -- the control, at the cell's own clusters -----------------------------------


def cluster_before_a_wave(config):
    """Every node full: the pool as ``Run.ballast_pods`` lays it, then
    fillers up to what the node holds."""
    cluster = config["cluster"]
    shape, spec, zones = cluster["node"], cluster["ballast"], cluster["zones"]
    classes = config["pod_classes"]
    kinds = {c: ref.Pod(c, int(classes[c].get("priority", 0)),
                        classes[c]["cpu_milli"], classes[c]["memory_mib"] * MIB)
             for c in classes}
    cap_cpu = int(shape["cpu"]) * 1000
    holds = cap_cpu // classes["filler"]["cpu_milli"]
    nodes, fillers = [], 0
    for i in range(cluster["nodes"]):
        j = i // zones
        pods = []
        if j < spec["per_zone"]:
            pods += [kinds["mid"]] * (j % spec["grid"])
            pods += [kinds["upper"]] * ((j // spec["grid"]) % spec["grid"])
        fillers += holds - len(pods)
        pods += [kinds["filler"]] * (holds - len(pods))
        nodes.append(ref.Node(f"node-{i}", cap_cpu,
                              int(shape["memory"][:-2]) * GIB, shape["pods"],
                              pods))
    assert fillers == cluster["init_pods"]["count"]
    return nodes, kinds["high"]


def control(config, count):
    nodes, high = cluster_before_a_wave(config)
    out = {}
    for what, how in (("float32", {"precision": "float32"}),
                      ("bfloat16", {"precision": "bfloat16"}),
                      ("blind", {"seen_priority": lambda p: 0})):
        landed, left = ref.tally(ref.wave(nodes, [high] * count, **how))
        out[what] = unexplained_count(
            ref.unexplained(nodes, high, landed, left, count))
    return out


def test_the_control_fails_the_comparison_at_the_rehearsal_pools_size():
    """Reading every resident's priority as 0 lands preemptors on the
    nodes that hold no priority-0 pod; float32 reads 0. bfloat16 reads 0
    too: every sum and key of this deployment is a bfloat16 number or
    rounds to the same side (PERF.md section 2), so precision is not
    the control here."""
    small = harness._overlay(CONFIG, CONFIG["rehearsal"])
    count = harness._overlay(MIX, MIX["rehearsal"])["params"]["preemptors"]["count"]
    limit = CONFIG["window_preempt_reference"]["limit_preemptors"]
    found = control(small, count)
    assert found["float32"] <= limit and found["bfloat16"] <= limit
    assert found["blind"] == 4 > limit  # one node a zone, four zones


def test_the_control_fails_the_comparison_at_the_timed_size():
    found = control(CONFIG, MIX["params"]["preemptors"]["count"])
    assert found == {"float32": 0, "bfloat16": 0, "blind": 10}


# -- readers and bytes -----------------------------------------------------------


def sample(**end):
    start = {"t": 0.0, "stage_seconds": {"preempt_wave": 1.0, "victim_wait": 0.5},
             "preempt_waves": 10, "device_preemptions": 100, "state_uploads": 7}
    return {"start": start, "end": dict(
        {"t": 51.0, "preempt_waves": 30, "device_preemptions": 2460,
         "state_uploads": 65}, **end)}


def test_stage_per_counter():
    args = rules.spec_of(ROOT, "preempt_wave_ms_per_wave")["args"]
    s = sample(stage_seconds={"preempt_wave": 5.0})
    assert stage_per_counter.read(s, args) == pytest.approx(200.0)
    # a program from before the children's totals gives nothing
    pack = rules.spec_of(ROOT, "preempt_pack_ms_per_wave")["args"]
    assert stage_per_counter.read(s, pack) is None
    # with them, a child that never ran reads 0 and the two add up
    s["end"]["stage_seconds"].update({"preempt_wave.solve": 2.0,
                                      "preempt_wave.pack_wait": 0.4})
    assert stage_per_counter.read(s, pack) == pytest.approx(20.0)
    s["end"]["stage_seconds"]["preempt_wave.pack_build"] = 0.6
    assert stage_per_counter.read(s, pack) == pytest.approx(50.0)
    # no wave in the window, or a run without the counter
    still = sample(stage_seconds={"preempt_wave": 5.0}, preempt_waves=10)
    assert stage_per_counter.read(still, args) is None
    del still["end"]["preempt_waves"]
    assert stage_per_counter.read(still, args) is None


class WavesRun:
    window_names = [f"p{k}" for k in range(2000)]
    waves = [{"in_window": False}] + [{"in_window": True}] * 20


def test_counter_ratio():
    s = dict(sample(stage_seconds={}), run=WavesRun())
    searches = rules.spec_of(ROOT, "preempt_searches_per_preemptor")["args"]
    assert counter_ratio.read(s, searches) == pytest.approx(1.18)
    uploads = rules.spec_of(ROOT, "preempt_carry_uploads_per_wave")["args"]
    assert counter_ratio.read(s, uploads) == pytest.approx(2.9)
    assert counter_ratio.read(s, {"counter": "state_uploads",
                                  "over": "preempt_waves"}) == pytest.approx(2.9)
    assert counter_ratio.read(s, {"counter": "gone", "over": "window_pods"}) is None
    assert counter_ratio.read(s, {"counter": "state_uploads",
                                  "over": "gone"}) is None


def test_the_preemption_kernels_bytes_against_a_hand_count():
    shape = CONFIG["preempt_kernel_shape"]
    assert shape == {"n": 5000, "v": 16, "a": 3, "r": 8, "u": 8, "m": 8,
                     "chunk": 512}
    # node rows: allocatable 3, priorities + starts + flags 3 x 16,
    # requests twice 2 x 48, candidate rows 8, nominations 24, state 8 + 8
    per_node = 3 + 48 + 96 + 8 + 24 + 16
    # a preemptor: request 8 + 3 scalars in, 3 out, one 128-lane pack row
    per_pod = 11 + 3 + 128
    assert preempt_call_bytes(**shape) == 4 * (5000 * per_node + 512 * per_pod + 8)
    assert 3 * 16 + 3 * 16 + 3 <= 128  # the pack row fits one lane tile


def test_the_kernels_readers_on_a_small_sample(monkeypatch):
    ops = {"pallas_preempt_solve.1": (4, 0.006), "pallas_greedy_solve.1": (9, 0.027),
           "copy-start.4": (30, 0.002)}
    s = {"trace": {"ops": ops}, "root": ROOT, "device": {"kind": "TPU v5 lite"},
         "cell": {"config": CONFIG, "name": CELL}}
    args = rules.spec_of(ROOT, "preempt_kernel_roofline")["args"]
    least_s = preempt_call_bytes(**CONFIG["preempt_kernel_shape"]) / 819e9
    assert preempt_kernel_roofline.read(s, args) == pytest.approx(
        100 * least_s / 0.0015)
    assert 0 < preempt_kernel_roofline.read(s, args) < 1
    s["device"]["kind"] = "cpu"
    with pytest.raises(KeyError):
        preempt_kernel_roofline.read(s, args)
    assert preempt_kernel_roofline.read(dict(s, trace={"ops": {}}), args) is None
    # a configuration without the shape gives the reader nothing
    bare = dict(s, cell={"config": {}, "name": CELL})
    assert preempt_kernel_roofline.read(bare, args) is None
    per_wave = rules.spec_of(ROOT, "preempt_kernel_ms_per_wave")["args"]
    spans = {"window": (0, 100), "spans": [
        {"name": "sched/preempt_wave", "start": 10 * k, "end": 10 * k + 5,
         "line": ("/host:CPU", 0), "stats": {}} for k in range(3)
    ] + [{"name": "sched/preempt_wave", "start": 200, "end": 205,
          "line": ("/host:CPU", 0), "stats": {}}]}
    monkeypatch.setattr(program_spans, "load", lambda sample: spans)
    assert kernel_time_per_span.read(s, per_wave) == pytest.approx(2.0)
    monkeypatch.setattr(program_spans, "load", lambda sample: None)
    assert kernel_time_per_span.read(s, per_wave) is None


def test_the_waves_spans_carry_their_stats_and_the_metrics_read_them(tmp_path):
    """A traced rehearsal in a root of its own, the trace kept: the
    wave's three spans with the stats PERF.md section 3 lists, and every
    metric of the wave in the line."""
    import os
    import shutil
    import subprocess
    import sys

    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(ROOT / "chipbench", copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", copy)
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.proving.run", "--workload", CELL,
         "--seed", str(2**31 + 321), "--seconds", "1", "--trace", "1",
         "--rehearsal", "--keep-trace", str(tmp_path / "kept")],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert WAVE_METRICS <= set(line["metrics"])
    assert KERNEL_METRICS.isdisjoint(line["metrics"])  # a CPU has no kernel
    for name in ("preempt_wave_ms_per_wave", "preempt_solve_ms_per_wave",
                 "preempt_searches_per_preemptor"):
        assert line["metrics"][name]["value"] > 0, name
    parts = sum(line["metrics"][name]["value"] for name in (
        "preempt_pack_ms_per_wave", "preempt_solve_ms_per_wave"))
    assert parts <= line["metrics"]["preempt_wave_ms_per_wave"]["value"]
    assert "preemptors searched again in the window" in proc.stdout
    (path,) = (tmp_path / "kept").glob("*.xplane.pb")
    trace = program_spans.read_trace(str(path))
    by_name = {}
    for sp in trace["spans"]:
        by_name.setdefault(sp["name"], []).append(sp["stats"])
    waves = by_name["sched/preempt_wave"]
    assert all(set(w) >= {"pods", "searched", "nominated", "victims", "tier",
                          "v_max", "nodes", "pack"} for w in waves)
    first = [w for w in waves if w["victims"]]
    assert first and all(
        w["pods"] == w["searched"] == w["nominated"] == w["victims"] == 16
        and w["tier"] == "xla" and w["nodes"] == 24 and w["v_max"] == 8
        and w["pack"] in ("built", "reused") for w in first)
    assert all(set(w) >= {"victims", "timed_out"}
               for w in by_name["sched/victim_wait"])
    assert all(w["victims"] == 16 for w in by_name["sched/victim_wait"])
    assert all("pods" in w for w in by_name["sched/preempt_requeue"])
    for child in ("pack_wait", "solve"):
        assert f"sched/preempt_wave.{child}" in by_name
