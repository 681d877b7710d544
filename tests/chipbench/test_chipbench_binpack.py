"""The deployment ``gpu-binpack-5000`` and its cell
``gpu-binpack-5000.binpack-burst-6k`` (PR 43): the files as ISSUE 43's
tables give them, the cell's entries, the cell at rehearsal size through
the real harness, its three broken twins (the default score rule put
under the program, a fit without the fifth column, a scheduler deaf to
the node selector), each failing by the line named, the controls at the
timed size with the reference alone, and the three per-layer metrics and
the stats they read."""

import json
import time
from pathlib import Path

import benchmark_rules as rules
import numpy as np
import pytest

from chipbench import binpack_reference as ref
from chipbench import harness, kernel_bytes, program_spans
from chipbench.generators import binpack_waves

ROOT = Path(__file__).resolve().parents[2]
CELL = "gpu-binpack-5000.binpack-burst-6k"
CONFIG = "gpu-binpack-5000"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIB = 1 << 20
GPU = "nvidia.com/gpu"
#: the cell's own three, in the order ``per_layer`` holds them
THREE_IN_ORDER = [
    "solve_resource_dims", "solve_most_allocated_share",
    "pack_templates_per_batch",
]
RESIDENTS = "set-up against the reference: of the"
WINDOW = "window against the reference: pods of the worst wave outside"
OVER = "binpack guarantees over"
POOL = "binpack guarantees: pods bound outside the pool"


def load(name):
    return json.loads((ROOT / "chipbench" / name).read_text())


# -- the files ---------------------------------------------------------------


def test_the_deployment_at_the_sizes_the_issue_gives():
    config = load(f"configs/{CONFIG}.json")
    assert config["reduced"] == [] and config["layout"]["chips"] == 1
    assert len(config["source"]) <= 200
    for words in ("BASELINE.json config 4", "most_allocated.go", "GPUBinPack"):
        assert words in config["source"], words
    cluster = config["cluster"]
    assert (cluster["nodes"], cluster["zones"]) == (5000, 10)
    assert cluster["node"] == {"cpu": "32", "memory": "64Gi", "pods": 110,
                               "scalars": {GPU: 8}}
    assert "ballast" not in cluster
    assert cluster["init_pods"] == {"count": 20000, "class": "gpu1"}
    # one size per GPU: a node's use is always a multiple of gpu1's
    classes = config["pod_classes"]
    assert sorted(classes) == ["gpu1", "gpu2", "gpu4", "gpu8"]
    for name, cls in classes.items():
        gpus = int(name[3:])
        assert cls == {"cpu_milli": 3500 * gpus, "memory_mib": 7000 * gpus,
                       "scalars": {GPU: gpus}}
    # eight GPUs fill a node at 87.5 % of its cpu and 85.4 % of its
    # memory: cpu and memory take a ninth gpu1, the GPU column refuses it
    assert 9 * 3500 <= 32000 and 9 * 7000 <= 64 * 1024
    assert 8 * 3500 / 32000 == 0.875
    assert round(8 * 7000 / (64 * 1024), 3) == 0.854
    wire = config["wire"]
    assert wire["tpuSolver"] == {"maxBatch": 4096} and set(wire) == {
        "tpuSolver", "profiles"}
    (profile,) = wire["profiles"]
    assert profile["plugins"] == {"score": {
        "disabled": [{"name": "NodeResourcesLeastAllocated"},
                     {"name": "NodeResourcesBalancedAllocation"}],
        "enabled": [{"name": "NodeResourcesMostAllocated", "weight": 1}],
    }}
    assert config["checks"] == [
        "replay", "binpack_guarantees", "window_binpack_reference"]
    assert config["expect_tier"] == "pallas" and "expect_tiers" not in config
    assert config["score_precision"] == "float32"
    assert config["binpack_guarantees"]["limit_nodes_over"] == 0
    assert config["binpack_guarantees"]["limit_pods_outside_pool"] == 0
    assert config["window_binpack_reference"]["limit_pods"] == 0
    guarantees = " ".join(config["guarantees"])
    for words in ("cpu, memory, pod count and nvidia.com/gpu", "bound once",
                  "pool its node selector names", "every pod of a wave is "
                  "bound", "NodeResourcesMostAllocated", "expect_tier",
                  "host path answers counts as failed"):
        assert words in guarantees, words
    # every value that is not the source's is assumed, with its reason
    for key in ("node shape", "maxBatch", "accelerator pool", "pod classes",
                "four sizes", "20,000 residents", "MostAllocated at weight 1",
                "waves", "kernel_shape", "rehearsal"):
        assert any(key in k for k in config["assumed"]), key
    # the greedy kernel's call at five resource columns, and its bytes
    shape = config["kernel_shape"]
    assert shape == {"n_cap": 5632, "r": 5, "u": 16, "b": 4096,
                     "family_rows": 0, "families": 0}
    basic = load("configs/basic-5000.json")["kernel_shape"]
    assert kernel_bytes.solve_call_bytes(**shape) > (
        kernel_bytes.solve_call_bytes(**basic))
    rehearsal = config["rehearsal"]
    assert rehearsal["expect_tier"] == "xla"
    assert rehearsal["wire"] == {"tpuSolver": {"maxBatch": 64}}
    assert 20 <= rehearsal["cluster"]["nodes"] <= 60


def test_the_mix_holds_the_issues_table_value_for_value():
    mix = load("traffic/binpack-burst-6k.json")
    five_k = load("traffic/burst-5k.json")["params"]
    assert mix["generator"] == "binpack_waves"
    assert "window_check" not in mix and mix["trace_seconds"] == 8
    params = mix["params"]
    assert params["wave"] == [
        {"class": "gpu1", "zones": [0, 1, 2, 3], "pods_per_app": 1024},
        {"class": "gpu2", "zones": [4, 5], "pods_per_app": 512},
        {"class": "gpu4", "zones": [6, 7], "pods_per_app": 256},
        {"class": "gpu8", "zones": [8, 9], "pods_per_app": 128},
    ]
    pods = sum(len(p["zones"]) * p["pods_per_app"] for p in params["wave"])
    gpus = sum(len(p["zones"]) * p["pods_per_app"] * int(p["class"][3:])
               for p in params["wave"])
    assert (pods, gpus) == (5888, 10240)
    # every pool is asked 1,024 GPUs and no zone is asked twice
    zones = [z for p in params["wave"] for z in p["zones"]]
    assert sorted(zones) == list(range(10))
    assert all(p["pods_per_app"] * int(p["class"][3:]) == 1024
               for p in params["wave"])
    assert params["shuffle"] is True
    # closed waves as burst-5k's
    for key in ("chunk", "creators", "warmup_waves", "delete_timeout_s"):
        assert params[key] == five_k[key], key
    assert (params["chunk"], params["creators"], params["warmup_waves"],
            params["deadline_s"], params["delete_timeout_s"]) == (
        256, 4, 2, 60, 60)
    assert params["resident_delete_share"] == 0.25
    assert set(params) == {
        "wave", "shuffle", "chunk", "creators", "warmup_waves", "deadline_s",
        "delete_timeout_s", "resident_delete_share"}
    assert "no departure" in mix["why_warmup"]


def test_the_cell_and_its_metrics_as_declared():
    """Held as the benchmark's own rule has it (``chipbench/README.md``,
    "Adding things"): the cell and its configuration by name, its three
    as one contiguous run of ``per_layer`` in their order, and of a
    metric's list only that the cell is in it."""
    cell = rules.cell_named(BENCH, CELL)
    assert cell == dict(cell, config=CONFIG, traffic="binpack-burst-6k",
                        chips=1)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == load(f"configs/{CONFIG}.json")["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    rules.contiguous_run(BENCH, THREE_IN_ORDER)
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in THREE_IN_ORDER:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "pod_to_bind_p50_ms"
        spec = rules.spec_of(ROOT, name)
        assert spec["reader"] == "span_stat_mean"  # a reader the benchmark has
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in rules.cells_of(BENCH, m)}
    # every list that burst-10k and burst-5k are both in, and the tail
    both = {m["name"] for m in BENCH["per_layer"] if "workloads" in m
            and {"basic-5000.burst-10k", "spread-anti-5000.burst-5k"}
            <= set(m["workloads"])}
    assert both - {n for n in both if "listed_from" in rules.spec_of(ROOT, n)
                   } <= declared
    assert {"solve_kernel_roofline", "solve_kernel_ms_per_batch",
            "pack_masks_ms_per_batch", "pack_mask_rows_reused_share",
            "wave_drain_pods_per_s", "burst_pod_to_bind_p99_ms"} <= declared
    # what other cells' traffic alone can report stays theirs
    assert not any(n.startswith(("preempt_", "gang_", "shard_", "mesh_",
                                 "node_", "carry_")) for n in declared)
    assert "pack_family_node_rows_reused_share" not in declared


# -- the run -----------------------------------------------------------------


def run_cell(capsys, trace=0, seed=2**31 + 4300, keep_trace=""):
    args = harness.public_arguments("test").parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "1",
        "--trace", str(trace), "--rehearsal",
    ])
    with rules.one_traced_run_at_a_time(ROOT):
        rc = harness.run_one(args, time.perf_counter(), keep_trace=keep_trace)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    return json.loads(out[-1]), out, [l for l in out
                                      if l.startswith("compare ")]


def compared(lines, start):
    (line,) = [l for l in lines if l.startswith("compare " + start)]
    return int(line.split(": ")[-1].split(" ")[0]), line


def after_warmup(monkeypatch, break_it):
    """``break_it(run)`` once warm-up is over, as the window is built."""
    real = binpack_waves.prepare

    def prepare(run, params, seconds):
        break_it(run)
        return real(run, params, seconds)

    monkeypatch.setattr(binpack_waves, "prepare", prepare)


@pytest.mark.parametrize("seed", [2**31 + 4300, 43, 998244353])
def test_the_cell_is_correct_and_every_line_reads_0(capsys, seed):
    line, out, lines = run_cell(capsys, seed=seed)
    assert line["correct"] is True and line["failed"] == 0, out[-30:]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {
        "bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    assert all(l.endswith("-> ok") for l in lines), lines
    for start in (RESIDENTS, WINDOW, OVER, POOL):
        assert compared(lines, start)[0] == 0
    (note,) = [l for l in out if l.startswith("binpack waves: ")]
    assert "160 residents on 20 nodes" in note and "40 deleted" in note
    (window,) = [l for l in out if l.startswith("window: ")]
    assert "pods_fallback 0" in window


def test_a_scheduler_deaf_to_the_profile_cannot_run_the_deployment(
        capsys, monkeypatch):
    """What the parent is: the device scores the default rule whatever
    the profile says, set-up spreads the residents, and the run ends
    before warm-up with no result line (exit code 2)."""
    from kubernetes_tpu.scheduler import batch

    monkeypatch.setattr(
        batch.GreedyConfig, "from_score_weights",
        classmethod(lambda cls, weights: cls()),
    )
    args = harness.public_arguments("test").parse_args([
        "--workload", CELL, "--seed", "43", "--seconds", "1", "--trace", "0",
        "--rehearsal",
    ])
    assert harness.run_one(args, time.perf_counter()) == 2
    captured = capsys.readouterr()
    assert "residents on 40 nodes" in captured.err
    assert "does not score by its profile" in captured.err
    assert not captured.out.strip().splitlines()[-1].startswith("{")


def test_the_default_rule_under_the_program_fails_the_windows_line_alone(
        capsys, monkeypatch):
    from kubernetes_tpu.scheduler import batch

    def deafen(run):  # a driver's override: every profile's rule
        run.sched.solver_config = batch.GreedyConfig()

    after_warmup(monkeypatch, deafen)
    line, out, lines = run_cell(capsys, seed=2**31 + 4301)
    assert line["correct"] is False and line["failed"] == 0, out[-30:]
    value, text = compared(lines, WINDOW)
    assert value > 0 and text.endswith("FAILED")
    # every pod still binds, fits and stays in its pool, and set-up's
    # residents were packed: that line alone
    failed = [l for l in lines if not l.endswith("-> ok")]
    assert failed == [text], failed


def test_a_fit_without_the_fifth_column_fails_the_gpu_line(
        capsys, monkeypatch):
    from kubernetes_tpu.scheduler import batch

    real = batch.pack_pod_batch

    def blind(pods, dims, **kw):
        packed = real(pods, dims, **kw)
        packed.requests[:, 4:] = 0  # cpu and memory take a ninth gpu1
        return packed

    after_warmup(
        monkeypatch,
        lambda run: monkeypatch.setattr(batch, "pack_pod_batch", blind),
    )
    line, out, lines = run_cell(capsys, seed=2**31 + 4302)
    assert line["correct"] is False, out[-30:]
    value, text = compared(lines, OVER)
    assert value > 0 and text.endswith("FAILED")
    assert compared(lines, POOL)[0] == 0
    # the replay's own fit line reads cpu, memory and pod count alone
    assert compared(lines, "replay of")[0] == 0


def test_a_scheduler_deaf_to_the_selector_fails_the_pool_line(
        capsys, monkeypatch):
    from kubernetes_tpu.scheduler import batch

    real = batch.static_mask_compact

    def everywhere(pods, snapshot, nt, kept=None):
        rows, index = real(pods, snapshot, nt, kept)
        return np.ones_like(rows), index

    after_warmup(
        monkeypatch,
        lambda run: monkeypatch.setattr(batch, "static_mask_compact",
                                        everywhere),
    )
    line, out, lines = run_cell(capsys, seed=2**31 + 4303)
    assert line["correct"] is False, out[-30:]
    value, text = compared(lines, POOL)
    assert value > 0 and text.endswith("FAILED")
    assert compared(lines, OVER)[0] == 0  # every pod still fits its node


# -- the three metrics and the stats they read -------------------------------


def test_the_three_metrics_read_the_stats_of_a_traced_rehearsal(
        capsys, tmp_path):
    from kubernetes_tpu.utils import metrics

    before = metrics.solves_by_resource_score.value(score="most")
    line, out, _ = run_cell(capsys, trace=1, keep_trace=str(tmp_path))
    assert line["correct"] is True
    assert set(THREE_IN_ORDER) <= set(line["metrics"])
    shape = load(f"configs/{CONFIG}.json")["kernel_shape"]
    assert line["metrics"]["solve_resource_dims"] == {
        "value": float(shape["r"]), "unit": "columns"}
    assert line["metrics"]["solve_most_allocated_share"]["value"] == 1.0
    # four sizes of request row a wave; a batch holds some of them
    assert 1.0 < line["metrics"]["pack_templates_per_batch"]["value"] <= 4.0
    # the operator's counter, by the profile's rule
    assert metrics.solves_by_resource_score.value(score="most") > before
    assert ('scheduler_solves_by_resource_score_total{score="most"}'
            in metrics.registry.expose())
    (path,) = list(tmp_path.glob("*.xplane.pb"))
    trace = program_spans.read_trace(str(path))
    solves = [sp for sp in trace["spans"]
              if sp["name"] == "sched/solve_dispatch"]
    packs = [sp for sp in trace["spans"] if sp["name"] == "sched/pack.pods"]
    masks = [sp for sp in trace["spans"] if sp["name"] == "sched/pack.masks"]
    assert solves and packs and masks
    for sp in solves:
        assert int(sp["stats"]["r_dims"]) == 5
        assert int(sp["stats"]["score_most"]) == 1
    assert all(1 <= int(sp["stats"]["templates"]) <= 4 for sp in packs)
    # the ten (class, zone) selectors are mask rows
    assert max(int(sp["stats"]["rows"]) for sp in masks) == 10
    # the cells that joined no new list still read their old metrics
    assert "pack_mask_rows_reused_share" in line["metrics"]
    assert "burst_pod_to_bind_p99_ms" in line["metrics"]


# -- the controls, at the timed size, with the reference alone ---------------


def timed_cluster(seed: int):
    """The cell's own cluster before a wave: 20,000 residents packed
    eight a node onto nodes 0-2,499, a seeded 5,000 of them deleted."""
    config = load(f"configs/{CONFIG}.json")
    mix = load("traffic/binpack-burst-6k.json")
    n = config["cluster"]["nodes"]
    held = np.zeros(n, dtype=np.int64)
    held[:config["cluster"]["init_pods"]["count"] // 8] = 8
    rng = np.random.default_rng(seed)
    residents = np.repeat(np.arange(n), held)
    gone = rng.choice(residents.size, size=residents.size // 4, replace=False)
    held -= np.bincount(residents[gone], minlength=n)
    unit = np.array([3500, 7000 * MIB, 0, 1], dtype=np.int64)
    used = held[:, None] * unit[None, :]
    used[:, ref.PODS] = held
    cap = np.tile(np.array([32000, 64 << 30, 110, 8], dtype=np.int64), (n, 1))
    zone = np.arange(n) % config["cluster"]["zones"]
    pools = []
    for part in mix["params"]["wave"]:
        pod = unit * int(part["class"][3:])
        pod[ref.PODS] = 1
        pools += [(pod, part["pods_per_app"], zone == z)
                  for z in part["zones"]]
    return ref.Nodes(cap, used), pools


def wave_reading(nodes, pools, rule, precision) -> int:
    return sum(
        ref.unexplained(nodes, pod, count, ref.schedule(
            nodes, pod, count, eligible, rule, precision)[0], eligible)
        for pod, count, eligible in pools
    )


def test_control_a_the_default_rule_leaves_thousands_outside():
    """A scheduler deaf to the profile's score plugins, which is what
    the parent is: it spreads a wave over the emptiest nodes."""
    nodes, pools = timed_cluster(43)
    assert all(ref.exact_for(nodes, pod, el) for pod, _, el in pools)
    assert wave_reading(nodes, pools, "most", "exact") == 0
    reading = wave_reading(nodes, pools, "default", "exact")
    assert reading >= 2000, reading


def test_control_b_bfloat16_has_no_power_here():
    """3500m and 7000Mi are not bfloat16 numbers, but the eight levels a
    node can hold score 10 or 11 apart, and bfloat16 moves a score by
    less than 1: the order of the levels is the exact one, and so is
    every placement. Control (a) is the one with power."""
    nodes, pools = timed_cluster(43)
    assert wave_reading(nodes, pools, "most", "bfloat16") == 0
    assert wave_reading(nodes, pools, "most", "float32") == 0
    levels = np.arange(1, 9, dtype=np.int64)
    low = ref.most_allocated(32000, 64 << 30, levels * 3500,
                             levels * 7000 * MIB, "bfloat16")
    assert (np.diff(low) >= 9).all()
    assert ref.reference.round_bfloat16(np.float32(3500)) != 3500


def test_control_c_float32_equals_the_exact_integers_over_the_cells_range():
    """A node's use is k x gpu1, k <= 8, plus the incoming pod: every
    total the rule is asked to score, fitting or not."""
    for k in range(9):
        for size in (1, 2, 4, 8):
            cpu, mem = (k + size) * 3500, (k + size) * 7000 * MIB
            exact = ref.most_allocated(32000, 64 << 30, cpu, mem)
            assert ref.most_allocated(
                32000, 64 << 30, cpu, mem, "float32") == exact, (k, size)
    scores = [int(ref.most_allocated(32000, 64 << 30, k * 3500,
                                     k * 7000 * MIB)) for k in range(1, 9)]
    assert scores == [10, 21, 32, 42, 53, 64, 75, 86]
