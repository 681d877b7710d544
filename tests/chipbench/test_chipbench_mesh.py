"""The deployment ``basic-50000`` and its cell on the four-chip mesh: the
configuration and the mix letter for letter, what the lower-precision
control can and cannot tell at this cluster, the shard kernel's bytes
against a hand count, the device-event patterns against names as the
chip's trace printed them, and the stats the program writes on
``sched/solve_dispatch`` read from a rehearsal's own trace."""

import importlib
import json
import re
import time
from pathlib import Path

import benchmark_rules as rules
import numpy as np
import pytest

from chipbench import harness, program_spans, reference as ref, tracing
from chipbench.readers import kernel_time_per_step, span_stat_mean
from chipbench.shard_kernel_bytes import shard_call_bytes
from test_chipbench_control import unexplained
from test_chipbench_reference import MIB, ballast_pool

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "testdata"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "basic-50000.mesh-burst-20k"
NEW_METRICS = {
    "shard_kernel_ms_per_step", "shard_kernel_roofline",
    "mesh_collective_ms_per_step", "carry_full_uploads",
    "carry_rows_per_batch",
}
ON_CHIP = {"shard_kernel_ms_per_step", "shard_kernel_roofline",
           "mesh_collective_ms_per_step"}


def load(path):
    return json.loads((ROOT / path).read_text())


CONFIG = load("chipbench/configs/basic-50000.json")
MIX = load("chipbench/traffic/mesh-burst-20k.json")


def test_the_deployment_and_the_mix_letter_for_letter():
    base = load("chipbench/configs/basic-5000.json")
    cluster = CONFIG["cluster"]
    assert cluster["nodes"] == 50000 and cluster["zones"] == 10
    assert cluster["node"] == {"cpu": "32", "memory": "64Gi", "pods": 110}
    assert cluster["init_pods"] == {"count": 25000, "class": "plain"}
    assert cluster["ballast"] == base["cluster"]["ballast"]
    assert CONFIG["pod_classes"] == base["pod_classes"]
    assert CONFIG["wire"] == {"tpuSolver": {"maxBatch": 4096, "meshDevices": 4}}
    assert CONFIG["expect_tier"] == "pallas"
    assert CONFIG["score_precision"] == "float32"
    assert CONFIG["guarantees"] == base["guarantees"]
    assert CONFIG["layout"]["chips"] == 4
    assert CONFIG["reduced"] == ["mesh_chips"]
    assert CONFIG["mesh_chips"]["here"] == 4 and CONFIG["mesh_chips"]["source"] == 8
    # the rehearsal keeps the mesh, and names the tier a CPU mesh reports
    rehearsal = CONFIG["rehearsal"]
    assert rehearsal["wire"]["tpuSolver"]["meshDevices"] == 4
    assert rehearsal["expect_tier"] == "xla"
    params = MIX["params"]
    assert MIX["generator"] == "waves"
    assert params["wave"] == [
        {"class": "plain", "apps": 1, "pods_per_app": 20000}
    ]
    assert (params["shuffle"], params["creators"], params["chunk"]) == (False, 4, 256)
    assert (params["warmup_waves"], params["deadline_s"],
            params["delete_timeout_s"]) == (2, 60, 60)
    assert params["check_classes"] == ["plain"]
    assert MIX["window_check"]["limit_pods"] == 0
    assert MIX["trace_seconds"] in (1, 2)
    # a rehearsal's wave changes fewer rows than a row scatter takes
    assert MIX["rehearsal"]["params"]["wave"][0]["pods_per_app"] < 64


def test_the_cell_and_its_metrics_as_declared():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="basic-50000", traffic="mesh-burst-20k",
                        chips=4)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "basic-50000"]
    assert entry["reduced"] == ["mesh_chips"]
    assert entry["source"] == CONFIG["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "pod_to_bind_p50_ms"
    assert rules.on_chip_only(ROOT, NEW_METRICS) == ON_CHIP
    # the one-chip kernels' metrics name other kernels
    for name in ("solve_kernel_ms_per_batch", "solve_kernel_roofline"):
        assert CELL not in per_layer[name]["workloads"]


def cluster_before_a_wave():
    """The cluster as a window's wave finds it: the ballast, then the
    25,000 init pods on the 25,000 lowest empty nodes (the ballast leaves
    10 nodes of the pool empty), which ``reference.bands`` confirms is
    where the rule puts them."""
    cluster = CONFIG["cluster"]
    n, zones = cluster["nodes"], cluster["zones"]
    pool = ballast_pool(
        zones * cluster["ballast"]["per_zone"], zones, cluster["ballast"]["grid"]
    )
    nodes = ref.Nodes(
        cap_cpu=np.full(n, 32000), cap_mem=np.full(n, 64 << 30),
        cap_pods=np.full(n, 110), used_cpu=np.zeros(n, np.int64),
        used_mem=np.zeros(n, np.int64), used_pods=np.zeros(n, np.int64),
        zone=np.arange(n) % zones,
    )
    for field in ("used_cpu", "used_mem", "used_pods"):
        getattr(nodes, field)[:pool.zone.shape[0]] = getattr(pool, field)
    cls = CONFIG["pod_classes"][cluster["init_pods"]["class"]]
    pod = ref.PodClass(cls["cpu_milli"], cls["memory_mib"] * MIB)
    count = cluster["init_pods"]["count"]
    init = np.zeros(n, dtype=np.int64)
    init[np.flatnonzero(nodes.used_pods == 0)[:count]] = 1
    lo, hi = ref.bands(nodes, pod, count)
    assert init.sum() == count and ref.outside(init, lo, hi) == 0
    nodes.used_cpu += init * pod.cpu
    nodes.used_mem += init * pod.mem
    nodes.used_pods += init
    return nodes, pod


def test_the_lower_precision_control_at_this_cluster():
    """float32 leaves no pod of a 20,000-pod wave outside the bands, and
    neither does bfloat16: 24,370 nodes are empty before a wave, an
    empty node outscores every other in all three precisions, and a
    node's score is its own load's alone, so the wave takes the 20,000
    lowest empty nodes whatever the score's last bits (as in the
    open-loop window of ``basic-5000.arrivals-steady``). What the
    window's comparison tells at this cluster is a carry that has missed
    a wave's deletes (``tests/test_mesh_reference.py`` shows it at a
    small size); what tells the precision is the check wave, below."""
    count = MIX["params"]["wave"][0]["pods_per_app"]
    before, pod = cluster_before_a_wave()
    empty = before.used_pods == 0
    assert int(empty.sum()) == 24370 and count <= 24370
    for precision in ("exact", "float32", "bfloat16"):
        score = ref.scores(
            before.cap_cpu, before.cap_mem, before.used_cpu + pod.cpu,
            before.used_mem + pod.mem, precision,
        )
        assert score[empty].min() > score[~empty].max(), precision
    got = np.zeros(empty.shape[0], dtype=np.int64)
    got[np.flatnonzero(empty)[:count]] = 1
    lo, hi = ref.bands(before, pod, count)
    assert ref.outside(got, lo, hi) <= MIX["window_check"]["limit_pods"]
    # rows follow creation order but for a handful, 14,080 a chip: the
    # wave crosses the shard boundaries at 28,160 and 42,240
    placed = np.flatnonzero(got)
    assert (placed.min(), placed.max()) == (25630, 45629)


def test_bfloat16_scoring_fails_the_plain_check_of_this_deployment():
    cluster = CONFIG["cluster"]
    pool = ballast_pool(
        cluster["zones"] * cluster["ballast"]["per_zone"], cluster["zones"],
        cluster["ballast"]["grid"],
    )
    cls = CONFIG["pod_classes"]["plain"]
    pod = ref.PodClass(cls["cpu_milli"], cls["memory_mib"] * MIB)
    count, limit = cls["check"]["count"], cls["check"]["limit_pods"]
    sound, _ = ref.schedule(pool, pod, count, "float32")
    assert unexplained(pool, pod, count, sound) <= limit
    control, _ = ref.schedule(pool, pod, count, "bfloat16")
    assert unexplained(pool, pod, count, control) > 3 * max(limit, 1)


def test_the_shard_kernels_bytes_against_a_hand_count():
    """One call on one chip's 14,080 rows: alloc 4 + req 4 + nzr 2 +
    valid 1 + 8 mask rows = 19 node-length int32 rows, and the pod's
    4 + 2 + 1 words in and 2 words out."""
    shape = CONFIG["kernel_shape"]
    assert shape == {"rows_per_chip": 14080, "r": 4, "u": 8, "pods_per_call": 1}
    assert shape["rows_per_chip"] * CONFIG["layout"]["chips"] == 56320
    assert shard_call_bytes(**shape) == 4 * (14080 * 19 + 9) == 1070116
    assert shard_call_bytes(128, 4, 8) == 4 * (128 * 19 + 9)


def test_the_patterns_against_names_as_the_chips_trace_printed_them():
    kernel = rules.spec_of(ROOT, "shard_kernel_ms_per_step")["args"]["pattern"]
    assert rules.spec_of(ROOT, "shard_kernel_roofline")["args"]["pattern"] == kernel
    collective = rules.spec_of(ROOT, "mesh_collective_ms_per_step")["args"]
    assert collective["step_pattern"] == kernel
    one_chip = rules.spec_of(ROOT, "solve_kernel_ms_per_batch")["args"]["pattern"]
    names = {
        "pallas_shard_candidate.1": kernel, "pallas_shard_candidate": kernel,
        "pmax.14": collective["pattern"], "pmin.14": collective["pattern"],
        "pmax": collective["pattern"], "pmin.3.clone": collective["pattern"],
        "pallas_greedy_solve.1": one_chip,
        "pallas_constrained_solve.2": one_chip,
    }
    for name, mine in names.items():
        for pattern in (kernel, collective["pattern"], one_chip):
            assert bool(re.search(pattern, name)) == (pattern == mine), (
                name, pattern)
    for other in ("while.18", "copy.3", "fusion.7", "all-reduce.1",
                  "pmaximum.2", "xpmax.1"):
        assert not re.search(kernel, other)
        assert not re.search(collective["pattern"], other)


def test_the_collectives_time_is_a_steps():
    """The two all-reduces over the steps, which the shard kernel's calls
    count: 8 calls over 4 chips are 2 steps a chip."""
    args = rules.spec_of(ROOT, "mesh_collective_ms_per_step")["args"]
    ops = {"pallas_shard_candidate.1": [8, 24e-6], "pmax.14": [8, 30e-6],
           "pmin.14": [8, 26e-6], "while.18": [4, 1e-3]}
    assert kernel_time_per_step.read({"trace": {"ops": ops}}, args) == (
        pytest.approx((30e-6 + 26e-6) * 1e3 / 8)
    )
    del ops["pmax.14"], ops["pmin.14"]
    assert kernel_time_per_step.read({"trace": {"ops": ops}}, args) is None
    assert kernel_time_per_step.read({"trace": None}, args) is None


def test_a_program_without_the_new_stats_leaves_the_metrics_out():
    """The trace PR 24 recorded on the chip: the one-chip kernel, and
    ``sched/solve_dispatch`` spans that say ``tier`` and nothing of the
    carry, as the parent's do. Nothing to read is None, not an error."""
    spans = program_spans.read_trace(str(DATA / "burst-10k-8s-spans.xplane.pb"))
    assert program_spans.spans_in_slice(spans, "sched/solve_dispatch")
    args = rules.spec_of(ROOT, "carry_rows_per_batch")["args"]
    assert span_stat_mean.mean(spans, args) is None
    reduced = tracing.reduce(str(DATA / "burst-10k-8s.xplane.pb"))
    sample = {"trace": reduced, "root": ROOT, "device": {"kind": "TPU v5 lite"},
              "cell": {"config": CONFIG}}
    for name in ON_CHIP:
        spec = rules.spec_of(ROOT, name)
        reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        assert reader.read(sample, spec["args"]) is None


def test_solve_dispatch_says_how_the_carry_was_brought_up_to_date(
        tmp_path, capsys):
    """A traced rehearsal of the cell: every ``sched/solve_dispatch``
    span carries ``devices``, ``carry`` and ``carry_rows``, the counted
    metrics read them, and the device-event metrics stay out of a CPU's
    line."""
    args = harness.public_arguments("test").parse_args([
        "--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "1",
        "--trace", "1", "--rehearsal",
    ])
    with rules.one_traced_run_at_a_time(ROOT):
        rc = harness.run_one(
            args, time.perf_counter(), keep_trace=str(tmp_path)
        )
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert any(l.startswith("compare tier: batches by tier {'pallas': 0, 'xla': ")
               and l.endswith("-> ok") for l in out)
    (kept,) = tmp_path.glob("*.xplane.pb")
    trace = program_spans.read_trace(str(kept))
    spans = program_spans.spans_in_slice(trace, "sched/solve_dispatch")
    assert spans
    rows = []
    for sp in spans:
        stats = sp["stats"]
        assert stats["devices"] == 4 and stats["tier"] == "xla"
        assert stats["carry"] in ("reuse", "scatter", "upload")
        if stats["carry"] == "reuse":
            assert stats["carry_rows"] == 0
        elif stats["carry"] == "scatter":
            assert 0 < stats["carry_rows"] <= 2 * 64
        else:  # the padded row count
            assert stats["carry_rows"] == 256
        rows.append(stats["carry_rows"])
    # a wave's deletes reach the carry as a row scatter at this size
    assert "scatter" in {sp["stats"]["carry"] for sp in spans}
    metrics = line["metrics"]
    assert metrics["carry_rows_per_batch"]["value"] == pytest.approx(
        sum(rows) / len(rows)
    )
    assert metrics["carry_full_uploads"] == {"value": 0.0, "unit": "count"}
    assert ON_CHIP.isdisjoint(metrics)
