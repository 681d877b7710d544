"""The deployment ``image-locality-5000`` and its cell
``image-locality-5000.arrivals-apps-48`` (PR 48): the files as ISSUE 48's
tables give them, the cell's entries, the cell at rehearsal size through
the real harness on three seeds, the parent's answer by the
precondition, a broken twin (the scheduler deaf to ``ImageLocality``)
that fails by the comparison's line and no other, the controls at the
timed size with the reference alone, and the seven per-layer metrics
with the spans and stats they read."""

import json
import time
from pathlib import Path

import benchmark_rules as rules
import numpy as np
import pytest

from chipbench import harness, image_reference, kernel_bytes, program_spans
from chipbench import reference
from chipbench.generators import arrivals_apps

ROOT = Path(__file__).resolve().parents[2]
CELL = "image-locality-5000.arrivals-apps-48"
CONFIG = "image-locality-5000"
MIX = "arrivals-apps-48"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIB = 1 << 20
#: the cell's own seven, in the order ``per_layer`` holds them
SEVEN_IN_ORDER = [
    "score_live_share", "score_sigs_per_batch", "score_sig_rows_per_batch",
    "score_image_sigs_live_share", "pack_score_ms_per_batch",
    "pack_score_images_ms_per_batch", "pack_score_zones_ms_per_batch",
]
WINDOW = "window against the reference that knows the nodes' images"
STEADY = "basic-5000.arrivals-steady"
ROLL = "rolling-upgrade-5000.arrivals-roll-4"
#: the static rows of a live score family (ISSUE 48's 64; the program's
#: ops/scoring.MAX_SCORE_SIGS, which nothing under ``paths`` may import)
MAX_SCORE_SIGS = 64


def load(name):
    return json.loads((ROOT / "chipbench" / name).read_text())


# -- the files ---------------------------------------------------------------


def test_the_deployment_holds_the_issues_table_value_for_value():
    config = load(f"configs/{CONFIG}.json")
    basic = load("configs/basic-5000.json")
    assert config["reduced"] == [] and config["layout"]["chips"] == 1
    assert len(config["source"]) <= 200
    for words in ("image_locality.go", "nodeStatusMaxImages",
                  "SchedulingBasic", "5000 nodes"):
        assert words in config["source"], words
    # basic-5000's cluster, pod classes, wire and the rest, value for value
    for key in ("cluster", "pod_classes", "wire", "expect_tier",
                "score_precision", "setup_timeout_s"):
        assert config[key] == basic[key], key
    assert config["guarantees"][:4] == basic["guarantees"]
    said = " ".join(config["guarantees"][4:])
    for words in ("ImageLocality included", "expect_tier",
                  "host path answers counts as failed"):
        assert words in said, words
    images = config["images"]
    assert (images["apps"], images["size_mib"], images["holder_share"],
            images["max_per_node"], images["registry"]) == (
        48, [40, 2000], [0.05, 0.95], 50, "registry.example")
    assert len(images["infra"]) == 6
    assert all(1 <= mib <= 400 for _, mib in images["infra"])
    assert not any(name == "pause" for name, _ in images["infra"])
    assert config["checks"] == [
        "replay", "window_image_reference", "check_wave"]
    assert config["window_image_reference"]["limit_pods"] == 0
    assert "expect_tiers" not in config
    # every value that is not the source's is assumed, with its reason
    for key in ("48 application images", "size_mib", "holder_share",
                "6 infrastructure images", "max_per_node",
                "no image arrives as pods bind", "no owner references",
                "seeds", "kernel_shape"):
        assert any(key in k for k in config["assumed"]), key
    # the constrained call at the live family's one shape: not
    # rolling-upgrade-5000's 157 / 50, which counted 4 static rows
    shape = config["kernel_shape"]
    assert shape == {"n_cap": 5632, "r": 4, "u": 8, "b": 4096,
                     "family_rows": 337, "families": 50}
    roll = load("configs/rolling-upgrade-5000.json")["kernel_shape"]
    assert shape["family_rows"] - roll["family_rows"] == 3 * (64 - 4)
    assert kernel_bytes.solve_call_bytes(**shape) > (
        kernel_bytes.solve_call_bytes(**roll))
    rehearsal = config["rehearsal"]
    assert {k: rehearsal[k] for k in basic["rehearsal"]} == basic["rehearsal"]
    assert rehearsal["images"] == {"apps": 20}  # past 16 signatures too


def test_the_catalogue_is_the_one_the_file_says():
    config = load(f"configs/{CONFIG}.json")
    cat = image_reference.catalogue(config["images"], 5000)
    scores = image_reference.image_scores(cat)
    said = config["assumed"]["seeds"]
    assert f"{cat.pairs():,} (node, image) pairs" in said
    assert f"{int((scores.max(axis=1) > 0).sum())} have a row above 0" in said
    per_node = cat.holds.sum(axis=0) + len(cat.infra)
    assert per_node.max() <= 50
    assert f"{per_node.mean():.1f} a node" in said


def test_the_mix_holds_the_issues_table_value_for_value():
    mix = load(f"traffic/{MIX}.json")
    steady = load("traffic/arrivals-steady.json")
    assert mix["generator"] == "arrivals_apps"
    assert "window_check" not in mix  # the comparison is the cell's own
    assert mix["trace_seconds"] == steady["trace_seconds"] == 4
    assert mix["rehearsal"] == steady["rehearsal"]
    params = dict(mix["params"])
    assert (params.pop("app_seed"), params.pop("zipf_exponent")) == (
        20261048, 1.0)
    theirs = dict(steady["params"])
    assert params.pop("gap_seed") != theirs.pop("gap_seed")
    assert params == theirs  # rate, tick, creators, deadline, warm-up
    assert (params["rate"], params["tick_ms"], params["creators"],
            params["deadline_s"], params["warmup_seconds"],
            params["warmup_rounds"], params["class"]) == (
        2600, 5, 1, 10, 1, 2, "plain")
    # 51 s of it: about 132,600 pods, the busiest app about 29,700
    apps = image_reference.zipf_apps(
        round(2600 * 51), 48, 1.0, mix["params"]["app_seed"])
    counts = np.bincount(apps, minlength=48)
    assert len(apps) == 132600 and 28000 <= counts[0] <= 31000
    assert counts.min() > 0


def test_the_cell_and_its_metrics_as_declared():
    """Held as the benchmark's own rule has it (``chipbench/README.md``,
    "Adding things"): the cell and its configuration by name, its seven
    as one contiguous run of ``per_layer`` in their order, and of a
    metric's list only that the cell is in it."""
    cell = rules.cell_named(BENCH, CELL)
    assert cell == dict(cell, config=CONFIG, traffic=MIX, chips=1)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == load(f"configs/{CONFIG}.json")["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"pod_to_bind_p50_ms", "setup_s"}  # not the p99's list
    rules.contiguous_run(BENCH, SEVEN_IN_ORDER)
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SEVEN_IN_ORDER:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "pod_to_bind_p50_ms"
        # the solver call's shape is read where its other shapes are
        assert per_layer[name]["layer"] == (
            "device solve, host side" if name == "score_sig_rows_per_batch"
            else "pack")
        # data files for readers the benchmark has
        assert rules.spec_of(ROOT, name)["reader"] in (
            "span_stat_mean", "span_stat_ratio", "span_ms_per_span")
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in rules.cells_of(BENCH, m)}
    # every list that arrivals-steady and arrivals-roll-4 are both in
    both = {m["name"] for m in BENCH["per_layer"] if "workloads" in m
            and {STEADY, ROLL} <= set(m["workloads"])}
    assert both - {n for n in both if "listed_from" in rules.spec_of(ROOT, n)
                   } <= declared
    assert {"solve_kernel_roofline", "solve_kernel_ms_per_batch",
            "burst_pod_to_bind_p99_ms", "pack_families_ms_per_batch",
            "compiles_in_window"} <= declared
    # what other cells' traffic alone can report stays theirs
    assert not any(n.startswith(("preempt_", "gang_", "shard_", "mesh_",
                                 "node_", "carry_", "wave_"))
                   for n in declared)


# -- the run -----------------------------------------------------------------


def run_cell(capsys, trace=0, seed=2**31 + 4800, keep_trace="", rc_want=0):
    args = harness.public_arguments("test").parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "2",
        "--trace", str(trace), "--rehearsal",
    ])
    with rules.one_traced_run_at_a_time(ROOT):
        rc = harness.run_one(args, time.perf_counter(), keep_trace=keep_trace)
    captured = capsys.readouterr()
    out = captured.out.strip().splitlines()
    assert rc == rc_want, (out[-20:], captured.err[-2000:])
    if rc:
        return None, out, captured.err
    return json.loads(out[-1]), out, [l for l in out
                                      if l.startswith("compare ")]


def compared(lines, start):
    (line,) = [l for l in lines if l.startswith("compare " + start)]
    return int(line.split(": ")[-1].split(" ")[0]), line


def after_warmup(monkeypatch, break_it):
    """``break_it(run)`` once warm-up is over, as the window is built."""
    real = arrivals_apps.prepare

    def prepare(run, params, seconds):
        break_it(run)
        return real(run, params, seconds)

    monkeypatch.setattr(arrivals_apps, "prepare", prepare)


@pytest.mark.parametrize("seed", [2**31 + 4800, 48, 998244353])
def test_the_cell_is_correct_and_every_line_reads_0(capsys, seed):
    line, out, lines = run_cell(capsys, seed=seed)
    assert line["correct"] is True and line["failed"] == 0, out[-30:]
    assert line["attempted"] == 120
    assert set(line["metrics"]) == {"pod_to_bind_p50_ms", "setup_s"}
    assert all(l.endswith("-> ok") for l in lines), lines
    value, text = compared(lines, WINDOW)
    assert value == 0 and "120 pods of 20 apps" in text
    (note,) = [l for l in out if l.startswith("arrivals apps: ")]
    assert "48 nodes reported" in note and "20 app and 6 infra" in note
    (window,) = [l for l in out if l.startswith("window: ")]
    assert "pods_fallback 0" in window
    (tier,) = [l for l in lines if l.startswith("compare tier: ")]
    assert "'sequential': 0" in tier and "'host_greedy': 0" in tier


def test_the_parent_answers_by_the_precondition(capsys, monkeypatch):
    """What the parent is: 16 score signatures, the seventeenth sends
    the batch whole to the host path. Warm-up's first create holds a pod
    of every app; the run ends there, exit code 2, with no result line."""
    from kubernetes_tpu.scheduler import batch

    real = batch.pack_score_batch

    def sixteen(pods, *args, **kw):
        if len({tuple(c.image for c in p.spec.containers)
                for p in pods}) > 16:
            raise batch.ScoreEnvelopeExceeded("too many score signatures")
        return real(pods, *args, **kw)

    monkeypatch.setattr(batch, "pack_score_batch", sixteen)
    _, out, err = run_cell(capsys, rc_want=2)
    assert "a batch of 20 pods that name 20 images" in err
    assert "pods_fallback moved by 20" in err
    assert "cannot solve a batch of that many images on the device" in err
    assert not (out and out[-1].startswith("{"))


def test_a_scheduler_deaf_to_image_locality_fails_the_comparisons_line_alone(
        capsys, monkeypatch):
    from kubernetes_tpu.scheduler import batch

    real = batch.pack_score_batch

    def deaf(pods, snapshot, nt, informers, weights, **kw):
        return real(pods, snapshot, nt, informers,
                    dict(weights, ImageLocality=0), **kw)

    after_warmup(
        monkeypatch,
        lambda run: monkeypatch.setattr(batch, "pack_score_batch", deaf),
    )
    line, out, lines = run_cell(capsys, seed=2**31 + 4801)
    assert line["correct"] is False and line["failed"] == 0, out[-30:]
    value, text = compared(lines, WINDOW)
    assert value >= 12 and text.endswith("FAILED")  # a tenth and more
    # every pod still binds and fits, and the check wave's pods name no
    # image a node holds: that line alone
    failed = [l for l in lines if not l.endswith("-> ok")]
    assert failed == [text], failed


# -- the seven metrics, the spans and the stats they read --------------------


def test_the_metrics_read_the_spans_and_stats_of_a_traced_rehearsal(
        capsys, tmp_path):
    from kubernetes_tpu.utils import metrics

    live = metrics.score_family_batches.value(live="true")
    line, out, _ = run_cell(capsys, trace=1, keep_trace=str(tmp_path))
    assert line["correct"] is True
    got = line["metrics"]
    assert set(SEVEN_IN_ORDER) <= set(got)
    assert got["compiles_in_window"]["value"] == 0.0
    assert 0.5 < got["score_live_share"]["value"] <= 1.0
    assert 0.5 < got["score_image_sigs_live_share"]["value"] <= 1.0
    # a rehearsal's batches hold a pod or two; every live one uploads 64
    assert 0 < got["score_sigs_per_batch"]["value"] <= 20
    assert 0 < got["score_sig_rows_per_batch"]["value"] <= MAX_SCORE_SIGS
    assert (got["pack_score_ms_per_batch"]["value"]
            >= got["pack_score_images_ms_per_batch"]["value"]
            + got["pack_score_zones_ms_per_batch"]["value"] > 0)
    assert (got["pack_families_ms_per_batch"]["value"]
            >= got["pack_score_ms_per_batch"]["value"])
    assert metrics.score_family_batches.value(live="true") > live
    (path,) = list(tmp_path.glob("*.xplane.pb"))
    trace = program_spans.read_trace(str(path))
    by_name = {}
    for sp in trace["spans"]:
        by_name.setdefault(sp["name"], []).append(sp)
    families = by_name["sched/pack.families"]
    for sp in families:
        stats = sp["stats"]
        assert "score_sig_rows" not in stats  # the solver call's, below
        assert int(stats["score_sigs"]) <= int(stats["score_image_sigs"]) + 1
    rows = [int(sp["stats"]["score_sig_rows"]) for sp in
            program_spans.spans_in_slice(trace, "sched/solve_dispatch")]
    assert set(rows) <= {0, MAX_SCORE_SIGS}
    assert sum(rows) / len(rows) == pytest.approx(
        got["score_sig_rows_per_batch"]["value"])
    assert len(by_name["sched/pack.score"]) == len(families)
    assert by_name["sched/pack.score.images"]
    assert by_name["sched/pack.score.zones"]
    # the cells that joined no new list still read their old metrics
    assert "pack_mask_rows_reused_share" in got
    assert "burst_pod_to_bind_p99_ms" in got


def test_the_image_index_is_a_span_with_what_it_indexed(tmp_path):
    import jax

    from kubernetes_tpu.cache.cache import SchedulerCache
    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.testing import make_node

    config = load(f"configs/{CONFIG}.json")
    cat = image_reference.catalogue(dict(config["images"], apps=20), 48)
    cache = SchedulerCache()
    for j in range(48):
        w = make_node(f"node-{j}")
        for image, size in cat.node_images(j):
            w.image(image, size)
        cache.add_node(w.obj())
    snap = cache.update_snapshot(Snapshot())
    with rules.one_traced_run_at_a_time(ROOT):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(program_spans.SLICE):
                index = snap.image_holders()
                assert snap.image_holders() is index  # one walk an epoch
        finally:
            jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    spans = [sp for sp in program_spans.read_trace(str(path))["spans"]
             if sp["name"] == "sched/pack.image_index"]
    (span,) = spans
    assert int(span["stats"]["images"]) == 26 == len(index)
    assert int(span["stats"]["pairs"]) == cat.pairs()


# -- the controls, at the timed size, with the reference alone ---------------


def timed_window(seed: int):
    """The cell's own cluster before its window (the ballast grid, and
    the 5,000 init pods where the rule puts them from there), its
    catalogue, and 51 s of arrivals in the order ``seed`` gives."""
    config = load(f"configs/{CONFIG}.json")
    mix = load(f"traffic/{MIX}.json")["params"]
    cluster = config["cluster"]
    n, zones = cluster["nodes"], cluster["zones"]
    spec = cluster["ballast"]
    classes = config["pod_classes"]
    first, second = (classes[c] for c in spec["classes"])
    used = np.zeros((3, n), dtype=np.int64)
    for i in range(zones * spec["per_zone"]):
        j = i // zones
        for cls, count in ((first, j % spec["grid"]),
                           (second, (j // spec["grid"]) % spec["grid"])):
            used[:, i] += count * np.array(
                [cls["cpu_milli"], cls["memory_mib"] * MIB, 1])
    nodes = reference.Nodes(
        cap_cpu=np.full(n, 32000, dtype=np.int64),
        cap_mem=np.full(n, 64 << 30, dtype=np.int64),
        cap_pods=np.full(n, 110, dtype=np.int64),
        used_cpu=used[0], used_mem=used[1], used_pods=used[2],
        zone=np.arange(n, dtype=np.int64) % zones,
    )
    plain = classes["plain"]
    pod = reference.PodClass(cpu=plain["cpu_milli"],
                             mem=plain["memory_mib"] * MIB)
    init, _ = reference.schedule(nodes, pod, cluster["init_pods"]["count"])
    nodes.used_cpu += init * pod.cpu
    nodes.used_mem += init * pod.mem
    nodes.used_pods += init
    cat = image_reference.catalogue(config["images"], n)
    apps = image_reference.zipf_apps(
        round(mix["rate"] * 51), len(cat.apps), mix["zipf_exponent"],
        mix["app_seed"])
    return nodes, pod, cat, apps[np.random.default_rng(seed).permutation(
        len(apps))]


def test_the_controls_at_the_timed_size():
    """The rule reads 0; (a) the reference deaf to images and (b) the
    reference that scores each app by the next app's row leave a tenth
    of the window outside; (c) float32 is the exact integers. bfloat16
    is read as outside here too (the rows break the symmetry that hides
    it in ``arrivals-steady``), and ``check_wave`` tells it apart as in
    every cell."""
    nodes, pod, cat, arrivals = timed_window(48)
    scores = image_reference.image_scores(cat)

    def reading(rows, precision="exact"):
        got, left = image_reference.schedule(
            nodes, pod, rows, arrivals, precision)
        return image_reference.unexplained(nodes, pod, scores, got) + left

    assert reading(scores) == 0
    assert reading(np.zeros_like(scores)) >= 10000
    mixed = image_reference.image_scores(
        cat, rows=(np.arange(len(cat.apps)) + 1) % len(cat.apps))
    assert reading(mixed) >= 10000
    assert reading(scores, "float32") == 0
    assert reading(scores, "bfloat16") >= 1000
