"""The deployment ``services-5000`` and its cell
``services-5000.rollout-5k`` (PR 51): the files as ISSUE 51's tables give
them, the cell's entries, the cell at rehearsal size through the real
harness on two seeds, the parent's answer by the precondition, two
broken twins (the scheduler deaf to both counting scorers; the scheduler
that forgets the residents' half of the symmetric terms) that each fail
by the comparisons' lines and no other, the controls at the timed size
with the reference alone, and the six per-layer metrics with the spans
and stats they read."""

import json
import time
from pathlib import Path

import benchmark_rules as rules
import numpy as np
import pytest

from chipbench import harness, kernel_bytes, program_spans, reference
from chipbench import services_reference as sr
from chipbench.checks import window_services_reference as window_check
from chipbench.generators import rollout_waves

ROOT = Path(__file__).resolve().parents[2]
CELL = "services-5000.rollout-5k"
CONFIG = "services-5000"
MIX = "rollout-5k"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIB = 1 << 20
#: the cell's own six, in the order ``per_layer`` holds them
SIX_IN_ORDER = [
    "pack_score_dynamic_ms_per_batch", "pack_score_selectors_ms_per_batch",
    "pack_score_ipa_ms_per_batch", "score_dynamic_rows_per_batch",
    "score_dynamic_cuts_per_batch", "score_census_recounted_share",
]
WINDOW = "window against the reference that knows the Services"
CHECK = "check wave plain"
BURST = "spread-anti-5000.burst-5k"
IMAGES = "image-locality-5000.arrivals-apps-48"


def load(name):
    return json.loads((ROOT / "chipbench" / name).read_text())


# -- the files ---------------------------------------------------------------


def test_the_deployment_holds_the_issues_table_value_for_value():
    config = load(f"configs/{CONFIG}.json")
    basic = load("configs/basic-5000.json")
    images = load("configs/image-locality-5000.json")
    assert config["reduced"] == [] and config["layout"]["chips"] == 1
    assert len(config["source"]) <= 200
    for words in ("registry.go:118-125", "defaultpodtopologyspread",
                  "interpodaffinity/scoring.go", "5000 nodes",
                  "SchedulingPreferredPodAntiAffinity", "ServiceSpread"):
        assert words in config["source"], words
    # basic-5000's cluster, wire and the rest, value for value: the batch
    # window is the binary's, as that file leaves it
    for key in ("cluster", "wire", "expect_tier", "score_precision"):
        assert config[key] == basic[key], key
    assert "batchWindow" not in config["wire"]["tpuSolver"]
    assert config["setup_timeout_s"] == images["setup_timeout_s"]
    classes = {k: dict(v) for k, v in config["pod_classes"].items()}
    theirs = {k: dict(v) for k, v in basic["pod_classes"].items()}
    for cls in (classes, theirs):
        cls["plain"]["check"] = dict(cls["plain"]["check"], why="")
    assert classes == theirs
    assert (classes["plain"]["cpu_milli"], classes["plain"]["memory_mib"]) == (
        250, 512)
    assert "images" not in config  # no node reports an image
    assert config["guarantees"][:4] == basic["guarantees"]
    assert config["guarantees"][5] == images["guarantees"][5]
    for words in ("DefaultPodTopologySpread", "preferred InterPodAffinity",
                  "every resident's", "top class",
                  # what the window cannot tell, and what can
                  "the order the apiserver created the pods in",
                  "The timed window proves selector spread alone",
                  "proved by the check wave alone"):
        assert words in config["guarantees"][4], words
    assert config["services"] == {
        "count": 48, "namespace": "default", "term_weight": 100,
        "topology_key": "kubernetes.io/hostname", "zipf_exponent": 1.0,
        "app_seed": 20261051, "residents": 20000, "resident_class": "plain",
    }
    assert config["checks"] == [
        "replay", "window_services_reference", "services_check_wave"]
    assert config["window_services_reference"]["limit_pods"] == 0
    assert config["pod_classes"]["plain"]["check"]["limit_pods"] == 0
    assert "expect_tiers" not in config
    # every value that is not the source's is assumed, with its reason
    for key in ("48 services", "Zipf shares", "20,000 residents",
                "weight 100", "hostname as the key", "no images",
                "waves repeated and deleted", "kernel_shape"):
        assert any(key in k for k in config["assumed"]), key
    assert "cancels" in config["assumed"]["weight 100"]
    said = config["assumed"]["Zipf shares, exponent 1.0"]
    shares = sr.zipf_shares(20000, 48, 1.0, 20261051)
    wave = sr.zipf_shares(5000, 48, 1.0, 20261051)
    for number in (shares.max(), shares.min(), wave.max(), wave.min()):
        assert f"{int(number):,}" in said, number
    # the constrained call at the wide shape: 64 + 64 dynamic rows beside
    # 4 static ones, not image-locality-5000's 8 + 8 beside 64
    shape = config["kernel_shape"]
    assert shape == {"n_cap": 5632, "r": 4, "u": 8, "b": 4096,
                     "family_rows": 549, "families": 274}
    assert shape["family_rows"] == (
        3 * 4 + 64 + 1 + 8 + 64 + 2 * (64 + 8 + 64 + 64))
    assert shape["families"] == 2 + (2 * 8 + 3 * 64 + 64)
    assert kernel_bytes.solve_call_bytes(**shape) > (
        kernel_bytes.solve_call_bytes(**images["kernel_shape"]))
    rehearsal = config["rehearsal"]
    assert {k: rehearsal[k] for k in basic["rehearsal"]} == basic["rehearsal"]
    # past the 8 groups of the small shape at rehearsal size too
    assert rehearsal["services"] == {"count": 12, "residents": 96}


def test_the_mix_holds_the_issues_table_value_for_value():
    mix = load(f"traffic/{MIX}.json")
    burst = load("traffic/burst-5k.json")
    assert mix["generator"] == "rollout_waves"
    assert "window_check" not in mix  # the comparison is the cell's own
    assert mix["trace_seconds"] == burst["trace_seconds"] == 8
    params = mix["params"]
    assert params == {
        "class": "plain", "pods": 5000, "chunk": 256, "creators": 4,
        "warmup_waves": 2, "deadline_s": 120, "delete_timeout_s": 60,
        "check_classes": ["plain"],
    }
    for key in ("chunk", "creators", "warmup_waves", "deadline_s",
                "delete_timeout_s"):
        assert params[key] == burst["params"][key], key
    assert sum(p["apps"] * p["pods_per_app"]
               for p in burst["params"]["wave"]) == params["pods"]
    assert mix["rehearsal"]["params"] == {"pods": 120, "warmup_waves": 1}


def test_the_cell_and_its_metrics_as_declared():
    """Held as the benchmark's own rule has it (``chipbench/README.md``,
    "Adding things"): the cell and its configuration by name, its six as
    one contiguous run of ``per_layer`` in their order, and of a
    metric's list only that the cell is in it."""
    cell = rules.cell_named(BENCH, CELL)
    assert cell == dict(cell, config=CONFIG, traffic=MIX, chips=1)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == load(f"configs/{CONFIG}.json")["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in rules.cells_of(BENCH, m)}
    assert e2e == {"bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    rules.contiguous_run(BENCH, SIX_IN_ORDER)
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SIX_IN_ORDER:
        assert CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "pod_to_bind_p50_ms"
        assert per_layer[name]["layer"] == "pack"
        spec = rules.spec_of(ROOT, name)
        assert spec["needs"] == "services"
        assert (ROOT / "chipbench" / "readers"
                / f"{spec['reader']}.py").is_file()
    declared = {m["name"] for m in BENCH["per_layer"]
                if CELL in rules.cells_of(BENCH, m)}
    # every list that burst-5k and the image cell are both in
    both = {m["name"] for m in BENCH["per_layer"] if "workloads" in m
            and {BURST, IMAGES} <= set(m["workloads"])}
    assert both - {n for n in both if "listed_from" in rules.spec_of(ROOT, n)
                   } <= declared
    assert {"solve_kernel_roofline", "solve_kernel_ms_per_batch",
            "burst_pod_to_bind_p99_ms", "wave_drain_pods_per_s",
            "pack_families_ms_per_batch", "pack_drain_ms_per_batch",
            "pack_score_ms_per_batch", "pack_score_zones_ms_per_batch",
            "score_live_share", "score_sig_rows_per_batch",
            "compiles_in_window"} <= declared
    # what other cells' traffic alone can report stays theirs
    assert not any(n.startswith(("preempt_", "gang_", "shard_", "mesh_",
                                 "node_", "carry_"))
                   or "image" in n for n in declared)


# -- the run -----------------------------------------------------------------


def run_cell(capsys, trace=0, seed=2**31 + 5100, keep_trace="", rc_want=0,
             control=False):
    args = harness.public_arguments("test").parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "1.5",
        "--trace", str(trace), "--rehearsal",
    ])
    with rules.one_traced_run_at_a_time(ROOT):
        rc = harness.run_one(args, time.perf_counter(), keep_trace=keep_trace,
                             control=control)
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
    real = rollout_waves.prepare

    def prepare(run, params, seconds):
        break_it(run)
        return real(run, params, seconds)

    monkeypatch.setattr(rollout_waves, "prepare", prepare)


@pytest.mark.parametrize("seed", [2**31 + 5100, 51])
def test_the_cell_is_correct_and_every_line_reads_0(capsys, seed):
    line, out, lines = run_cell(capsys, seed=seed, control=True)
    assert line["correct"] is True and line["failed"] == 0, out[-30:]
    assert line["attempted"] > 0 and line["attempted"] % 120 == 0
    assert set(line["metrics"]) == {
        "bound_pods_per_s", "pod_to_bind_p50_ms", "setup_s"}
    assert all(l.endswith("-> ok") for l in lines), lines
    value, text = compared(lines, WINDOW)
    assert value == 0 and "pods of 12 services" in text
    assert "0 such pods in all, 0 unbound" in text
    value, text = compared(lines, CHECK)
    assert value == 0 and "64 pods, 64 bound" in text
    (note,) = [l for l in out if l.startswith("rollout waves: ")]
    assert "12 Services and ReplicaSets, 96 residents" in note
    (window,) = [l for l in out if l.startswith("window: ")]
    assert "pods_fallback 0" in window
    (tier,) = [l for l in lines if l.startswith("compare tier: ")]
    assert "'sequential': 0" in tier and "'host_greedy': 0" in tier
    # the controls, at this size: the rows mixed up and the zones unheard
    # read above the limit; the full rule in float32 reads what the
    # program reads
    controls = {
        l.split(" pods ", 1)[1].split(" leaves ")[0]:
        int(l.split(" leaves ")[1].split(" ")[0])
        for l in out if l.startswith("control window: ")
    }
    assert len(controls) == len(window_check.CONTROLS)
    assert controls["each service scored by the next one's counts"] > 60
    assert controls["deaf to selector spread"] > 10
    assert controls["the full rule, resource scores in float32"] == 0


def test_the_parent_answers_by_the_precondition(capsys, monkeypatch):
    """What the parent is: 8 selector groups, the ninth sends the batch
    whole to the host path. Warm-up's first create holds a pod of every
    service; the run ends there, exit code 2, with no result line."""
    from kubernetes_tpu.scheduler import batch

    real = batch.pack_score_batch

    def eight(pods, *args, **kw):
        if len({p.metadata.labels.get("app") for p in pods}) > 8:
            raise batch.ScoreEnvelopeExceeded("selector_groups")
        return real(pods, *args, **kw)

    monkeypatch.setattr(batch, "pack_score_batch", eight)
    _, out, err = run_cell(capsys, rc_want=2)
    assert "a batch of 12 pods of 12 services" in err
    assert "pods_fallback moved by 12" in err
    assert "that many selector groups and preferred-affinity rows" in err
    assert not (out and out[-1].startswith("{"))


def failing(lines):
    return [l for l in lines if not l.endswith("-> ok")]


def test_a_scheduler_deaf_to_both_scorers_fails_the_comparisons_lines_alone(
        capsys, monkeypatch):
    from kubernetes_tpu.scheduler import batch

    real = batch.pack_score_batch

    def deaf(pods, snapshot, nt, informers, weights, **kw):
        return real(pods, snapshot, nt, informers, dict(
            weights, DefaultPodTopologySpread=0, InterPodAffinity=0), **kw)

    after_warmup(
        monkeypatch,
        lambda run: monkeypatch.setattr(batch, "pack_score_batch", deaf),
    )
    line, out, lines = run_cell(capsys, seed=2**31 + 5101)
    assert line["correct"] is False and line["failed"] == 0, out[-30:]
    value, text = compared(lines, WINDOW)
    assert value >= 12 and text.endswith("FAILED")  # a tenth and more
    # every pod still binds and fits: the two comparisons' lines alone
    assert set(failing(lines)) <= {text, compared(lines, CHECK)[1]}


def test_a_scheduler_that_forgets_the_residents_terms_fails_the_check_wave(
        capsys, monkeypatch):
    """The owners' weight is the one fact the score packer keeps that no
    batch brings: a packer that loses it places every wave of the window
    as a right one does (selector spread's node term ranks alike there)
    and fails where the largest service's residents decide, in the pool."""
    after_warmup(
        monkeypatch,
        lambda run: monkeypatch.setattr(
            type(run.sched.family_facts), "term_owners", lambda self: []),
    )
    line, out, lines = run_cell(capsys, seed=2**31 + 5102)
    assert line["failed"] == 0, out[-30:]
    value, text = compared(lines, CHECK)
    if value:  # at 36 pool nodes not every seed's wave meets the case
        assert line["correct"] is False and text.endswith("FAILED")
        assert set(failing(lines)) <= {text, compared(lines, WINDOW)[1]}


# -- the controls at the timed size, with the reference alone ----------------


def pool_state():
    """The ballast pool of the deployment as ``Run.build_cluster`` and the
    generator make it, in the reference's own arrays: 5,000 nodes, the
    pool's 640 with their ballast, an init pod and 4 residents a node."""
    config = load(f"configs/{CONFIG}.json")
    cluster, spec = config["cluster"], config["services"]
    n, zones = cluster["nodes"], cluster["zones"]
    grid, per_zone = cluster["ballast"]["grid"], cluster["ballast"]["per_zone"]
    i = np.arange(n)
    j = i // zones
    pool = j < per_zone
    first = np.where(pool, j % grid, 0)
    second = np.where(pool, (j // grid) % grid, 0)
    shares = sr.zipf_shares(spec["residents"], spec["count"],
                            spec["zipf_exponent"], spec["app_seed"])
    counts = np.zeros((spec["count"] + 1, n), dtype=np.int64)
    for k, rows in enumerate(sr.resident_nodes(shares, n, spec["app_seed"])):
        np.add.at(counts[k], rows, 1)
    plain = counts.sum(axis=0) + 1  # the residents and an init pod
    nodes = reference.Nodes(
        cap_cpu=np.full(n, 32000), cap_mem=np.full(n, 64 << 30),
        cap_pods=np.full(n, 110),
        used_cpu=first * 1000 + second * 100 + plain * 250,
        used_mem=(first * 128 + second * 2048 + plain * 512) * MIB,
        used_pods=first + second + plain, zone=i % zones,
    )
    pod = reference.PodClass(cpu=250, mem=512 * MIB)

    def make(precision="exact"):
        return sr.State(nodes, pod, counts, spec["term_weight"], pool,
                        precision)

    return make, spec["count"], int(shares.argmax())


def test_every_control_reads_far_above_the_limit_in_the_check_wave():
    """4,096 pods to the pool, half of a fresh service and half of the
    largest: each broken rule leaves hundreds that the full rule does
    not explain, and the full rule in float32 leaves none."""
    make, fresh, largest = pool_state()
    wave = np.array([fresh, largest] * 2048)
    wave = wave[np.random.default_rng(51).permutation(len(wave))]
    read = {}
    for name, rule in window_check.CONTROLS:
        other = sr.schedule(make(rule.precision), wave, rule)
        read[name] = sr.certify(make(), wave, other)
    assert read.pop("the full rule, resource scores in float32") == 0
    assert len(read) == 5 and min(read.values()) >= 400, read


# -- the six metrics, the spans and the stats they read ----------------------


def test_the_metrics_read_the_spans_and_stats_of_a_traced_rehearsal(
        capsys, tmp_path):
    line, out, _ = run_cell(capsys, trace=1, keep_trace=str(tmp_path))
    assert line["correct"] is True
    got = line["metrics"]
    assert set(SIX_IN_ORDER) <= set(got)
    assert got["compiles_in_window"]["value"] == 0.0
    assert got["score_live_share"]["value"] == 1.0
    # 12 groups and 12 rows where a batch names every service
    assert 12 < got["score_dynamic_rows_per_batch"]["value"] <= 24
    assert got["score_dynamic_cuts_per_batch"]["value"] == 0.0
    assert 0 < got["score_census_recounted_share"]["value"] <= 1.0
    assert (got["pack_score_ms_per_batch"]["value"]
            >= got["pack_score_dynamic_ms_per_batch"]["value"]
            >= got["pack_score_selectors_ms_per_batch"]["value"]
            + got["pack_score_ipa_ms_per_batch"]["value"] > 0)
    # no static family is live: the placeholders' rows
    assert got["score_sig_rows_per_batch"]["value"] == 4.0
    # every batch of such a cluster drains the pipeline before it packs
    assert got["pack_drain_ms_per_batch"]["value"] >= 0.0
    (path,) = list(tmp_path.glob("*.xplane.pb"))
    trace = program_spans.read_trace(str(path))
    by_name = {}
    for sp in trace["spans"]:
        by_name.setdefault(sp["name"], []).append(sp)
    families = by_name["sched/pack.families"]
    for sp in families:
        stats = sp["stats"]
        # the residents' 12 terms, and the groups the batch named
        assert 12 < int(stats["score_dynamic_rows"]) <= 24
        assert int(stats["score_census_recounted"]) <= int(
            stats["score_census_nodes"]) == 48
    assert len(by_name["sched/pack.score.dynamic"]) == len(families)
    assert len(by_name["sched/pack.score.selectors"]) == len(families)
    assert len(by_name["sched/pack.score.ipa"]) == len(families)
    for sp in by_name["sched/pack.score.selectors"]:
        assert 0 < int(sp["stats"]["groups"]) <= 12
    for sp in by_name["sched/pack.score.ipa"]:
        assert int(sp["stats"]["rows"]) == 12
    assert "sched/pack.score.images" not in by_name
    assert "burst_pod_to_bind_p99_ms" in got and "wave_drain_pods_per_s" in got
