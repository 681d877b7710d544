"""The readers of the program's own spans (``chipbench/program_spans.py``,
``readers/idle_by_span``, ``span_stat_ratio``, ``span_stat_max``,
``stage_per_second``): on hand-made intervals, and on a slice of
basic-5000.burst-10k recorded on the chip with the spans in it
(chipbench/testdata/burst-10k-8s-spans.xplane.pb)."""

import functools
import json
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench import program_spans
from chipbench.readers import (
    idle_by_span,
    span_stat_max,
    span_stat_ratio,
    stage_per_second,
)

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "testdata"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = rules.NEW  # PR 24's fourteen


spec_of = functools.partial(rules.spec_of, ROOT)


def span(name, start, end, line=0, **stats):
    return {"name": name, "start": start, "end": end,
            "line": ("/host:CPU", line), "stats": stats}


# -- hand-made intervals -----------------------------------------------------


def test_interval_helpers():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert program_spans.intersect(a, b) == [(5, 10), (20, 25), (28, 30)]
    assert program_spans.complement(a, 0, 40) == [(10, 20), (30, 40)]
    assert program_spans.complement(a, 5, 25) == [(10, 20)]
    assert program_spans.complement([], 3, 9) == [(3, 9)]
    assert program_spans.total(b) == 32


@pytest.fixture
def made():
    """A slice of 100 in which the device is busy from 10 to 20 and from 60
    to 70: 80 idle. Two threads overlap in pack and ingest; a commit
    crosses the slice's end; a pop_wait is the only span open from 40 to
    60; nothing at all is open from 30 to 40."""
    return {
        "window": (0, 100),
        "busy": [(10, 20), (60, 70)],
        "device_plane": True,
        "spans": [
            span("sched/dispatch", 0, 30, 0, batch=1, pods=40,
                 queue_wait_sum_ms=400.0, queue_wait_max_ms=30.0),
            span("sched/pack", 0, 25, 0, batch=1),
            span("sched/pack.state", 2, 12, 0, batch=1),
            span("sched/ingest", 5, 15, 1, kind="Pod", events=9),
            span("sched/ingest", 22, 28, 1, kind="Pod", events=3),
            span("sched/pop_wait", 40, 60, 0),
            span("sched/mark/fallback", 45, 45, 0),
            span("sched/dispatch", 70, 80, 0, batch=2, pods=10,
                 queue_wait_sum_ms=600.0, queue_wait_max_ms=90.0),
            span("sched/commit", 90, 130, 2, batch=2),
            span("sched/dispatch", 120, 140, 0, batch=3, pods=1000,
                 queue_wait_sum_ms=9e6, queue_wait_max_ms=9e3),
            span("sched/gc", -20, -5, 0, generation=2),
        ],
    }


def test_idle_by_span_on_overlapping_threads(made):
    share = idle_by_span.share
    # pack is open 0-25, of which 10-20 is busy: 15 of 80 idle
    assert share(made, {"span": "sched/pack"}) == pytest.approx(100 * 15 / 80)
    # ingest on another thread overlaps pack: 5-10 and 22-28 are idle
    assert share(made, {"span": "sched/ingest"}) == pytest.approx(100 * 11 / 80)
    # a span that crosses the slice's edge counts for the part inside
    assert share(made, {"span": "sched/commit"}) == pytest.approx(100 * 10 / 80)
    # a span that lies outside the slice counts for nothing
    assert share(made, {"span": "sched/gc"}) == 0.0
    assert share(made, {"span": "sched/bind"}) == 0.0


def test_the_waiting_share_is_exclusive(made):
    waiting = idle_by_span.share(made, {"no_span_but": "sched/pop_wait"})
    # idle and under no span but pop_wait: 30-60 (a mark does not count as
    # work) and 80-90
    assert waiting == pytest.approx(100 * 40 / 80)
    working = program_spans.open_intervals(
        made, lambda name: name != "sched/pop_wait"
        and not name.startswith("sched/mark/")
    )
    under_any = program_spans.total(
        program_spans.intersect(program_spans.idle(made), working)
    )
    assert waiting + 100 * under_any / 80 == pytest.approx(100.0)


def test_a_device_that_never_idles_has_no_shares(made):
    made["busy"] = [(0, 100)]
    assert idle_by_span.share(made, {"span": "sched/pack"}) is None


def test_span_stat_readers_take_the_spans_that_began_in_the_slice(made):
    args = spec_of("queue_wait_ms_per_pod")["args"]
    assert span_stat_ratio.ratio(made, args) == pytest.approx(1000.0 / 50)
    assert span_stat_max.largest(
        made, spec_of("queue_wait_max_ms")["args"]
    ) == 90.0
    made["spans"] = [sp for sp in made["spans"]
                     if sp["name"] != "sched/dispatch"]
    assert span_stat_ratio.ratio(made, args) is None
    assert span_stat_max.largest(
        made, spec_of("queue_wait_max_ms")["args"]
    ) is None


def test_stage_per_second():
    args = spec_of("gc_pause_ms_per_s")["args"]
    assert args == {"stage": "gc", "beside": "ingest"}
    sample = {
        "start": {"t": 100.0, "stage_seconds": {"gc": 1.0, "ingest": 3.0}},
        "end": {"t": 150.0, "stage_seconds": {"gc": 6.0, "ingest": 9.0}},
    }
    assert stage_per_second.read(sample, args) == pytest.approx(100.0)
    # a stage that first ran inside the window
    del sample["start"]["stage_seconds"]["gc"]
    assert stage_per_second.read(sample, args) == pytest.approx(120.0)
    # a program that has the new timers and never collected spent nothing
    # there; one from before them has nothing to read
    del sample["end"]["stage_seconds"]["gc"]
    assert stage_per_second.read(sample, args) == 0.0
    sample["end"]["stage_seconds"] = {"pack": 9.0}
    assert stage_per_second.read(sample, args) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_declared_for_every_cell(name):
    """PR 24's fourteen, held to the rule that later PRs keep without
    touching this test: a PR appends; nothing that was there moves
    (``benchmark_rules.declared_since_pr24``)."""
    rules.declared_since_pr24(BENCH, ROOT, name)


def _appended(bench, entry):
    bench["per_layer"].append(dict(bench["per_layer"][0], name="appended"))
    bench["workloads"].append(dict(bench["workloads"][0], name="a.new-cell"))
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("a.new-cell")


def _moved(bench, entry):
    bench["per_layer"].insert(
        bench["per_layer"].index(entry) + 3,
        dict(bench["per_layer"][0], name="put_in_the_middle"),
    )


def _rewritten(bench, entry):
    del entry["workloads"][0]


def _unknown_cell(bench, entry):
    entry["workloads"].append("no-such.cell")


@pytest.mark.parametrize("change, kept", [
    (_appended, True), (_moved, False), (_rewritten, False),
    (_unknown_cell, False),
])
def test_the_rule_takes_an_addition_and_refuses_a_move(change, kept):
    bench = json.loads(json.dumps(BENCH))
    change(bench, next(m for m in bench["per_layer"] if m["name"] == NEW[0]))
    if kept:
        rules.declared_since_pr24(bench, ROOT, NEW[0])
    else:
        with pytest.raises(AssertionError):
            rules.declared_since_pr24(bench, ROOT, NEW[0])


# -- a program without the spans, and a trace that lost them ----------------


def _write_trace(tmp_path, body):
    """A CPU profiler session with the slice span around ``body``, laid
    out under ``tmp_path`` as the harness lays a cell's trace."""
    import jax

    log_dir = tmp_path / ".chipbench_trace" / "cell"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(program_spans.SLICE):
            body()
    finally:
        jax.profiler.stop_trace()
    return {"root": tmp_path, "cell": {"name": "cell"}}


def test_load_reads_the_runs_slice_once_and_none_without_spans(tmp_path):
    from kubernetes_tpu.utils import flightrecorder

    def body():
        with flightrecorder.stage("pack", pods=3):
            pass

    sample = _write_trace(tmp_path, body)
    trace = program_spans.load(sample)
    assert [sp["name"] for sp in trace["spans"]] == ["sched/pack"]
    assert trace["spans"][0]["stats"] == {"pods": 3}
    assert not trace["device_plane"]
    assert program_spans.load(sample) is trace  # opened once a run
    # no traced slice at all (``--trace 0``, or a reader's unit test)
    assert program_spans.load({"root": tmp_path, "cell": {"name": "x"}}) is None
    for name in NEW[6:]:
        spec = spec_of(name)
        reader = __import__(
            f"chipbench.readers.{spec['reader']}", fromlist=["read"]
        )
        assert reader.read(
            {"root": tmp_path, "cell": {"name": "x"}}, spec["args"]
        ) is None


def test_a_trace_without_spans_is_none_or_an_error(tmp_path, monkeypatch):
    sample = _write_trace(tmp_path / "a", lambda: None)
    # a CPU's trace (a rehearsal): nothing to read, no error
    assert program_spans.load(sample) is None
    # the chip's trace of a program that writes spans: an error, not a zero
    program_spans._loaded.clear()
    real = program_spans.read_trace
    monkeypatch.setattr(
        program_spans, "read_trace",
        lambda path: dict(real(path), device_plane=True),
    )
    with pytest.raises(RuntimeError, match="holds no 'sched/' span"):
        program_spans.load(sample)
    # ... and of a program from before the spans: nothing to read
    program_spans._loaded.clear()
    monkeypatch.setattr(program_spans, "program_has_spans", lambda: False)
    assert program_spans.load(sample) is None


# -- the recorded slice ------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """The first 8 s of a window of basic-5000.burst-10k on one v5e chip,
    with the program's spans (my chip run, PR 24)."""
    return program_spans.read_trace(
        str(DATA / "burst-10k-8s-spans.xplane.pb")
    )


def test_recorded_slice_agrees_with_the_reduction(recorded):
    from chipbench import tracing

    reduced = tracing.reduce(str(DATA / "burst-10k-8s-spans.xplane.pb"))
    w0, w1 = recorded["window"]
    assert (w1 - w0) / 1e9 == pytest.approx(reduced["window_s"])
    assert program_spans.total(recorded["busy"]) / 1e9 == pytest.approx(
        reduced["busy_s"]
    )
    assert recorded["device_plane"]
    idle_s = program_spans.total(program_spans.idle(recorded)) / 1e9
    assert idle_s == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_recorded_spans_of_a_batch_share_its_id_across_threads(recorded):
    by_batch: dict = {}
    for sp in recorded["spans"]:
        if "batch" in sp["stats"]:
            by_batch.setdefault(sp["stats"]["batch"], []).append(sp)
    whole = [
        spans for spans in by_batch.values()
        if {"sched/dispatch", "sched/pack", "sched/solve_dispatch",
            "sched/solve_wait", "sched/commit", "sched/bind"}
        <= {sp["name"] for sp in spans}
    ]
    assert len(whole) >= 6  # some waves of three batches
    for spans in whole:
        assert len({sp["line"] for sp in spans}) >= 3
    names = {sp["name"] for sp in recorded["spans"]}
    assert {"sched/ingest", "sched/pop", "sched/pop_wait", "sched/gc",
            "sched/pack.state", "sched/pack.pods", "sched/pack.masks",
            "sched/bind.api", "sched/commit.gather",
            "sched/commit.assume"} <= names


def test_recorded_slice_through_every_new_trace_reader(recorded):
    values = {}
    for name in NEW[6:]:
        spec = spec_of(name)
        if spec["reader"] == "idle_by_span":
            values[name] = idle_by_span.share(recorded, spec["args"])
        elif spec["reader"] == "span_stat_ratio":
            values[name] = span_stat_ratio.ratio(recorded, spec["args"])
        else:
            values[name] = span_stat_max.largest(recorded, spec["args"])
    assert all(v is not None for v in values.values()), values
    for name, value in values.items():
        if name.endswith("_pct"):
            assert 0.0 <= value <= 100.0, name
    assert values["idle_under_pack_pct"] > values["idle_under_commit_pct"] > 0
    assert 0 < values["queue_wait_ms_per_pod"] <= values["queue_wait_max_ms"]
    working = program_spans.open_intervals(
        recorded, lambda n: n != "sched/pop_wait"
        and not n.startswith("sched/mark/")
    )
    gaps = program_spans.idle(recorded)
    under_any = 100.0 * program_spans.total(
        program_spans.intersect(gaps, working)
    ) / program_spans.total(gaps)
    assert values["idle_scheduler_waiting_pct"] + under_any == pytest.approx(100.0)
