"""The one stage primitive (utils/flightrecorder.py ``stage``): each use
writes the always-on total, the batch's ring record and the profiler's
trace once, and a CPU ``jax.profiler`` session around a small burst shows
the scheduler's stages on the lines of the threads that did the work."""

import gc
import glob
import os
import pathlib
import subprocess
import sys
import threading
import time
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from kubernetes_tpu.apiserver.server import ADDED, APIServer, WatchEvent
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import (
    Informer,
    InformerFactory,
    ResourceEventHandler,
)
from kubernetes_tpu.plugins.queuesort import PrioritySort
from kubernetes_tpu.queue.scheduling_queue import PriorityQueue
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import flightrecorder, gc_tuning, metrics
from kubernetes_tpu.utils.gc_tuning import GCBatchGuard


@pytest.fixture(autouse=True)
def _clean():
    flightrecorder.RECORDER.reset()
    yield
    flightrecorder.ENABLED = True


@contextmanager
def profiled(tmp_path):
    """A profiler session; the list it yields holds, after the block, the
    ``sched/`` events as dicts (name, start, end, line, stats)."""
    events = []
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    ))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("sched/"):
                    events.append({
                        "name": ev.name, "start": ev.start_ns,
                        "end": ev.start_ns + ev.duration_ns,
                        "line": (plane.name, index),
                        "stats": dict(ev.stats),
                    })


def named(events, name):
    return [ev for ev in events if ev["name"] == name]


def own_stats(ev):
    """A span's stats less the two every clocked span may carry: the
    primitive's ``cpu_ms`` and a hand-off's ``waited_ms``."""
    return {k: v for k, v in ev["stats"].items()
            if k not in ("cpu_ms", "waited_ms")}


def _stack(num_nodes=16, max_batch=64):
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=max_batch)
    for i in range(num_nodes):
        client.create_node(
            make_node(f"node-{i}")
            .capacity(cpu="32", memory="64Gi", pods=110).obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    return server, client, informers, sched


def _burst(client, sched, count, tag="p"):
    client.create_pods_bulk([
        make_pod(f"{tag}-{i}").container(cpu="10m", memory="16Mi").obj()
        for i in range(count)
    ])
    _wait_bound(client, sched, count)


def _report_status(server, name):
    """A kubelet's status write: conditions, nothing of the spec."""
    from kubernetes_tpu.api.types import NodeCondition

    def mutate(node):
        node.status.conditions = [NodeCondition("Ready", "True")]

    server.guaranteed_update("Node", "", name, mutate)


def _wait_bound(client, sched, count):
    deadline = time.time() + 60
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if len(pods) >= count and all(p.spec.node_name for p in pods):
            break
        time.sleep(0.02)
    else:
        raise AssertionError("the burst did not bind")
    sched.wait_for_inflight_binds()


# -- the primitive alone -----------------------------------------------------


def test_stage_writes_total_ring_and_trace_once_each(tmp_path):
    totals = flightrecorder.StageTotals()
    span = flightrecorder.RECORDER.begin_batch(3)
    with profiled(tmp_path) as events:
        with flightrecorder.stage("pack", span, totals, pods=3) as st:
            time.sleep(0.002)
            st.set_metadata(padded=8)
    assert totals.calls() == {"pack": 1}
    assert totals.seconds()["pack"] == pytest.approx(st.seconds)
    assert st.seconds >= 0.002
    assert span.stages == {"pack": st.seconds}
    (ev,) = named(events, "sched/pack")
    assert own_stats(ev) == {"batch": span.batch_id, "pods": 3, "padded": 8}
    assert 0 <= ev["stats"]["cpu_ms"] <= st.seconds * 1e3 + TICK_MS
    assert (ev["end"] - ev["start"]) / 1e9 == pytest.approx(
        st.seconds, abs=1e-3
    )


@pytest.mark.parametrize("total,span_name", [
    ("pop_batch", "sched/pop"),
    ("device_solve", "sched/solve_dispatch"),
    ("download", "sched/solve_wait"),
])
def test_three_totals_keep_their_names_under_honest_span_names(
    tmp_path, total, span_name
):
    totals = flightrecorder.StageTotals()
    span = flightrecorder.RECORDER.begin_batch(1)
    with profiled(tmp_path) as events:
        with flightrecorder.stage(total, span, totals):
            pass
    assert list(totals.seconds()) == [total]
    assert list(span.to_dict()["stages_ms"]) == [total]
    assert len(named(events, span_name)) == 1


def test_totals_merge_threads_without_losing_time():
    totals = flightrecorder.StageTotals()

    def work():
        for _ in range(200):
            totals.add("commit", 0.001)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert totals.calls()["commit"] == 800
    assert totals.seconds()["commit"] == pytest.approx(0.8)


def test_mark_lands_in_the_trace_and_in_the_ring(tmp_path):
    with profiled(tmp_path) as events:
        flightrecorder.mark("jit_recompile", signature="solve_packed")
    (ev,) = named(events, "sched/mark/jit_recompile")
    assert ev["end"] - ev["start"] < 1e6  # zero-length: under a millisecond
    marks = flightrecorder.RECORDER.dump()["marks"]
    assert [m["kind"] for m in marks] == ["jit_recompile"]


def test_the_guards_collections_are_gc_stages(tmp_path, monkeypatch):
    totals = flightrecorder.StageTotals()
    guard = GCBatchGuard(totals)
    # a whole walk on record that no idle collection of this test pays for
    monkeypatch.setattr(gc_tuning, "_whole_walk_seconds", 3600.0)
    try:
        with profiled(tmp_path) as events:
            guard.active()
            guard._last_collect -= 2 * guard.ACTIVE_COLLECT_INTERVAL_S
            guard.active()  # overdue under sustained load: a gen-1 pass
            guard.idle()  # active -> idle: the full pass, survivors frozen
            guard.close()  # the whole heap, and nothing left frozen
    finally:
        gc.unfreeze()
        gc.enable()
    assert totals.calls() == {"gc": 3}
    assert [(ev["stats"]["generation"], ev["stats"]["whole"])
            for ev in named(events, "sched/gc")] == [(1, 0), (2, 0), (2, 1)]
    assert (guard.freezes, guard.whole_walks) == (1, 1)


def test_pop_wait_and_pop_work_are_side_by_side(tmp_path):
    queue = PriorityQueue(PrioritySort().queue_sort_less)
    totals = flightrecorder.StageTotals()
    pod = make_pod("late").container(cpu="10m", memory="16Mi").obj()
    timer = threading.Timer(0.15, queue.add, args=(pod,))
    with profiled(tmp_path) as events:
        timer.start()
        batch = queue.pop_batch(8, timeout=5.0, totals=totals)
        timer.join()
    assert [pi.pod.metadata.name for pi in batch] == ["late"]
    seconds = totals.seconds()
    assert seconds["pop_wait"] == pytest.approx(
        queue.last_pop_wait_seconds
    )
    assert seconds["pop_wait"] >= 0.1
    assert seconds["pop_batch"] == pytest.approx(
        queue.last_pop_work_seconds
    )
    assert seconds["pop_batch"] < 0.05
    waits, works = named(events, "sched/pop_wait"), named(events, "sched/pop")
    assert waits and len(works) == len(waits) + 1
    for wait in waits:  # never nested: a wait is open only between works
        assert not any(
            work["start"] < wait["end"] and wait["start"] < work["end"]
            for work in works
        )


def test_the_queue_says_which_pops_found_their_window_spent(
    tmp_path, monkeypatch
):
    """Four pops, two of which found their oldest pod aged: the two
    counters say so, and a ``pop_wait`` span says whether it waited for
    a first pod or for company, and then what was left of the window."""
    queue = PriorityQueue(PrioritySort().queue_sort_less)
    window = 0.2

    def pods(tag, count=1):
        return [make_pod(f"{tag}-{i}").obj() for i in range(count)]

    def pop(size=8):
        return [pi.pod.metadata.name
                for pi in queue.pop_batch(size, timeout=5.0, window=window)]

    pops0 = metrics.queue_pops.value()
    spent0 = metrics.queue_window_spent_pops.value()
    timer = threading.Timer(0.1, queue.add_many, args=(pods("idle"),))
    with profiled(tmp_path) as events:
        timer.start()
        assert pop() == ["idle-0"]  # arrives at a waiting pop
        timer.join()
        queue.add_many(pods("aged"))
        time.sleep(window / 2)
        assert pop() == ["aged-0"]  # half of its window spent
        queue.add_many(pods("old"))
        time.sleep(window + 0.05)
        assert pop() == ["old-0"]  # all of it
        assert queue.last_pop_wait_seconds == 0.0
        queue.add_many(pods("full", 2))
        time.sleep(0.02)
        assert len(pop(size=2)) == 2  # a full batch waits on no window
        assert queue.pop_batch(8, timeout=0.01, window=window) == []
    assert metrics.queue_pops.value() - pops0 == 4  # the empty one is none
    assert metrics.queue_window_spent_pops.value() - spent0 == 2
    waits = sorted(named(events, "sched/pop_wait"), key=lambda ev: ev["start"])
    first = [ev for ev in waits if ev["stats"]["waits_for"] == "first_pod"]
    company = [ev for ev in waits if ev["stats"]["waits_for"] == "company"]
    assert len(first) + len(company) == len(waits)
    assert len(first) == 2 and len(company) == 2  # the last found no pod
    assert all("window_left_ms" not in ev["stats"] for ev in first)
    whole, half = (ev["stats"]["window_left_ms"] for ev in company)
    assert 0.75 * window * 1e3 < whole <= window * 1e3
    assert 0 < half <= 0.5 * window * 1e3
    for ev in company:  # the window's cost is a sum over these spans
        assert (ev["end"] - ev["start"]) / 1e6 == pytest.approx(
            ev["stats"]["window_left_ms"], abs=50
        )
    # with no session nothing is built for the span
    built = []
    stage = flightrecorder.stage
    monkeypatch.setattr(
        flightrecorder, "stage",
        lambda name, **kw: built.append(kw) or stage(name, **kw),
    )
    queue.add_many(pods("plain"))
    assert pop() == ["plain-0"]
    assert built and all(set(kw) == {"totals"} for kw in built)


def test_an_informer_frame_is_one_ingest_span(tmp_path):
    totals = flightrecorder.StageTotals()
    seen = []
    informer = Informer(APIServer(), "Pod")
    informer.add_event_handler(ResourceEventHandler(
        on_batch=seen.extend, stage_totals=totals
    ))
    frame = [
        WatchEvent(ADDED, make_pod(f"f-{i}").obj(), i + 1) for i in range(3)
    ]
    with profiled(tmp_path) as events:
        informer._apply_batch(frame)
    assert len(seen) == 3
    assert totals.calls() == {"ingest": 1}
    (ev,) = named(events, "sched/ingest")
    # no ``waited_ms``: these events went through no broadcast
    assert ev["stats"].keys() == {"kind", "events", "cpu_ms"}
    assert own_stats(ev) == {"kind": "Pod", "events": 3}


# -- on a small burst --------------------------------------------------------


@pytest.fixture
def burst_trace(tmp_path):
    """120 pods in batches of at most 64 under a profiler session."""
    server, client, informers, sched = _stack()
    sched.start()
    try:
        with profiled(tmp_path) as events:
            _burst(client, sched, 120)
            # the broadcaster emits a frame 0.2 s after its first bind:
            # every pod's event is stored before the session ends
            deadline = time.time() + 60
            while len(client.list_events()[0]) < 120:
                assert time.time() < deadline, "the events never came"
                time.sleep(0.02)
            # a kubelet's status report: the node handlers' stage has a
            # total since set-up's node adds, so it has a span here too
            _report_status(server, "node-0")
            # the run loop's idle point, 0.5 s after the last pop, makes
            # one full collection. Its span is in the trace only once it
            # has ENDED, and in a worker whose heap earlier test files
            # grew it takes longer than any fixed sleep allows: wait for
            # the stage's total, which is written after the span closes
            deadline = time.time() + 60
            while not sched.stage_totals.calls().get("gc"):
                assert time.time() < deadline, "the idle collect never ran"
                time.sleep(0.02)
        dump = flightrecorder.RECORDER.dump()
        yield events, dump, sched
    finally:
        sched.stop()
        informers.stop()


def test_a_burst_shows_every_stage_with_a_shared_batch(burst_trace):
    events, dump, _sched = burst_trace
    for name in ("sched/pack", "sched/commit", "sched/bind", "sched/ingest",
                 "sched/dispatch", "sched/solve_dispatch", "sched/solve_wait",
                 "sched/bind.api", "sched/pop", "sched/pop_wait", "sched/gc"):
        assert named(events, name), name
    batch_ids = {s["batch_id"] for s in dump["spans"]}
    assert len(batch_ids) >= 2
    for batch in batch_ids:
        mine = [ev for ev in events if ev["stats"].get("batch") == batch]
        names = {ev["name"] for ev in mine}
        assert {"sched/dispatch", "sched/pack", "sched/solve_dispatch",
                "sched/solve_wait", "sched/commit", "sched/bind",
                "sched/bind.api"} <= names
        # dispatcher, committer and bind pool: three threads' lines
        assert len({ev["line"] for ev in mine}) >= 3
        # no site is instrumented twice
        for name in ("sched/pack", "sched/commit", "sched/bind"):
            assert len([ev for ev in mine if ev["name"] == name]) == 1


def test_packs_children_lie_inside_their_parent(burst_trace):
    events, _dump, _sched = burst_trace
    packs = named(events, "sched/pack")
    for child_name in ("sched/pack.snapshot", "sched/pack.state",
                       "sched/pack.pods", "sched/pack.masks",
                       "sched/pack.families"):
        children = named(events, child_name)
        assert len(children) == len(packs)
        for child in children:
            (parent,) = [p for p in packs
                         if p["stats"]["batch"] == child["stats"]["batch"]]
            assert parent["line"] == child["line"]
            assert parent["start"] <= child["start"]
            assert child["end"] <= parent["end"]
    for pack in packs:
        (dispatch,) = [d for d in named(events, "sched/dispatch")
                       if d["stats"]["batch"] == pack["stats"]["batch"]]
        assert dispatch["start"] <= pack["start"]
        assert pack["end"] <= dispatch["end"]


def _packs_children_of_a_plain_burst(events):
    """What a plain burst's packs say whatever the order of a pack and
    the commit of the batch ahead of it; the snapshot refreshes and the
    row repacks by start, for what does depend on that order."""
    by_start = lambda ev: ev["start"]
    refreshes = sorted(named(events, "sched/pack.snapshot"), key=by_start)
    for refresh in refreshes:
        assert refresh["stats"]["nodes"] == 16
        assert 0 <= refresh["stats"]["nodes_refreshed"] <= 16
    masks = sorted(named(events, "sched/pack.masks"), key=by_start)
    assert len(masks) >= 2
    for mask in masks:  # plain pods: one signature a batch
        assert mask["stats"]["rows"] == 1
    # built once, for the first batch; no node object changed since
    assert [m["stats"]["rows_reused"] for m in masks] == \
        [0] + [1] * (len(masks) - 1)
    # the row repack says what it did itself: rows written, and this
    # thread's CPU time beside the span's wall clock
    states = sorted(named(events, "sched/pack.state"), key=by_start)
    for state in states:
        assert 0 <= state["stats"]["rows"] <= 16
        assert 0 <= state["stats"]["cpu_ms"] <= \
            (state["end"] - state["start"]) / 1e6 + 1.0
    return refreshes, states


def _refreshes_follow_the_commits_that_landed(events, refreshes, states):
    """The pipeline packs a batch without waiting for the one ahead of
    it to commit, so a refresh reads the commits that ended before it
    began and nothing of a burst none of whose commits had begun.
    Returns how many refreshes after the first were of each kind."""
    commits = named(events, "sched/commit")
    after, ahead = 0, 0
    for before, refresh, state in zip(refreshes, refreshes[1:], states[1:]):
        if any(before["end"] <= commit["start"]
               and commit["end"] <= refresh["start"] for commit in commits):
            assert refresh["stats"]["nodes_refreshed"] >= 1
            assert state["stats"]["rows"] >= 1
            after += 1
        elif all(refresh["end"] <= commit["start"] for commit in commits):
            assert refresh["stats"]["nodes_refreshed"] == 0
            ahead += 1
    return after, ahead


def test_packs_children_say_how_much_was_incremental(burst_trace):
    """One burst. Its tail batch is packed after the first batch's
    commit where it waited for company meanwhile (its pods younger than
    the batch window when the pop came back), and ahead of it where the
    first dispatch outlasted the window (a cold compile): the window
    runs from the oldest pod's arrival, so the aged tail leaves at once.
    Either way the refresh says what had landed."""
    events, _dump, _sched = burst_trace
    refreshes, states = _packs_children_of_a_plain_burst(events)
    _refreshes_follow_the_commits_that_landed(events, refreshes, states)


def test_a_burst_created_once_the_one_ahead_bound_refreshes_its_nodes(
    tmp_path
):
    """Two bursts, the second created once the first has bound, so its
    pack follows the first's commits whatever the pipeline and the batch
    window made of the first's own batches."""
    _server, client, informers, sched = _stack()
    sched.start()
    try:
        with profiled(tmp_path) as events:
            _burst(client, sched, 100)
            _burst(client, sched, 20, tag="q")
    finally:
        sched.stop()
        informers.stop()
    refreshes, states = _packs_children_of_a_plain_burst(events)
    # the first burst's commits changed nodes, so the last refreshed some
    assert refreshes[-1]["stats"]["nodes_refreshed"] >= 1
    assert states[-1]["stats"]["rows"] >= 1
    after, _ahead = _refreshes_follow_the_commits_that_landed(
        events, refreshes, states
    )
    assert after >= 1


def test_an_aged_tail_batch_is_dispatched_without_a_wait_for_company(
    tmp_path
):
    """A burst that aged past the batch window before the dispatcher
    came to it (here the scheduler starts late; on the chip a dispatch
    outlasts the window): the full batch leaves at once as ever, and the
    partial batch behind it no longer waits a window for company that
    cannot come, so nothing makes it wait for the batch ahead to
    commit. The pipeline packs it against the carry either way."""
    _server, client, informers, sched = _stack()
    try:
        with profiled(tmp_path) as events:
            client.create_pods_bulk([
                make_pod(f"p-{i}").container(cpu="10m", memory="16Mi").obj()
                for i in range(120)
            ])
            deadline = time.time() + 60
            while sched.queue.active_count() < 120:
                assert time.time() < deadline, "the burst never queued"
                time.sleep(0.01)
            time.sleep(5 * sched.batch_window)
            spent0 = metrics.queue_window_spent_pops.value()
            sched.start()
            _wait_bound(client, sched, 120)
    finally:
        sched.stop()
        informers.stop()
    dispatches = sorted(named(events, "sched/dispatch"),
                        key=lambda ev: ev["start"])
    assert [d["stats"]["pods"] for d in dispatches] == [64, 56]
    assert metrics.queue_window_spent_pops.value() - spent0 == 1
    for wait in named(events, "sched/pop_wait"):
        if wait["start"] < dispatches[-1]["start"]:
            assert wait["stats"]["waits_for"] == "first_pod"
    refreshes, states = _packs_children_of_a_plain_burst(events)
    _refreshes_follow_the_commits_that_landed(events, refreshes, states)


def _constrained(tag, cpu):
    """Two templates: a zone-spread app and a host-anti app."""
    pods = [
        make_pod(f"{tag}-sp-{i}").labels(app=f"{tag}-sp")
        .spread_constraint(1, "zone", match_labels={"app": f"{tag}-sp"})
        .container(cpu=cpu, memory="16Mi").obj() for i in range(6)
    ]
    return pods + [
        make_pod(f"{tag}-an-{i}").labels(app=f"{tag}-an")
        .pod_affinity("kubernetes.io/hostname", {"app": f"{tag}-an"},
                      anti=True)
        .container(cpu=cpu, memory="16Mi").obj() for i in range(4)
    ]


def test_pack_families_says_what_it_kept(tmp_path):
    """``sched/pack.families`` carries the kept facts' five counters
    (ops/family_facts.py): nodes, nodes recounted, node-value rows asked
    for and served from the store, pod templates."""
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=64)
    for i in range(6):
        client.create_node(
            make_node(f"node-{i}")
            .labels(zone=f"z{i % 3}",
                    **{"kubernetes.io/hostname": f"node-{i}"})
            .capacity(cpu="32", memory="64Gi", pods=110).obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()

    def batch(pods, binds):
        client.create_pods_bulk(pods)
        deadline = time.time() + 30
        while len(sched.queue.pending_pods()) < len(pods):
            assert time.time() < deadline, "the pods never queued"
            time.sleep(0.01)
        assert sched.schedule_batch(timeout=1.0) == len(pods)
        sched.wait_for_inflight_binds()
        while binds and time.time() < deadline:
            if (
                all(client.get_pod(p.metadata.namespace, p.metadata.name)
                    .spec.node_name for p in pods)
                and not sched.cache._assumed_pods
            ):
                break
            time.sleep(0.01)
        if not binds:  # out of the queue, so that no later batch retries
            for p in pods:
                client.delete_pod(p.metadata.namespace, p.metadata.name)
            while sched.queue.pending_pods():
                assert time.time() < deadline
                time.sleep(0.01)

    plain = [make_pod(f"plain-{i}").container(cpu="10m", memory="16Mi").obj()
             for i in range(8)]
    try:
        with profiled(tmp_path) as events:
            batch(_constrained("a", cpu="64"), binds=False)  # fits nowhere
            batch(_constrained("b", cpu="64"), binds=False)
            before_plain = sched.family_facts.tally()
            batch(plain, binds=True)
            assert sched.family_facts.tally() == before_plain
            batch(_constrained("c", cpu="10m"), binds=True)
            batch(_constrained("d", cpu="10m"), binds=True)
    finally:
        sched.stop()
        informers.stop()
    assert sched.pods_fallback == 0
    spans = sorted(named(events, "sched/pack.families"),
                   key=lambda ev: ev["start"])
    first, quiet, plain_one, fourth, after_binds = [
        {k: ev["stats"][k] for k in (
            "nodes", "nodes_recounted", "node_rows", "node_rows_reused",
            "templates")}
        for ev in spans
    ]
    # first use: every node counted, the zone and hostname rows built
    assert first == {"nodes": 6, "nodes_recounted": 6, "node_rows": 2,
                     "node_rows_reused": 0, "templates": 2}
    # nothing changed since: no node recounted, every row from the store
    assert quiet == {"nodes": 6, "nodes_recounted": 0, "node_rows": 2,
                     "node_rows_reused": 2, "templates": 2}
    assert plain_one == dict.fromkeys(first, 0)  # never entered
    # the plain pods landed on some nodes: those alone are recounted
    assert 1 <= fourth["nodes_recounted"] <= 6
    assert 1 <= after_binds["nodes_recounted"] <= 6
    for stats in (fourth, after_binds):
        assert stats["node_rows"] == stats["node_rows_reused"] == 2


def test_dispatch_spans_carry_the_rings_queue_waits(burst_trace):
    events, dump, _sched = burst_trace
    dispatches = {d["stats"]["batch"]: d["stats"]
                  for d in named(events, "sched/dispatch")}
    assert len(dispatches) == len(dump["spans"])
    for span in dump["spans"]:
        stats = dispatches[span["batch_id"]]
        waits = [p["queue_wait_ms"] for p in span["pods"]]
        assert stats["pods"] == span["size"] == len(waits)
        assert stats["padded"] == span["padded"]
        assert stats["queue_wait_sum_ms"] == pytest.approx(
            sum(waits), abs=0.001 * len(waits) + 0.001
        )
        assert stats["queue_wait_max_ms"] == pytest.approx(
            max(waits), abs=0.002
        )
    assert sum(s["pods"] for s in dispatches.values()) == 120


def test_solve_dispatch_says_how_many_steps_the_batch_needs(burst_trace):
    """``steps`` is the batch's pod count: what a one-chip kernel runs of
    the ``padded`` its ``sched/dispatch`` span says, beside how the carry
    was brought up to date."""
    events, dump, _sched = burst_trace
    solves = {s["stats"]["batch"]: s["stats"]
              for s in named(events, "sched/solve_dispatch")}
    assert len(solves) == len(dump["spans"])
    for span in dump["spans"]:
        stats = solves[span["batch_id"]]
        assert stats["steps"] == span["size"] <= span["padded"]
        assert {"tier", "devices", "carry", "carry_rows"} <= set(stats)
    assert sum(s["steps"] for s in solves.values()) == 120


def test_stage_seconds_keeps_its_keys_and_gains_the_new(burst_trace):
    _events, dump, sched = burst_trace
    seconds = sched.stage_seconds
    old = {"pop_batch", "pop_wait", "pack", "device_solve", "download",
           "commit"}
    new = {"ingest", "bind", "bind.api", "gc", "pack.state", "pack.pods",
           "pack.masks", "pack.snapshot", "pack.families", "events"}
    assert old | new <= set(seconds)
    assert "classify" not in seconds  # per pod: only under profile_stages
    assert all(v >= 0 for v in seconds.values())
    parts = sum(seconds[name] for name in (
        "pack.snapshot", "pack.state", "pack.pods", "pack.masks",
        "pack.families",
    ))
    assert parts <= seconds["pack"]
    assert seconds["bind.api"] <= seconds["bind"]
    # the ring's stages_ms keeps its names; the bulk bind joins them
    for span in dump["spans"]:
        assert {"pack", "device_solve", "download", "commit", "bind"} <= set(
            span["stages_ms"]
        )
    batches = len(dump["spans"])
    calls = sched.stage_totals.calls()
    for name in ("pack", "device_solve", "download", "commit", "bind",
                 "pack.snapshot", "pack.families"):
        assert calls[name] == batches, name


def test_classify_is_timed_per_pod_only_when_asked():
    server, client, informers, sched = _stack(num_nodes=4)
    sched.profile_stages = True
    sched.start()
    try:
        _burst(client, sched, 10, tag="c")
    finally:
        sched.stop()
        informers.stop()
    assert sched.stage_totals.calls()["classify"] == 10


def test_without_the_ring_totals_and_annotations_still_work(tmp_path):
    flightrecorder.ENABLED = False
    server, client, informers, sched = _stack(num_nodes=4)
    sched.start()
    try:
        with profiled(tmp_path) as events:
            _burst(client, sched, 20, tag="off")
            flightrecorder.mark("fallback", tier="xla")
    finally:
        sched.stop()
        informers.stop()
    assert flightrecorder.RECORDER.dump()["spans"] == []
    assert flightrecorder.RECORDER.dump()["marks"] == []
    seconds = sched.stage_seconds
    assert {"pack", "device_solve", "download", "commit", "bind",
            "ingest"} <= set(seconds)
    assert named(events, "sched/pack") and named(events, "sched/bind")
    assert named(events, "sched/mark/fallback")
    (dispatch,) = named(events, "sched/dispatch")[:1]
    assert dispatch["stats"]["batch"] == 0  # no ring, no batch id
    assert dispatch["stats"]["pods"] > 0
    assert dispatch["stats"]["queue_wait_sum_ms"] >= 0


def test_a_fresh_schedulers_totals_are_its_own():
    """An informer frame belongs to the scheduler whose handlers are
    registered on it: another stack's burst adds nothing here."""
    _s1, client1, informers1, sched1 = _stack(num_nodes=4)
    _s2, _client2, informers2, sched2 = _stack(num_nodes=4)
    base = sched2.stage_seconds.get("ingest", 0.0)
    sched1.start()
    try:
        _burst(client1, sched1, 10, tag="own")
    finally:
        sched1.stop()
        informers1.stop()
        informers2.stop()
    assert sched1.stage_seconds["ingest"] > 0
    assert "pack" in sched1.stage_seconds
    assert sched2.stage_seconds.get("ingest", 0.0) == base
    assert "pack" not in sched2.stage_seconds


# -- a stage tells its work from its waiting ---------------------------------

#: the stages that are given no totals, which the primitive does not clock
UNCLOCKED = {"sched/dispatch", "sched/commit.gather", "sched/commit.clone",
             "sched/commit.assume", "sched/pack.score",
             "sched/pack.score.images", "sched/pack.score.zones",
             "sched/pack.image_index", "sched/dispatch.begin",
             "sched/dispatch.handshake", "sched/dispatch.landed"}
TICK_MS = 10.0  # the coarsest CPU clock a host of ours has


def _spin(seconds):
    until = time.thread_time() + seconds
    while time.thread_time() < until:
        pass


@pytest.mark.parametrize("body,cpu_ms,share", [
    # (the body, its CPU time in ms, its share of the wall clock): a
    # spin's share is near 1 on an idle host; beside five other test
    # workers the wall clock stretches, so it is held to a quarter
    (lambda: time.sleep(0.05), (0.0, 10.0), (0.0, 0.2)),
    (lambda: _spin(0.05), (50.0, 80.0), (0.25, 1.05)),
], ids=["sleeps", "spins"])
def test_the_primitive_tells_a_body_that_sleeps_from_one_that_spins(
    tmp_path, body, cpu_ms, share
):
    totals = flightrecorder.StageTotals()
    with profiled(tmp_path) as events:
        with flightrecorder.stage("commit", totals=totals) as st:
            body()
        with flightrecorder.stage("commit.gather") as bare:
            body()
    (ev,) = named(events, "sched/commit")
    wall_ms = (ev["end"] - ev["start"]) / 1e6
    assert cpu_ms[0] <= ev["stats"]["cpu_ms"] <= cpu_ms[1]
    assert share[0] <= ev["stats"]["cpu_ms"] / wall_ms <= share[1]
    # the span is the only place the CPU time goes: the totals keep
    # the wall clock's seconds and the calls
    assert totals.seconds()["commit"] == st.seconds
    assert totals.calls() == {"commit": 1}
    # no totals, no CPU clock: the rule is the primitive's own
    (gather,) = named(events, "sched/commit.gather")
    assert "cpu_ms" not in gather["stats"] and bare.seconds > 0


def test_without_a_session_the_cpu_clock_is_not_read(monkeypatch):
    """``time.thread_time`` is a system call of 5.7 us on the chip's
    host: the primitive pays it only for a span somebody will read, and
    builds no hand-off stat either."""
    def read():
        raise AssertionError("the CPU clock was read with no session")

    monkeypatch.setattr(flightrecorder, "_cpu_clock", read)
    totals = flightrecorder.StageTotals()
    with flightrecorder.stage("pack", totals=totals) as st:
        _spin(0.01)
        st.set_metadata(rows=3)  # nothing to put it on: no error
    assert st.seconds >= 0.01
    assert totals.seconds()["pack"] == st.seconds
    assert totals.calls() == {"pack": 1}
    assert flightrecorder.handoff_wait(time.perf_counter() - 1.0) == {}


def test_every_clocked_span_of_a_burst_carries_cpu_ms(burst_trace):
    events, _dump, sched = burst_trace
    clocked = [ev for ev in events if ev["name"] not in UNCLOCKED
               and not ev["name"].startswith("sched/mark/")]
    assert named(events, "sched/dispatch")
    assert len({ev["name"] for ev in clocked}) >= 15
    for ev in clocked:
        wall_ms = (ev["end"] - ev["start"]) / 1e6
        assert 0 <= ev["stats"]["cpu_ms"] <= wall_ms + TICK_MS, ev
    for ev in events:
        if ev["name"] in UNCLOCKED:
            assert "cpu_ms" not in ev["stats"], ev
    # every stage with an always-on total is a clocked span of the
    # trace, so a window's CPU time is a sum over the trace's spans
    spans = {ev["name"] for ev in clocked}
    for name in sched.stage_seconds:
        renamed = flightrecorder._SPAN_NAMES.get(name, name)
        assert "sched/" + renamed in spans, name
    # waiting for pods is no work
    waits = named(events, "sched/pop_wait")
    assert sum(ev["stats"]["cpu_ms"] for ev in waits) < 0.5 * sum(
        (ev["end"] - ev["start"]) / 1e6 for ev in waits
    )


def test_a_frame_of_events_is_a_span_on_the_broadcasters_line(burst_trace):
    """What follows a bulk bind off its thread: one ``sched/events`` span
    a frame the broadcaster drained, with the frame's size, how many of
    its items took the batch build, and the thread's CPU time."""
    events, _dump, sched = burst_trace
    frames = named(events, "sched/events")
    assert len(frames) == sched.stage_totals.calls()["events"] >= 1
    assert sum(ev["stats"]["events"] for ev in frames) == 120
    from kubernetes_tpu import native

    for ev in frames:
        # the burst emits Scheduled events alone: every item of a frame
        # is the batch build's where the extension built
        assert ev["stats"]["scheduled"] == (
            ev["stats"]["events"] if native.hotpath is not None else 0
        )
        wall_ms = (ev["end"] - ev["start"]) / 1e6
        assert 0 <= ev["stats"]["cpu_ms"] <= wall_ms + TICK_MS
        assert set(own_stats(ev)) == {"events", "scheduled"}
    binds = named(events, "sched/bind")
    assert min(ev["start"] for ev in frames) >= min(ev["end"] for ev in binds)
    # a thread of its own: not the bind pool's, the committer's or the
    # dispatcher's line
    (line,) = {ev["line"] for ev in frames}
    others = {ev["line"] for name in ("sched/bind", "sched/commit",
                                      "sched/pack", "sched/ingest")
              for ev in named(events, name)}
    assert line not in others
    assert sched.stage_seconds["events"] == pytest.approx(
        sum(ev["end"] - ev["start"] for ev in frames) / 1e9, rel=0.05,
        abs=2e-3,
    )


def test_without_a_session_a_frame_of_events_builds_no_annotation(
    monkeypatch,
):
    """The primitive's rule holds on the broadcaster's thread: with no
    session no annotation is built and no CPU clock is read; the always-on
    total is still written."""
    from kubernetes_tpu.utils.event_recorder import EventBroadcaster

    def refuse(*_args, **_kwargs):
        raise AssertionError("built for a session that does not run")

    monkeypatch.setattr(flightrecorder, "_cpu_clock", refuse)
    monkeypatch.setattr(flightrecorder, "TraceAnnotation", refuse)
    server = APIServer()
    broadcaster = EventBroadcaster(server)
    broadcaster.stop()
    pods = [make_pod(f"e-{i}").container(cpu="10m").obj() for i in range(9)]
    broadcaster._emit_batch(
        [("default-scheduler", pod, "Normal", "Scheduled", None)
         for pod in pods]
        + [("default-scheduler", pods[0], "Warning", "FailedScheduling",
            "0/0 nodes are available")]
    )
    assert len(server.list("Event")[0]) == 10
    assert broadcaster.stage_totals.calls() == {"events": 1}
    assert broadcaster.stage_totals.seconds()["events"] > 0


def test_the_stages_threads_carry_their_names_at_the_os(burst_trace):
    """The profiler names a trace's host line after the OS thread, so the
    informer, dispatcher, committer, bind-pool and broadcaster threads
    take their Python names there as they start
    (``flightrecorder.name_thread``)."""
    _events, _dump, _sched = burst_trace
    names = {p.read_text().strip()
             for p in pathlib.Path("/proc/self/task").glob("*/comm")}
    assert {"scheduler", "batch-committer", "informer-Pod", "bind_0",
            "event-broadcast"} <= names
    assert "informer-Persis" in names  # of 15 bytes, which Linux keeps


def test_a_fresh_processs_trace_names_the_line_after_the_thread(tmp_path):
    """In a process of its own: the profiler keeps a dead thread's
    record, name included, for the next thread that traces, so in a
    process that has traced before (this one) a line may carry a dead
    thread's name. The benchmark traces once a process."""
    script = (
        "import sys, threading, jax\n"
        "from kubernetes_tpu.utils import flightrecorder as fr\n"
        "def work():\n"
        "    fr.name_thread()\n"
        "    with fr.stage('ingest'): pass\n"
        "jax.profiler.start_trace(sys.argv[1])\n"
        "t = threading.Thread(target=work, name='informer-Pod')\n"
        "t.start(); t.join()\n"
        "jax.profiler.stop_trace()\n"
    )
    subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)], check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120,
        cwd=pathlib.Path(__file__).resolve().parents[1],
    )
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    lines = [line.name for plane in
             jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines
             if any(ev.name == "sched/ingest" for ev in line.events)]
    assert lines == ["informer-Pod"]


def test_ingest_spans_say_what_a_pod_frame_was_for(burst_trace):
    events, _dump, _sched = burst_trace
    frames = [ev["stats"] for ev in named(events, "sched/ingest")
              if ev["stats"]["kind"] == "Pod"]
    assert sum(f.get("adds", 0) for f in frames) == 120
    assert sum(f.get("bind_echoes", 0) for f in frames) == 120
    assert sum(f.get("deletes", 0) for f in frames) == 0
    assert sum(f["events"] for f in frames) == 240
    # through the apiserver's broadcast: every frame says how long its
    # oldest event waited for the informer's thread
    assert all(f["waited_ms"] >= 0 for f in frames)


def _pods_batch(informers):
    (handler,) = [h for h in informers.pods()._handlers
                  if h.on_batch is not None and h.stage_totals is not None]
    return handler.on_batch


def test_pods_batch_counts_a_mixed_frame_and_nothing_of_an_empty_one(
    monkeypatch
):
    _server, _client, informers, sched = _stack(num_nodes=2)
    # the counts are stats of a span, returned while a session runs
    monkeypatch.setattr(flightrecorder, "tracing", lambda: True)
    try:
        pods_batch = _pods_batch(informers)
        pending = make_pod("mixed").container(cpu="10m", memory="16Mi").obj()
        bound = make_pod("mixed").container(cpu="10m", memory="16Mi").obj()
        bound.metadata.uid = pending.metadata.uid
        bound.spec.node_name = "node-0"
        other = make_pod("other").container(cpu="10m", memory="16Mi").obj()
        assert pods_batch([]) is None
        assert pods_batch([
            ("ADDED", None, pending), ("ADDED", None, other),
            ("MODIFIED", pending, bound), ("DELETED", None, bound),
        ]) == {"adds": 2, "bind_echoes": 1, "deletes": 1}
        assert sched.queue.num_pending()["active"] == 1  # ``other``
        # a frame that is all one thing says that alone
        assert pods_batch([("DELETED", None, other)]) == {"deletes": 1}
        # and with no session there is no span to put them on
        monkeypatch.setattr(flightrecorder, "tracing", lambda: False)
        assert pods_batch([("ADDED", None, other)]) is None
        assert sched.queue.num_pending()["active"] == 1
    finally:
        informers.stop()


def _held_stack(max_inflight=None):
    """A stack whose dispatcher the test drives by hand and whose
    committer waits for ``release`` before it completes a batch."""
    server, client, informers, sched = _stack()
    if max_inflight is not None:
        sched.max_inflight = max_inflight
    sched.queue.run()
    release = threading.Event()
    complete = sched._complete_solve

    def held(p):
        release.wait(10)
        complete(p)

    sched._complete_solve = held
    return client, informers, sched, release


def _release_once_the_dispatcher_waits(sched, release):
    """Let the held committer go 0.1 s after the dispatcher began to
    wait on the pipeline's condition (the committer is held elsewhere,
    so a waiter there is the dispatcher)."""
    def run():
        deadline = time.time() + 60
        while not sched._pending_cv._waiters and time.time() < deadline:
            time.sleep(0.002)
        time.sleep(0.1)
        release.set()

    threading.Thread(target=run, daemon=True).start()


def _dispatch(client, sched, pods):
    client.create_pods_bulk(pods)
    deadline = time.time() + 30
    while len(sched.queue.pending_pods()) < len(pods):
        assert time.time() < deadline, "the pods never queued"
        time.sleep(0.01)
    assert sched.schedule_batch(timeout=1.0, pipeline=True) == len(pods)


def _plain(tag, count=4):
    return [make_pod(f"{tag}-{i}").container(cpu="10m", memory="16Mi").obj()
            for i in range(count)]


def test_a_batch_that_must_drain_shows_the_wait_inside_its_pack(tmp_path):
    client, informers, sched, release = _held_stack()
    spread = [
        make_pod(f"sp-{i}").labels(app="sp")
        .spread_constraint(1, "zone", match_labels={"app": "sp"})
        .container(cpu="10m", memory="16Mi").obj() for i in range(3)
    ]
    try:
        with profiled(tmp_path) as events:
            _dispatch(client, sched, _plain("first"))  # in flight, held
            _release_once_the_dispatcher_waits(sched, release)
            _dispatch(client, sched, spread)  # hard spread: has to drain
            sched._drain_pending()
            sched.wait_for_inflight_binds()
    finally:
        sched.stop()
        informers.stop()
    first, second = sorted(named(events, "sched/pack"),
                           key=lambda ev: ev["start"])
    (drain,) = named(events, "sched/pack.drain")
    assert drain["stats"]["reason"] == "spread"
    assert drain["stats"]["batch"] == second["stats"]["batch"]
    assert drain["line"] == second["line"]
    assert second["start"] <= drain["start"] and drain["end"] <= second["end"]
    assert drain["end"] - drain["start"] >= 0.09e9  # held 0.1 s
    assert drain["stats"]["cpu_ms"] <= 0.5 * (drain["end"] - drain["start"]) \
        / 1e6  # a wait, not work
    # the port-free plain batch before it drained nothing
    assert not any(first["start"] <= ev["start"] < first["end"]
                   for ev in named(events, "sched/pack.drain"))
    assert sched.stage_totals.calls()["pack.drain"] == 1
    assert sched.pipeline_drains == 1


def test_a_full_pipeline_shows_the_dispatcher_blocked_on_its_depth(tmp_path):
    client, informers, sched, release = _held_stack(max_inflight=1)
    try:
        with profiled(tmp_path) as events:
            _dispatch(client, sched, _plain("first"))  # fills the pipeline
            assert "inflight_wait" not in sched.stage_totals.calls()
            _release_once_the_dispatcher_waits(sched, release)
            _dispatch(client, sched, _plain("second"))  # blocks on the depth
            sched._drain_pending()
            sched.wait_for_inflight_binds()
    finally:
        sched.stop()
        informers.stop()
    (wait,) = named(events, "sched/inflight_wait")
    assert wait["end"] - wait["start"] >= 0.09e9  # held 0.1 s
    (pack,) = [ev for ev in named(events, "sched/pack")
               if ev["stats"]["batch"] == wait["stats"]["batch"]]
    # after the batch's dispatch, on the dispatcher's line, in no pack
    assert wait["line"] == pack["line"] and wait["start"] >= pack["end"]
    assert sched.stage_totals.calls()["inflight_wait"] == 1
    assert not named(events, "sched/pack.drain")


HOLD_S = 0.05


@pytest.mark.parametrize("held,span_name", [
    ("_complete_solve", "sched/solve_wait"),
    ("_bulk_binding_cycle_safe", "sched/bind"),
], ids=["committer", "bind-pool"])
def test_a_hand_off_says_how_long_it_waited_for_the_thread(
    tmp_path, held, span_name
):
    """The receiving thread is held 50 ms before it takes what it was
    handed: the span's ``waited_ms`` reads at least that."""
    _server, client, informers, sched = _stack()
    taken = getattr(sched, held)

    def late(*args):
        time.sleep(HOLD_S)
        taken(*args)

    setattr(sched, held, late)
    sched.start()
    try:
        with profiled(tmp_path) as events:
            _burst(client, sched, 20, tag="late")
    finally:
        sched.stop()
        informers.stop()
    spans = named(events, span_name)
    assert spans
    for ev in spans:
        assert ev["stats"]["waited_ms"] >= HOLD_S * 1e3


def test_an_ingest_frame_says_how_long_it_waited_for_the_informer(tmp_path):
    server = APIServer()
    client = Client(server)
    entered, release = threading.Event(), threading.Event()

    def on_batch(frame):
        if frame and not entered.is_set():  # the initial list is empty
            entered.set()
            release.wait(10)

    totals = flightrecorder.StageTotals()
    informer = Informer(server, "Pod")
    informer.add_event_handler(ResourceEventHandler(
        on_batch=on_batch, stage_totals=totals
    ))
    informer.start()
    try:
        with profiled(tmp_path) as events:
            client.create_pod(make_pod("held").obj())
            assert entered.wait(10)  # the informer's thread is in frame 1
            client.create_pods_bulk([make_pod(f"late-{i}").obj()
                                     for i in range(3)])
            time.sleep(HOLD_S)
            release.set()
            deadline = time.time() + 10
            while totals.calls().get("ingest", 0) < 2:
                assert time.time() < deadline
                time.sleep(0.01)
    finally:
        informer.stop()
    first, second = sorted(named(events, "sched/ingest"),
                           key=lambda ev: ev["start"])
    assert first["stats"]["events"] == 1 and second["stats"]["events"] == 3
    assert second["stats"]["waited_ms"] >= HOLD_S * 1e3
    assert 0 <= first["stats"]["waited_ms"] < second["stats"]["waited_ms"]


# -- nodes that change (PR 41) -------------------------------------------------


def _wait_for(predicate, what, seconds=30.0):
    deadline = time.time() + seconds
    while not predicate():
        assert time.time() < deadline, what
        time.sleep(0.005)


def test_every_node_handler_is_a_node_event_span_with_its_stats(tmp_path):
    """``sched/node_event``: one a handler call, inside its frame's
    ``sched/ingest`` on the Node informer's line, with ``kind``,
    ``spec_changed`` (whether the node-spec epoch moved) and ``woke``
    (pods the event moved out of the unschedulable map)."""
    server, client, informers, sched = _stack(num_nodes=4)
    sched.start()
    calls0 = sched.stage_totals.calls().get("node_event", 0)
    assert calls0 == 4  # set-up's four adds, with no session
    # a pod no node holds waits in the unschedulable map for an event
    client.create_pods_bulk([
        make_pod("huge").container(cpu="64", memory="16Mi").obj()])
    _wait_for(lambda: sched.queue.unschedulable_pods(),
              "the pod that fits nowhere was never parked")

    def cordon(node):
        node.spec.unschedulable = True

    try:
        with profiled(tmp_path) as events:
            _report_status(server, "node-1")  # conditions: wakes, no epoch
            _report_status(server, "node-1")  # the same again: nothing
            server.guaranteed_update("Node", "", "node-2", cordon)
            client.delete_node("node-3")
            client.create_node(
                make_node("node-3")
                .capacity(cpu="32", memory="64Gi", pods=110).obj())
            _wait_for(
                lambda: sched.stage_totals.calls()["node_event"] == calls0 + 5,
                "the node informer did not see the five writes")
    finally:
        sched.stop()
        informers.stop()
    spans = sorted(named(events, "sched/node_event"),
                   key=lambda ev: ev["start"])
    assert [own_stats(ev)["kind"] for ev in spans] == [
        "update", "update", "update", "delete", "add"]
    assert [own_stats(ev)["spec_changed"] for ev in spans] == [0, 0, 1, 1, 1]
    for ev in spans:
        assert set(own_stats(ev)) == {"kind", "spec_changed", "woke"}
        assert "cpu_ms" in ev["stats"]  # a clocked stage: it has a total
        (frame,) = [f for f in named(events, "sched/ingest")
                    if f["line"] == ev["line"]
                    and f["start"] <= ev["start"] and ev["end"] <= f["end"]]
        assert frame["stats"]["kind"] == "Node"
    # the first report changed the conditions and woke the parked pod;
    # the second changed nothing and moved nobody
    assert own_stats(spans[0])["woke"] == 1
    assert own_stats(spans[1])["woke"] == 0


def test_pack_says_whether_the_node_epoch_moved_and_the_solve_its_member_rows(
        tmp_path):
    """``node_epoch_moved`` on ``sched/pack``: this batch packed against
    another node-spec epoch than the last. ``member_rows`` on
    ``sched/solve_dispatch``, beside ``carry_rows``: the slots a node
    joined or left since the last batch, which ride the carry's scatter."""
    server, client, informers, sched = _stack(num_nodes=8)
    sched.start()

    def cordon(node):
        node.spec.unschedulable = True

    try:
        with profiled(tmp_path) as events:
            _burst(client, sched, 10, tag="a")  # the first batch: upload
            _burst(client, sched, 10, tag="b")  # nothing moved
            _report_status(server, "node-1")
            _wait_for(lambda: sched.stage_totals.calls()["node_event"] == 9,
                      "the status report was not seen")
            _burst(client, sched, 10, tag="c")  # a status report: nothing
            server.guaranteed_update("Node", "", "node-2", cordon)
            _wait_for(lambda: sched.stage_totals.calls()["node_event"] == 10,
                      "the cordon was not seen")
            _burst(client, sched, 10, tag="d")  # the epoch moved
            uploads = sched.state_uploads
            # a node no pod was bound to (the cordoned one) leaves, a batch
            # packs without it, and it joins again under its own name: its
            # slot is retired and claimed again. (With no batch between
            # the two the slot's name never leaves the tensor: a changed
            # row, no membership.)
            client.delete_node("node-2")
            _wait_for(lambda: sched.stage_totals.calls()["node_event"] == 11,
                      "the delete was not seen")
            _burst(client, sched, 10, tag="e")
            client.create_node(
                make_node("node-2")
                .capacity(cpu="32", memory="64Gi", pods=110).obj())
            _wait_for(lambda: sched.stage_totals.calls()["node_event"] == 12,
                      "the replacement was not seen")
            _burst(client, sched, 10, tag="f")
            sched._drain_pending()
    finally:
        sched.stop()
        informers.stop()
    packs = sorted(named(events, "sched/pack"), key=lambda ev: ev["start"])
    moved = [ev["stats"]["node_epoch_moved"] for ev in packs]
    assert len(packs) >= 6 and set(moved) <= {0, 1}
    # the first batch (no epoch packed before it), the cordon's, the
    # delete's and the join's; the status report's batch is not among them
    assert sum(moved) == 4, moved
    solves = sorted(named(events, "sched/solve_dispatch"),
                    key=lambda ev: ev["start"])
    members = [ev["stats"]["member_rows"] for ev in solves]
    assert sum(members) == 2 and max(members) == 1, members
    for ev in solves:
        if ev["stats"]["member_rows"]:
            assert ev["stats"]["carry"] == "scatter"
            assert ev["stats"]["carry_rows"] >= ev["stats"]["member_rows"]
    assert sched.state_uploads == uploads  # the scatter took it, no upload
    assert sched.membership_row_patches == 2


def test_pack_says_which_nodes_and_rows_moved_in_their_pods_alone(tmp_path):
    """``nodes_shared`` beside ``nodes_refreshed`` on
    ``sched/pack.snapshot``: the clones that hold the node object, the
    allocatable, the images and the volume limits of the NodeInfo they
    took the place of. ``rows_pods_only`` beside ``rows`` on
    ``sched/pack.state``: the rows whose requested columns alone were
    written. Binds move pods and nothing else; a node write makes that
    node's clone and row whole ones, and no other's."""
    server, client, informers, sched = _stack(num_nodes=8)
    sched.start()
    try:
        with profiled(tmp_path) as events:
            _burst(client, sched, 12, tag="a")  # the first pack: all whole
            _burst(client, sched, 12, tag="b")  # binds alone since
            _report_status(server, "node-1")
            _wait_for(lambda: sched.stage_totals.calls()["node_event"] == 9,
                      "the status report was not seen")
            _burst(client, sched, 12, tag="c")  # node-1 written, and binds
            _burst(client, sched, 12, tag="d")  # binds alone again
            sched._drain_pending()
    finally:
        sched.stop()
        informers.stop()
    refreshes = sorted(named(events, "sched/pack.snapshot"),
                       key=lambda ev: ev["start"])
    states = sorted(named(events, "sched/pack.state"),
                    key=lambda ev: ev["start"])
    assert len(refreshes) == len(states) >= 4
    for refresh, state in zip(refreshes, states):
        assert {"nodes_refreshed", "nodes_shared", "nodes"} <= set(
            refresh["stats"])
        assert own_stats(state).keys() == {"batch", "rows", "rows_pods_only"}
        assert refresh["stats"]["nodes_shared"] <= (
            refresh["stats"]["nodes_refreshed"])
        assert state["stats"]["rows_pods_only"] <= state["stats"]["rows"]
    # the first pack writes every row whole: no slot has held a node yet
    assert states[0]["stats"]["rows"] == 8
    assert states[0]["stats"]["rows_pods_only"] == 0
    whole_nodes = [ev["stats"]["nodes_refreshed"] - ev["stats"]["nodes_shared"]
                   for ev in refreshes[1:]]
    whole_rows = [ev["stats"]["rows"] - ev["stats"]["rows_pods_only"]
                  for ev in states[1:]]
    # exactly the written node, once, in the batch after the write
    assert sum(whole_nodes) == 1 and sum(whole_rows) == 1
    assert whole_nodes.index(1) == whole_rows.index(1)
    assert sum(ev["stats"]["rows"] for ev in states[1:]) > 1
    assert sum(ev["stats"]["nodes_refreshed"] for ev in refreshes[1:]) > 1


@pytest.mark.parametrize("image,live", [
    ("pause", 0),  # no node holds it
    ("registry.example/app:v2", 1),  # seven of the eight nodes do
], ids=["an_image_no_node_holds", "an_image_most_nodes_hold"])
def test_pack_families_says_whether_the_score_family_is_live(
        tmp_path, monkeypatch, image, live):
    """A rolled cluster at rehearsal size: seven of eight nodes have
    reported the image their kubelet pulled, 50 MiB. Pods that name
    another image find every ImageLocality score 0: ``score_live`` 0 on
    every ``sched/pack.families`` span, the basic layout, and the
    always-on counter counts ``live="false"``. Pods that name that image
    (43.75 MiB a holder: a score of 2) make the family live:
    ``score_live`` 1, every image list of the batch a live one, the
    constrained layout."""
    from kubernetes_tpu.api.types import ContainerImage
    from kubernetes_tpu.scheduler import batch as batch_module

    server, client, informers, sched = _stack(num_nodes=8)
    sched.queue.run()
    modes = []
    solve_packed = batch_module.solve_packed

    def spy(*args, **kwargs):
        modes.append(kwargs["mode"])
        return solve_packed(*args, **kwargs)

    monkeypatch.setattr(batch_module, "solve_packed", spy)

    def report_image(node):
        node.status.images = [ContainerImage(
            names=["registry.example/app:v2"], size_bytes=50 << 20)]

    for i in range(7):
        server.guaranteed_update("Node", "", f"node-{i}", report_image)
    _wait_for(lambda: sched.stage_totals.calls()["node_event"] == 15,
              "the image reports were not seen")
    counted = {flag: metrics.score_family_batches.value(live=flag)
               for flag in ("true", "false")}

    def batch(tag, lists):
        pods = []
        for i, images in enumerate(lists):
            w = make_pod(f"{tag}-{i}")
            for name in images:
                w.container(cpu="10m", memory="16Mi", image=name)
            pods.append(w.obj())
        client.create_pods_bulk(pods)
        _wait_for(lambda: len(sched.queue.pending_pods()) == len(pods),
                  "the pods never queued")
        assert sched.schedule_batch(timeout=1.0) == len(pods)
        sched.wait_for_inflight_binds()

    try:
        with profiled(tmp_path) as events:
            batch("a", [[image]] * 6)
            batch("b", [[image]] * 3 + [[image, "sidecar"]] * 3)
            sched._drain_pending()
    finally:
        sched.stop()
        informers.stop()
    assert sched.pods_fallback == 0
    first, second = sorted(named(events, "sched/pack.families"),
                           key=lambda ev: ev["start"])
    for ev, lists in ((first, 1), (second, 2)):
        assert ev["stats"]["score_live"] == live
        assert ev["stats"]["score_image_sigs"] == lists
        assert ev["stats"]["score_image_sigs_live"] == lists * live
    assert modes == ["constrained" if live else "greedy"] * 2
    flag, other = ("true", "false") if live else ("false", "true")
    assert metrics.score_family_batches.value(live=flag) == counted[flag] + 2
    assert metrics.score_family_batches.value(live=other) == counted[other]


def test_the_wait_for_mirrors_has_a_span_of_its_own(tmp_path):
    """A membership change with a batch in flight: the dispatcher waits,
    outside ``sched/pack``, until the batch has mirrored
    (``sched/mirror_wait``), then scatters the rows."""
    client, informers, sched, release = _held_stack()
    try:
        with profiled(tmp_path) as events:
            _dispatch(client, sched, _plain("first"))  # in flight, held
            calls = sched.stage_totals.calls()["node_event"]
            client.create_node(
                make_node("late")
                .capacity(cpu="32", memory="64Gi", pods=110).obj())
            _wait_for(
                lambda: sched.stage_totals.calls()["node_event"] == calls + 1,
                "the new node was not seen")
            _release_once_the_dispatcher_waits(sched, release)
            _dispatch(client, sched, _plain("second"))
            sched._drain_pending()
            sched.wait_for_inflight_binds()
    finally:
        sched.stop()
        informers.stop()
    (wait,) = named(events, "sched/mirror_wait")
    first, second = sorted(named(events, "sched/pack"),
                           key=lambda ev: ev["start"])
    assert wait["stats"]["batch"] == second["stats"]["batch"]
    assert wait["line"] == second["line"] and wait["start"] >= second["end"]
    assert sched.stage_totals.calls()["mirror_wait"] == 1
    (solve,) = [ev for ev in named(events, "sched/solve_dispatch")
                if ev["stats"]["batch"] == second["stats"]["batch"]]
    assert solve["stats"]["member_rows"] == 1


def test_a_pack_that_predates_a_commit_never_becomes_the_carry():
    """What ``rolling-upgrade-5000`` showed on the chip as re-joined nodes
    filled twice: a batch packs while another is in flight, the handshake
    has to replace the carry (more rows changed than the scatter takes),
    the dispatcher waits for the mirrors, and by the time it looks again
    the other batch has committed and nothing is pending. The pack's host
    arrays predate that commit, so uploading them would drop its pods from
    the resident state and the next batch would place around pods that
    are there. The dispatch has to start again from a fresh pack."""
    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.tensors import NodeTensorCache

    server, client, informers, sched = _stack(num_nodes=80, max_batch=128)
    sched.queue.run()
    release = threading.Event()
    release.set()
    complete = sched._complete_solve

    def held(p):
        release.wait(10)
        complete(p)

    sched._complete_solve = held
    # the dispatcher is slow to wake: by the time it looks again the
    # batch it waited for has committed whole
    await_mirrors = sched._await_mirrors

    def slow_to_wake(timeout=30.0):
        woke = await_mirrors(timeout)
        sched._drain_pending()
        return woke

    sched._await_mirrors = slow_to_wake

    def plain(tag, count):
        return [make_pod(f"{tag}-{i}").container(cpu="10m", memory="16Mi")
                .obj() for i in range(count)]

    def cached(count):
        _wait_for(lambda: sched.cache.pod_count() == count,
                  f"the cache never held {count} pods")

    try:
        # a carry, then more rows changed from outside than a scatter takes
        _dispatch(client, sched, plain("first", 80))
        sched._drain_pending()
        sched.wait_for_inflight_binds()
        cached(80)
        client.delete_pods_bulk([("default", f"first-{i}") for i in range(80)])
        cached(0)
        release.clear()
        _dispatch(client, sched, plain("a", 1))  # uploads; in flight, held
        client.create_pods_bulk([
            make_pod(f"there-{i}").node(f"node-{i}")
            .container(cpu="10m", memory="16Mi").obj() for i in range(80)])
        cached(80)
        _release_once_the_dispatcher_waits(sched, release)
        _dispatch(client, sched, plain("b", 1))
        sched._drain_pending()
        sched.wait_for_inflight_binds()
        cached(82)
    finally:
        release.set()
        sched.stop()
        informers.stop()
    # "b" was dispatched twice: the second time from a fresh pack
    routed = [sp["routed"] for sp in flightrecorder.RECORDER.dump()["spans"]]
    assert routed.count("drain_redispatch") == 1
    # the resident state's shadow holds every pod the cache holds, "a" too
    snap = Snapshot()
    sched.cache.update_snapshot(snap)
    truth = NodeTensorCache().update(snap)
    shadow = sched.device_state.req_shadow
    assert int(shadow.sum()) == int(truth.requested.sum())
    assert np.array_equal(np.sort(shadow.sum(axis=1)),
                          np.sort(truth.requested.sum(axis=1)))


# -- every millisecond of a dispatch has a name (PR 53) ----------------------

#: pack's children since PR 53 and how many spans of each a dispatch
#: without a drain shows: ``pack.cluster_terms`` is three reads, each up
#: to the drain it may ask for
NEW_PACK_CHILDREN = {"pack.aggregates": 1, "pack.cluster_terms": 3,
                     "pack.overlay": 1, "pack.order": 1}


def _inside(child, parent):
    return (child["line"] == parent["line"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


@pytest.mark.parametrize("child, a_dispatch",
                         sorted(NEW_PACK_CHILDREN.items()))
def test_packs_new_children_come_once_a_dispatch_inside_it(
    burst_trace, child, a_dispatch
):
    events, _dump, sched = burst_trace
    packs = named(events, "sched/pack")
    children = named(events, "sched/" + child)
    assert len(children) == a_dispatch * len(packs)
    for pack in packs:
        mine = [c for c in children
                if c["stats"]["batch"] == pack["stats"]["batch"]]
        assert len(mine) == a_dispatch
        assert all(_inside(c, pack) for c in mine)
        assert all("cpu_ms" in c["stats"] for c in mine)  # clocked: totals
    assert sched.stage_totals.calls()[child] == \
        a_dispatch * sched.stage_totals.calls()["pack"]


def test_packs_children_follow_one_another_and_say_what_they_saw(burst_trace):
    events, _dump, sched = burst_trace
    for pack in named(events, "sched/pack"):
        batch = pack["stats"]["batch"]
        mine = sorted(
            (ev for ev in events if ev["name"].startswith("sched/pack.")
             and ev["name"].count(".") == 1
             and ev["stats"].get("batch") == batch),
            key=lambda ev: ev["start"],
        )
        assert [ev["name"][len("sched/pack."):] for ev in mine] == [
            "aggregates", "snapshot", "cluster_terms", "cluster_terms",
            "cluster_terms", "state", "pods", "masks", "overlay", "order",
            "families",
        ]
        for before, after in zip(mine, mine[1:]):  # siblings, never nested
            assert before["end"] <= after["start"]
        by_name = {ev["name"]: ev for ev in mine}
        assert own_stats(by_name["sched/pack.aggregates"]) == {
            "batch": batch, "nominees": 0}
        overlay = own_stats(by_name["sched/pack.overlay"])
        assert overlay["overlaid"] == 0 and overlay["inflight_pods"] >= 0
        (dispatch,) = [d for d in named(events, "sched/dispatch")
                       if d["stats"]["batch"] == batch]
        assert own_stats(by_name["sched/pack.order"]) == {
            "batch": batch, "padded": dispatch["stats"]["padded"],
            "inactive": 0}
    # no gang member in the run: the sweep found none and opened no span
    assert not named(events, "sched/gang_siblings")
    assert "gang_siblings" not in sched.stage_totals.calls()


def test_the_new_totals_count_dispatches_without_a_session():
    server, client, informers, sched = _stack(num_nodes=4)
    sched.start()
    try:
        _burst(client, sched, 30, tag="off")
    finally:
        sched.stop()
        informers.stop()
    calls = sched.stage_totals.calls()
    assert calls["pack"] >= 1
    for child, a_dispatch in NEW_PACK_CHILDREN.items():
        assert calls[child] == a_dispatch * calls["pack"], child
    seconds = sched.stage_seconds
    assert sum(seconds[c] for c in NEW_PACK_CHILDREN) <= seconds["pack"]
    assert not {"gang_siblings", "gang_fixup.members",
                "gang_fixup.verdict"} & set(calls)


def test_a_nominee_and_a_batch_in_flight_show_on_packs_children(tmp_path):
    """A pod nominated onto a node and outside the batch is counted by
    ``pack.aggregates`` and overlaid by ``pack.overlay``, which also says
    how many uids it took from the batches in flight: those behind the
    one the committer holds, whose own nominations stand."""
    client, informers, sched, release = _held_stack(max_inflight=3)
    nominee = make_pod("nominee").container(cpu="10m", memory="16Mi").obj()
    try:
        with profiled(tmp_path) as events:
            _dispatch(client, sched, _plain("first"))  # committing, held
            _wait_for(lambda: sched._pending_q[0].get("committing"),
                      "the committer never took the first batch")
            _dispatch(client, sched, _plain("second", 3))  # behind it
            sched.queue.update_nominated_pod_for_node(nominee, "node-0")
            _release_once_the_dispatcher_waits(sched, release)
            # overlaid with batches in flight: the handshake lands them
            # and the dispatch starts again from a fresh pack
            _dispatch(client, sched, _plain("third", 2))
            sched._drain_pending()
            sched.wait_for_inflight_binds()
    finally:
        release.set()
        sched.stop()
        informers.stop()
    by_start = lambda ev: ev["start"]
    aggregates = sorted(named(events, "sched/pack.aggregates"), key=by_start)
    overlays = sorted(named(events, "sched/pack.overlay"), key=by_start)
    assert [ev["stats"]["nominees"] for ev in aggregates] == [0, 0, 1, 1]
    assert [(ev["stats"]["inflight_pods"], ev["stats"]["overlaid"])
            for ev in overlays] == [(0, 0), (0, 0), (3, 1), (0, 1)]
    routed = [sp["routed"] for sp in flightrecorder.RECORDER.dump()["spans"]]
    assert routed.count("drain_redispatch") == 1


def _gang_stack(nodes, cpu):
    from kubernetes_tpu.api.types import ObjectMeta, PodGroup

    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=32)
    for i in range(nodes):
        client.create_node(
            make_node(f"n{i}").capacity(cpu=cpu, memory="8Gi").obj())
    client.create_pod_group(PodGroup(
        metadata=ObjectMeta(name="g8", namespace="default"), min_member=8))
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    return client, informers, sched


def _gang_pods(count=8):
    from kubernetes_tpu.api.types import POD_GROUP_LABEL

    pods = [make_pod(f"g{i}").container(cpu="1", memory="128Mi").obj()
            for i in range(count)]
    for pod in pods:
        pod.metadata.labels[POD_GROUP_LABEL] = "g8"
    return pods


def test_a_gang_batch_shows_its_siblings_members_and_verdict(tmp_path):
    """A gang of 8 that does not fit beside 4 plain pods that do: the
    first pass fails the gang, the second solves with its 8 slots masked
    (``inactive`` on that dispatch's ``pack.order``), and the verdict
    rejects the one group."""
    client, informers, sched = _gang_stack(nodes=1, cpu="4")
    pods = _gang_pods() + [
        make_pod(f"plain{i}").container(cpu="1", memory="128Mi").obj()
        for i in range(4)
    ]
    try:
        with profiled(tmp_path) as events:
            client.create_pods_bulk(pods)
            _wait_for(lambda: len(sched.queue.pending_pods()) == 12,
                      "the pods never queued")
            assert sched.schedule_batch(timeout=1.0) == 12
            sched.wait_for_inflight_binds()
    finally:
        sched.stop()
        informers.stop()
    (fixup,) = named(events, "sched/gang_fixup")
    assert fixup["stats"]["passes"] == 2
    (siblings,) = named(events, "sched/gang_siblings")
    assert own_stats(siblings) == {"groups": 1, "took": 0}
    assert siblings["line"] == fixup["line"]
    assert siblings["end"] <= fixup["start"]  # before any dispatch
    # the member index once, and the masked uids once a pass
    members = named(events, "sched/gang_fixup.members")
    assert len(members) == 1 + fixup["stats"]["passes"]
    (verdict,) = named(events, "sched/gang_fixup.verdict")
    assert own_stats(verdict) == {
        "rejected_groups": fixup["stats"]["masked_groups"]}
    assert verdict["stats"]["rejected_groups"] == 1
    for child in members + [verdict]:
        assert _inside(child, fixup) and "cpu_ms" in child["stats"]
    downloads = named(events, "sched/gang_fixup.download")
    assert len(downloads) == fixup["stats"]["passes"]
    # nothing of the fix-up's own is inside one of its dispatches
    dispatches = [d for d in named(events, "sched/dispatch")
                  if _inside(d, fixup)]
    assert len(dispatches) == 2
    for child in members + [verdict] + downloads:
        assert not any(_inside(child, d) for d in dispatches)
    first, second = sorted(named(events, "sched/pack.order"),
                           key=lambda ev: ev["start"])
    assert first["stats"]["inactive"] == 0
    assert second["stats"]["inactive"] == 8
    calls = sched.stage_totals.calls()
    assert calls["gang_siblings"] == 1 and calls["gang_fixup.verdict"] == 1
    assert calls["gang_fixup.members"] == 3
    assert calls["pack.order"] == calls["pack"] == 2


def test_gang_siblings_says_how_many_members_it_took_from_the_queue(tmp_path):
    """A pop that holds two of a gang's eight members takes the other
    six with it: the span's ``took``."""
    client, informers, sched = _gang_stack(nodes=2, cpu="4")
    try:
        client.create_pods_bulk(_gang_pods())
        _wait_for(lambda: len(sched.queue.pending_pods()) == 8,
                  "the gang never queued")
        popped = sched.queue.pop_batch(2, timeout=1.0)
        assert len(popped) == 2
        with profiled(tmp_path) as events:
            whole = sched._with_gang_siblings(popped, 32)
    finally:
        sched.stop()
        informers.stop()
    assert len(whole) == 8
    (siblings,) = named(events, "sched/gang_siblings")
    assert own_stats(siblings) == {"groups": 1, "took": 6}
    assert sched.stage_totals.calls()["gang_siblings"] == 1


@pytest.mark.parametrize("child", ["dispatch.begin", "dispatch.handshake",
                                   "dispatch.landed"])
def test_dispatchs_own_children_are_trace_only_once_a_dispatch(
    burst_trace, child
):
    """What ``sched/dispatch`` does outside pack and the solve's call:
    spans with no totals, as ``dispatch`` itself is, so never clocked."""
    events, _dump, sched = burst_trace
    dispatches = named(events, "sched/dispatch")
    children = named(events, "sched/" + child)
    assert len(children) == len(dispatches) >= 2
    for dispatch in dispatches:
        (mine,) = [c for c in children
                   if c["stats"]["batch"] == dispatch["stats"]["batch"]]
        assert _inside(mine, dispatch)
        assert own_stats(mine) == mine["stats"] == {
            "batch": dispatch["stats"]["batch"]}
        (pack,) = [p for p in named(events, "sched/pack")
                   if p["stats"]["batch"] == dispatch["stats"]["batch"]]
        (solve,) = [s for s in named(events, "sched/solve_dispatch")
                    if s["stats"]["batch"] == dispatch["stats"]["batch"]]
        if child == "dispatch.begin":
            assert mine["end"] <= pack["start"]
        elif child == "dispatch.handshake":
            assert pack["end"] <= mine["start"]
            assert mine["end"] <= solve["start"]
        else:
            assert solve["end"] <= mine["start"]
    assert child not in sched.stage_totals.calls()
