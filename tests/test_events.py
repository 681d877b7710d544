"""API event recorder: Scheduled / FailedScheduling / Preempted Event
objects stored and listable via the apiserver (reference profile.go:39
Recorder; scheduler.go:378, :544).
"""

import time

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod


def _cluster(max_batch=16):
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=max_batch)
    return server, client, informers, sched


def _events_by_reason(client, reason):
    events, _ = client.list_events()
    return [e for e in events if e.reason == reason]


def test_scheduled_and_failed_events():
    server, client, informers, sched = _cluster()
    client.create_node(make_node("n").capacity(cpu="2", memory="4Gi").obj())
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    client.create_pod(make_pod("fits").container(cpu="1").obj())
    client.create_pod(make_pod("toobig").container(cpu="64").obj())
    sched.start()
    deadline = time.time() + 15
    while time.time() < deadline:
        sched.event_broadcaster.flush()
        if _events_by_reason(client, "Scheduled") and _events_by_reason(
            client, "FailedScheduling"
        ):
            break
        time.sleep(0.05)
    sched.stop()
    informers.stop()

    scheduled = _events_by_reason(client, "Scheduled")
    assert scheduled, "no Scheduled event recorded"
    ev = scheduled[0]
    assert ev.involved_object.name == "fits"
    assert ev.type == "Normal"
    assert ev.source == "default-scheduler"
    assert "Successfully assigned default/fits to n" in ev.message

    failed = _events_by_reason(client, "FailedScheduling")
    assert failed, "no FailedScheduling event recorded"
    assert failed[0].involved_object.name == "toobig"
    assert failed[0].type == "Warning"


def test_failed_scheduling_aggregates_count():
    server, client, informers, sched = _cluster()
    client.create_node(make_node("n").capacity(cpu="1", memory="1Gi").obj())
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    client.create_pod(make_pod("big").container(cpu="64").obj())
    sched.start()
    deadline = time.time() + 20
    count = 0
    while time.time() < deadline:
        # repeated retries (backoff flush) re-fail the same pod
        sched.queue.move_all_to_active_or_backoff_queue("test")
        sched.event_broadcaster.flush()
        failed = _events_by_reason(client, "FailedScheduling")
        if failed and failed[0].count >= 2:
            count = failed[0].count
            break
        time.sleep(0.1)
    sched.stop()
    informers.stop()
    assert count >= 2
    # aggregation: repeats bumped count instead of new objects
    assert len(_events_by_reason(client, "FailedScheduling")) == 1


def test_preempted_event_on_victim():
    server, client, informers, sched = _cluster()
    client.create_node(make_node("n").capacity(cpu="2", memory="4Gi").obj())
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    client.create_pod(
        make_pod("victim").container(cpu="2").priority(0).obj()
    )
    sched.start()
    deadline = time.time() + 15
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if any(p.spec.node_name for p in pods):
            break
        time.sleep(0.05)
    client.create_pod(
        make_pod("high").container(cpu="2").priority(100).obj()
    )
    deadline = time.time() + 20
    while time.time() < deadline:
        sched.event_broadcaster.flush()
        if _events_by_reason(client, "Preempted"):
            break
        time.sleep(0.05)
    sched.stop()
    informers.stop()
    preempted = _events_by_reason(client, "Preempted")
    assert preempted, "no Preempted event recorded"
    assert preempted[0].involved_object.name == "victim"
    assert "Preempted by default/high on node n" in preempted[0].message


# -- a frame's Scheduled events, built a run at a time ------------------------
#
# ``EventBroadcaster._emit_batch`` hands the items ``scheduled_many``
# enqueued to one native call (``scheduled_events`` in
# native/_hotpath.c); ``_emit_loop`` takes every other item and is the
# twin where the extension did not build (KTPU_NATIVE_INGEST=0 here).

import dataclasses

import pytest

from kubernetes_tpu import native
from kubernetes_tpu.api.types import Event, ObjectMeta, ObjectReference
from kubernetes_tpu.utils import metrics
from kubernetes_tpu.utils.event_recorder import (
    _EVENT_SHAPE,
    EventBroadcaster,
)

needs_native = pytest.mark.skipif(
    native.hotpath is None, reason="native module unavailable"
)


def _bound_pods(n, namespaces=("default", "team-a", "team-b")):
    pods = []
    for i in range(n):
        pod = make_pod(f"web-{i}", namespace=namespaces[i % len(namespaces)])
        pod = pod.container(cpu="10m").obj()
        pod.spec.node_name = f"node-{i % 7}"
        pods.append(pod)
    return pods


def _scheduled(pods, source="default-scheduler"):
    # what EventRecorder.scheduled_many enqueues
    return [(source, pod, "Normal", "Scheduled", None) for pod in pods]


def _emit(frames, path, monkeypatch, seq=0):
    """The frames through a broadcaster of their own, on one path; the
    stored events in the store's order, and the broadcaster."""
    if path == "twin":
        monkeypatch.setenv("KTPU_NATIVE_INGEST", "0")
    else:
        monkeypatch.delenv("KTPU_NATIVE_INGEST", raising=False)
    server = APIServer()
    broadcaster = EventBroadcaster(server)
    broadcaster.stop()  # no thread: the frames are emitted by hand
    broadcaster._seq = seq
    for frame in frames:
        broadcaster._emit_batch(list(frame))
    return server.list("Event")[0], broadcaster


def _fields(obj, skip=()):
    return {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)
            if f.name not in skip}


@needs_native
def test_a_frame_of_scheduled_items_equals_the_twins_field_for_field(
    monkeypatch,
):
    pods = _bound_pods(300)
    frames = [_scheduled(pods[:40]), _scheduled(pods[40:])]
    built, b_native = _emit(frames, "native", monkeypatch, seq=250)
    looped, b_twin = _emit(frames, "twin", monkeypatch, seq=250)
    assert len(built) == len(looped) == 300
    assert b_native._seq == b_twin._seq == 550
    assert b_native._aggregate == b_twin._aggregate
    for pod, ev, twin in zip(pods, built, looped):
        assert type(ev) is Event and type(ev.metadata) is ObjectMeta
        assert type(ev.involved_object) is ObjectReference
        # the instance holds the dataclass's fields, in its order, only
        for a, b in ((ev, twin), (ev.metadata, twin.metadata),
                     (ev.involved_object, twin.involved_object)):
            assert list(vars(a)) == list(vars(b))
            assert list(vars(a)) == [f.name for f in dataclasses.fields(a)]
        stamps = ("creation_timestamp", "resource_version")
        assert _fields(ev.metadata, stamps) == _fields(twin.metadata, stamps)
        assert ev.involved_object == twin.involved_object
        assert (_fields(ev, ("metadata", "first_timestamp"))
                == _fields(twin, ("metadata", "first_timestamp")))
        for a, b in zip(_fields(ev).values(), _fields(twin).values()):
            assert type(a) is type(b)
        # the one departure: the frame's clock, not a clock read an event
        assert ev.metadata.creation_timestamp == ev.first_timestamp
        assert twin.metadata.creation_timestamp == twin.first_timestamp
        # what today's loop gives, spelled out once
        assert ev.metadata.name.startswith(pod.metadata.name + ".")
        assert ev.metadata.namespace == pod.metadata.namespace
        assert ev.metadata.uid == "" and ev.metadata.deletion_timestamp is None
        assert ev.involved_object.uid == pod.metadata.uid
        assert ev.involved_object.kind == "Pod"
        assert ev.message == (
            f"Successfully assigned {pod.metadata.namespace}/"
            f"{pod.metadata.name} to {pod.spec.node_name}"
        )
        assert (ev.reason, ev.type, ev.source, ev.count, ev.kind) == (
            "Scheduled", "Normal", "default-scheduler", 1, "Event")
    assert [ev.metadata.name for ev in built] == [
        f"{pod.metadata.name}.{250 + k:x}" for k, pod in enumerate(pods, 1)
    ]
    # a frame has one clock; two frames have two
    assert len({ev.first_timestamp for ev in built}) == 2
    # nothing is shared between two events' metadata
    for attr in ("labels", "annotations", "owner_references"):
        assert len({id(getattr(ev.metadata, attr)) for ev in built}) == 300
        assert all(getattr(ev.metadata, attr) == type(
            getattr(ObjectMeta(), attr))() for ev in built)
    built[0].metadata.labels["k"] = "v"
    assert built[1].metadata.labels == {}


@needs_native
def test_a_built_event_is_the_collectors_as_the_twins_is(monkeypatch):
    """The batch build goes through ``__new__`` and one attribute store
    a field, so an event is laid out as the one ``__init__`` makes: its
    fields in the instance and no ``__dict__`` object beside it, the
    same parts tracked by the cyclic collector on both paths, the same
    number of tracked objects an event, and freed by its reference
    counts with its parts."""
    import gc
    import sys
    import weakref

    def parts(ev):
        return (ev, ev.metadata, ev.involved_object, ev.metadata.labels,
                ev.metadata.annotations, ev.metadata.owner_references)

    class Keep:
        def __init__(self):
            self.events = []

        def create_bulk(self, objs):
            self.events.extend(objs)

    grown = {}
    stored = {}
    for path in ("native", "twin"):
        monkeypatch.setenv("KTPU_NATIVE_INGEST", "0" if path == "twin" else "1")
        broadcaster = EventBroadcaster(Keep())
        broadcaster.stop()
        broadcaster._emit_batch(_scheduled(_bound_pods(2, ("warm",))))
        frame = _scheduled(_bound_pods(64))
        gc.collect()
        gc.disable()
        try:
            before = len(gc.get_objects())
            broadcaster._emit_batch(frame)
            grown[path] = len(gc.get_objects()) - before
        finally:
            gc.enable()
        stored[path] = broadcaster._server.events[2:]
    # an event, its metadata, its reference, its owner_references, and
    # the aggregate's key and value: nothing more an event on either path
    assert grown["native"] // 64 == 6
    assert grown["native"] == grown["twin"]
    for ev, twin in zip(stored["native"], stored["twin"]):
        assert [gc.is_tracked(p) for p in parts(ev)] \
            == [gc.is_tracked(p) for p in parts(twin)]
        assert [sys.getsizeof(p) for p in parts(ev)] \
            == [sys.getsizeof(p) for p in parts(twin)]
        # the fields are the instance's own referents: no dict between
        for a, b in zip(parts(ev)[:3], parts(twin)[:3]):
            assert [type(r) for r in gc.get_referents(a)] \
                == [type(r) for r in gc.get_referents(b)]
            assert dict not in [type(r) for r in gc.get_referents(a)][:1]
    fresh = []
    native.hotpath.scheduled_events(
        _scheduled(_bound_pods(3)), 0, 0, 1.5, {}, fresh, _EVENT_SHAPE,
    )
    refs = [weakref.ref(fresh[1]), weakref.ref(fresh[1].metadata),
            weakref.ref(fresh[1].involved_object)]
    gc.disable()
    try:
        del fresh
        assert [ref() for ref in refs] == [None, None, None]
    finally:
        gc.enable()


def test_the_batch_build_is_given_the_types_own_fields():
    """The native build sets values by position under the names the
    dataclasses give, and runs no ``__init__``: a type that gains a
    field, or a ``__post_init__``, has to change the build with it."""
    shape = dict(_EVENT_SHAPE)
    assert list(shape) == [Event, ObjectMeta, ObjectReference]
    assert shape[Event] == (
        "metadata", "involved_object", "reason", "message", "type",
        "source", "count", "first_timestamp", "kind")
    assert shape[ObjectMeta] == (
        "name", "namespace", "uid", "labels", "annotations",
        "resource_version", "creation_timestamp", "owner_references",
        "deletion_timestamp")
    assert shape[ObjectReference] == ("kind", "namespace", "name", "uid")
    for tp in shape:
        assert not hasattr(tp, "__post_init__")
        assert not hasattr(tp, "__slots__")
    if native.hotpath is not None:
        short = tuple((tp, names[:-1]) for tp, names in _EVENT_SHAPE)
        with pytest.raises(ValueError):
            native.hotpath.scheduled_events(
                _scheduled(_bound_pods(1)), 0, 0, 1.5, {}, [], short)


@pytest.mark.parametrize("path", ["native", "twin"])
def test_a_mixed_frame_stores_its_events_in_frame_order(path, monkeypatch):
    pods = _bound_pods(12)
    frame = _scheduled(pods)
    frame.insert(0, ("default-scheduler", pods[3], "Warning",
                     "FailedScheduling", "0/5 nodes are available"))
    frame.insert(5, ("default-scheduler", pods[0], "Normal", "Preempted",
                     "Preempted by default/high on node node-0"))
    frame.insert(6, ("other-scheduler", pods[1], "Warning",
                     "FailedScheduling", "0/5 nodes are available"))
    frame.append(("default-scheduler", pods[2], "Normal", "Scheduled",
                  "a message of eventf's own"))
    stored, broadcaster = _emit([frame], path, monkeypatch)
    assert [(ev.reason, ev.involved_object.name, ev.source) for ev in stored] \
        == [(reason, pod.metadata.name, source)
            for source, pod, _type, reason, _message in frame]
    assert [ev.metadata.name.rsplit(".", 1)[1] for ev in stored] == [
        f"{k:x}" for k in range(1, 17)
    ]
    versions = [ev.metadata.resource_version for ev in stored]
    assert versions == sorted(versions) and len(set(versions)) == 16
    assert stored[-1].message == "a message of eventf's own"
    assert stored[0].type == "Warning" and stored[0].count == 1


@pytest.mark.parametrize("path", ["native", "twin"])
def test_a_repeat_bumps_the_stored_events_count(path, monkeypatch):
    pods = _bound_pods(6)
    first = _scheduled(pods)
    # the same pods on the same nodes again, among two that are new, and
    # one of them a third time
    again = _scheduled([pods[1]]) + _scheduled(_bound_pods(2, ("x",))) \
        + _scheduled([pods[4]])
    stored, broadcaster = _emit(
        [first, again, _scheduled([pods[4]])], path, monkeypatch
    )
    assert len(stored) == 8  # no second object for a repeat
    counts = {ev.involved_object.uid: ev.count for ev in stored}
    assert [counts[p.metadata.uid] for p in pods] == [1, 2, 1, 1, 3, 1]
    assert broadcaster._seq == 8
    # a stored event that left the store is written afresh
    server = broadcaster._server
    gone = stored[0]
    server.delete("Event", gone.metadata.namespace, gone.metadata.name)
    broadcaster._emit_batch(_scheduled([pods[0]]))
    names = [ev.metadata.name for ev in server.list("Event")[0]]
    assert gone.metadata.name not in names
    assert f"{pods[0].metadata.name}.9" in names


@needs_native
def test_what_the_batch_build_is_not_given_it_leaves_to_the_loop():
    build = native.hotpath.scheduled_events
    types = _EVENT_SHAPE
    pods = _bound_pods(4)
    items = _scheduled(pods)
    items[2] = items[2][:4] + ("said",)  # a message: eventf's item
    aggregate, fresh = {}, []
    assert build(items, 0, 0, 1.5, aggregate, fresh, types) == 2
    assert build(items, 2, 2, 1.5, aggregate, fresh, types) == 2
    assert build(items, 3, 2, 1.5, aggregate, fresh, types) == 4
    assert build(items, 4, 3, 1.5, aggregate, fresh, types) == 4
    assert [ev.metadata.name for ev in fresh] == [
        "web-0.1", "web-1.2", "web-3.3"]
    assert build(items, 0, 3, 1.5, aggregate, fresh, types) == 0  # a repeat
    assert len(fresh) == 3 and len(aggregate) == 3
    # a pod not yet on a node says so, as the f-string would
    pods[0].spec.node_name = None
    assert build(_scheduled(pods[:1]), 0, 9, 1.5, {}, fresh, types) == 1
    assert fresh[-1].message == "Successfully assigned default/web-0 to None"
    assert fresh[-1].metadata.name == "web-0.a"
    # an object without the fields raises what the loop raises
    with pytest.raises(AttributeError):
        build([("s", object(), "Normal", "Scheduled", None)], 0, 0, 1.5,
              {}, [], types)
    with pytest.raises(TypeError):
        build(items, 0, 0, 1.5, {}, [], (Event, ObjectMeta, 3))


def test_the_twin_is_the_configured_path_or_a_counted_fallback(monkeypatch):
    counter = metrics.ingest_native_fallbacks
    before = counter.value(site="scheduled-events")
    frames = [_scheduled(_bound_pods(5)), _scheduled(_bound_pods(3, ("y",)))]
    # KTPU_NATIVE_INGEST=0 asks for the loop: nothing is booked
    stored, _ = _emit(frames, "twin", monkeypatch)
    assert len(stored) == 8
    assert counter.value(site="scheduled-events") == before
    # native wanted and absent (a failed build): the loop, counted a frame
    monkeypatch.setitem(native._INGEST_FNS, "scheduled_events", None)
    stored, _ = _emit(frames, "native", monkeypatch)
    assert len(stored) == 8
    assert counter.value(site="scheduled-events") == before + 2


def test_every_bound_pod_of_a_burst_leaves_one_scheduled_event():
    server, client, informers, sched = _cluster(max_batch=64)
    for i in range(8):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="32", memory="64Gi", pods=110)
            .obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.start()
    client.create_pods_bulk([
        make_pod(f"b-{i}").container(cpu="10m", memory="16Mi").obj()
        for i in range(200)
    ])
    deadline = time.time() + 60
    scheduled = []
    while time.time() < deadline and len(scheduled) < 200:
        sched.event_broadcaster.flush()
        scheduled = _events_by_reason(client, "Scheduled")
        time.sleep(0.05)
    sched.stop()
    informers.stop()
    pods, _ = client.list_pods()
    assert len(scheduled) == 200
    by_uid = {ev.involved_object.uid: ev for ev in scheduled}
    assert set(by_uid) == {p.metadata.uid for p in pods}
    for pod in pods:
        ev = by_uid[pod.metadata.uid]
        assert ev.count == 1 and ev.source == "default-scheduler"
        assert ev.message == (
            f"Successfully assigned default/{pod.metadata.name} "
            f"to {pod.spec.node_name}"
        )
    # the frames were stages of the scheduler's, and the fold ran native
    assert sched.stage_totals.calls()["events"] >= 1
    assert sched.event_broadcaster.stage_totals is sched.stage_totals
