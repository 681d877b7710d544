"""A preemption wave's failure records as one hand-back
(``Scheduler.record_scheduling_failures``, ``PriorityQueue
.add_unschedulable_many``, ``APIServer.update_pod_status_bulk``), held to
N calls of the per-pod ``record_scheduling_failure`` on a twin cluster:
the same queue membership and nominations, the same conditions and
``nominatedNodeName`` on the stored pods, the same number of events; and
what is particular to the bulk: one queue wakeup, one status transaction
whose echoes reach a watcher as one frame, a missing pod as a per-slot
error, a failed transaction that leaves the pods requeued."""

import threading
import time

import pytest

from kubernetes_tpu.api.types import Binding
from kubernetes_tpu.apiserver.server import MODIFIED, APIServer, NotFound
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.queue import events
from kubernetes_tpu.robustness.faults import (
    FaultInjector,
    FaultPoint,
    FaultProfile,
    PointConfig,
    install_injector,
)
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod
from test_stage_spans import named, profiled

N = 16
DELETED, BOUND, REQUEUED = "p1", "p2", "p5"


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    install_injector(None)


def _wait(cond, what, timeout=10.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, f"timed out: {what}"
        time.sleep(0.005)


class _Twin:
    """A scheduler that is not running, N pending pods popped into the
    test's hands as the dispatcher would hold them, and beside them the
    three records a wave must not requeue blindly: a pod deleted
    meanwhile, a pod bound meanwhile and a pod a real update re-added."""

    def __init__(self):
        self.server = APIServer()
        self.client = Client(self.server)
        self.informers = InformerFactory(self.server)
        self.sched = new_scheduler(
            self.client, self.informers, batch=True, max_batch=256
        )
        self.client.create_node(
            make_node("n0").capacity(cpu="64", memory="64Gi", pods=110).obj()
        )
        self.informers.start()
        self.informers.wait_for_cache_sync()
        self.names = [f"p{i}" for i in range(N)]
        self.client.create_pods_bulk([
            make_pod(nm).container(cpu="1", memory="128Mi")
            .priority(100 - i % 3).obj()
            for i, nm in enumerate(self.names)
        ])
        queue = self.sched.queue
        _wait(lambda: queue.num_pending()["active"] == N, "pods queued")
        self.infos = queue.pop_batch(N, timeout=5)
        assert len(self.infos) == N
        by_name = {pi.pod.metadata.name: pi for pi in self.infos}
        # half of the records will find a move request made during their
        # attempt (backoffQ), half will not (unschedulableQ)
        queue.move_all_to_active_or_backoff_queue(events.NodeAdd)
        self.cycle = queue.scheduling_cycle
        pods = self.informers.pods()
        self.client.delete_pod("default", DELETED)
        _wait(lambda: pods.get("default", DELETED) is None, "delete seen")
        bound = by_name[BOUND].pod
        self.client.bind(Binding(
            pod_namespace="default", pod_name=BOUND,
            pod_uid=bound.metadata.uid, target_node="n0",
        ))
        _wait(
            lambda: self.sched.cache.has_pod_uid(bound.metadata.uid),
            "bind seen",
        )
        queue.add(by_name[REQUEUED].pod)
        self.prof = next(iter(self.sched.profiles.values()))

    def close(self):
        self.sched.stop()
        self.informers.stop()

    def parked(self):
        """The wave's parked failures in the order the flush sorts them."""
        items = [
            (
                self.prof, pi,
                Exception(f"0/1 nodes are available for {pi.pod.key()}"),
                self.cycle + i % 2,
            )
            for i, pi in enumerate(self.infos)
        ]
        items.sort(key=lambda t: (-t[1].pod.spec.priority, t[1].timestamp))
        return items

    @staticmethod
    def nominated(pod):
        # every fourth preemptor found no node
        return "" if int(pod.metadata.name[1:]) % 4 == 3 else "n0"

    def per_pod(self, evict_ok):
        """The requeue loop as it stood: one record a pod."""
        for prof, pi, fe, cycle in self.parked():
            if self.sched.cache.has_pod_uid(pi.pod.metadata.uid):
                continue
            node = self.nominated(pi.pod)
            self.sched.record_scheduling_failure(
                prof, pi, str(fe), "Unschedulable", node, cycle,
                skip_backoff=bool(node) and evict_ok,
            )

    def wave(self, evict_ok):
        """The wave's own flush, the search stubbed out."""
        def preempt_batch(prof, items):
            return (
                [self.nominated(pod) for pod, _fe in items],
                [] if evict_ok else None,
            )

        self.sched.preemptor.preempt_batch = preempt_batch
        self.sched._deferred_preempt = self.parked()
        self.sched._flush_deferred_preemptions()

    def state(self):
        """What the differential compares, by pod name."""
        queue = self.sched.queue
        uid_name = {
            pi.pod.metadata.uid: pi.pod.metadata.name for pi in self.infos
        }
        places, stored = {}, {}
        for nm in self.names:
            key = f"default/{nm}"
            places[nm] = (
                "active" if key in queue.active_q
                else "backoff" if key in queue.pod_backoff_q
                else "unschedulable" if key in queue.unschedulable_q
                else "none"
            )
            try:
                pod = self.client.get_pod("default", nm)
            except NotFound:
                stored[nm] = None
                continue
            stored[nm] = (
                [
                    (c.type, c.status, c.reason, c.message)
                    for c in pod.status.conditions
                ],
                pod.status.nominated_node_name,
                pod.spec.node_name,
            )
        nominations = {
            uid_name[uid]: node
            for uid, node in
            queue.nominated_pods.nominated_pod_to_node.items()
        }
        return places, nominations, stored

    def failed_scheduling_events(self):
        evs, _ = self.client.list_events()
        return [e for e in evs if e.reason == "FailedScheduling"]


@pytest.mark.parametrize("evict_ok", [True, False])
def test_the_bulk_record_equals_n_per_pod_records(evict_ok):
    a, b = _Twin(), _Twin()
    try:
        a.per_pod(evict_ok)
        b.wave(evict_ok)
        live = N - 2  # the deleted pod and the bound pod take no record
        for twin in (a, b):
            _wait(
                lambda: len(twin.failed_scheduling_events()) >= live,
                "events emitted",
            )
            # the status echoes have come back through the informer
            pods = twin.informers.pods()
            _wait(
                lambda: all(
                    pods.get("default", nm).status.conditions
                    for nm in twin.names if nm not in (DELETED, BOUND)
                ),
                "echoes ingested",
            )
        places, nominations, stored = b.state()
        assert (places, nominations, stored) == a.state()
        assert len(b.failed_scheduling_events()) == live
        assert len(a.failed_scheduling_events()) == live

        # and the state is the one the per-pod contract describes
        assert stored[DELETED] is None and places[DELETED] == "none"
        assert stored[BOUND] == ([], "", "n0") and places[BOUND] == "none"
        assert places[REQUEUED] == "active"  # as the real update left it
        for i, nm in enumerate(b.names):
            if nm in (DELETED, BOUND):
                assert nm not in nominations
                continue
            node = "" if i % 4 == 3 else "n0"
            (cond,) = stored[nm][0]
            assert cond[:3] == ("PodScheduled", "False", "Unschedulable")
            assert cond[3] == f"0/1 nodes are available for default/{nm}"
            assert stored[nm][1] == node
            assert nominations.get(nm, "") == node
            if nm == REQUEUED:
                continue
            if node and evict_ok:
                # each pod's own skip_backoff: straight to the activeQ
                assert places[nm] == "active"
            else:
                assert places[nm] in ("backoff", "unschedulable")
        assert {"backoff", "unschedulable"} <= set(places.values())
        # the echoes of pods the queue holds moved nothing and were not
        # "ignored": that counter is for pods the queue does not hold
        assert b.sched.queue.echoes_ignored == 0
        assert b.sched.stage_totals.calls()["preempt_requeue"] == 1
    finally:
        a.close()
        b.close()


def test_the_requeue_span_says_what_the_hand_back_did(tmp_path):
    twin = _Twin()
    try:
        with profiled(tmp_path) as spans:
            twin.wave(evict_ok=True)
        (ev,) = named(spans, "sched/preempt_requeue")
        cpu_ms = ev["stats"].pop("cpu_ms")  # every clocked span's
        assert 0 <= cpu_ms <= (ev["end"] - ev["start"]) / 1e6 + 10.0
        assert ev["stats"] == {
            "pods": N, "records": N - 2, "stale": 2, "transactions": 1,
        }
    finally:
        twin.close()


def test_one_wakeup_hands_the_dispatcher_the_whole_wave():
    """A dispatcher blocked in ``pop_batch`` wakes to every no-backoff
    preemptor of the wave at once, and not to its first few."""
    twin = _Twin()
    try:
        queue = twin.sched.queue
        queue.delete(next(
            pi.pod for pi in twin.infos if pi.pod.metadata.name == REQUEUED
        ))
        # wakeups made by the wave's own thread (the informer's echoes
        # of pods the queue holds wake the dispatcher too, on theirs)
        notifies = []
        notify = queue._cond.notify
        me = threading.get_ident()
        queue._cond.notify = lambda *a: (
            notifies.append(threading.get_ident() == me), notify(*a)
        )
        twin.wave(evict_ok=True)
        expected = sum(
            1 for nm in twin.names
            if nm not in (DELETED, BOUND) and int(nm[1:]) % 4 != 3
        )
        assert queue.num_pending()["active"] == expected
        assert sum(notifies) == 1
        batch = queue.pop_batch(N, timeout=1)
        assert len(batch) == expected
        # the nominees keep their first enqueue time: they sort before
        # later arrivals of their priority
        assert all(pi.timestamp <= twin.infos[-1].timestamp for pi in batch)
    finally:
        twin.close()


def test_status_bulk_is_one_frame_of_modified_events():
    server = APIServer()
    client = Client(server)
    client.create_pods_bulk([make_pod(f"s{i}").obj() for i in range(5)])
    before = {
        p.metadata.name: p for p in client.list_pods()[0]
    }
    watch = server.watch("Pod", since_rv=server.current_rv())

    def nominate(node):
        def mutate(p):
            p.status.nominated_node_name = node
        return mutate

    def boom(p):
        raise RuntimeError("mutate failed")

    errors = client.update_pod_status_bulk(
        [("default", "s0", nominate("a")), ("default", "gone", nominate("x")),
         ("default", "s1", nominate("b")), ("default", "s2", boom),
         ("default", "s3", nominate("c"))]
    )
    assert [(i, type(e)) for i, e in errors] == [
        (1, NotFound), (3, RuntimeError),
    ]
    frame = watch.pending()  # ONE read delivers the whole transaction
    assert [(ev.type, ev.object.metadata.name) for ev in frame] == [
        (MODIFIED, "s0"), (MODIFIED, "s1"), (MODIFIED, "s3"),
    ]
    rvs = [ev.resource_version for ev in frame]
    assert rvs == sorted(set(rvs)) and rvs[0] > max(
        p.metadata.resource_version for p in before.values()
    )
    assert [ev.object.metadata.resource_version for ev in frame] == rvs
    assert [ev.object.status.nominated_node_name for ev in frame] == [
        "a", "b", "c",
    ]
    # copy-on-write: the objects a watcher already holds are untouched
    assert all(p.status.nominated_node_name == "" for p in before.values())
    assert client.get_pod("default", "s2") is before["s2"]
    assert watch.pending() == []


def test_a_failed_status_transaction_leaves_the_pods_requeued():
    twin = _Twin()
    try:
        install_injector(FaultInjector(FaultProfile(
            name="api-down-once",
            points={
                FaultPoint.API_UNAVAILABLE: PointConfig(
                    rate=1.0, max_fires=1
                ),
            },
        )))
        twin.wave(evict_ok=True)
        install_injector(None)
        places, nominations, stored = twin.state()
        for nm in twin.names:
            if nm in (DELETED, BOUND):
                continue
            assert places[nm] != "none", nm
            assert stored[nm][0] == [] and stored[nm][1] == ""
        assert nominations == {
            nm: "n0" for nm in twin.names
            if nm not in (DELETED, BOUND) and int(nm[1:]) % 4 != 3
        }
    finally:
        twin.close()


def test_the_ingest_span_counts_the_echoes_the_queue_ignored(tmp_path):
    """Status writes for pods the scheduler holds come back as one frame;
    the queue adds none of them, and the frame's ``sched/ingest`` span
    and the metric say how many there were."""
    from kubernetes_tpu.utils import metrics

    twin = _Twin()
    try:
        held = [nm for nm in twin.names if nm not in (DELETED, BOUND, REQUEUED)]
        counted = metrics.queue_echoes_ignored.value()

        def nominate(p):
            p.status.nominated_node_name = "n0"

        with profiled(tmp_path) as spans:
            errors = twin.client.update_pod_status_bulk(
                [("default", nm, nominate) for nm in held + [REQUEUED]]
            )
            assert errors == []
            pods = twin.informers.pods()
            _wait(
                lambda: pods.get("default", REQUEUED)
                .status.nominated_node_name == "n0",
                "echoes ingested",
            )
        assert twin.sched.queue.echoes_ignored == len(held)
        assert metrics.queue_echoes_ignored.value() - counted == len(held)
        # the queued pod follows its update, the held ones stay held
        assert twin.sched.queue.num_pending()["active"] == 1
        (frame,) = [
            ev for ev in named(spans, "sched/ingest")
            if ev["stats"].get("echoes_ignored")
        ]
        # beside the primitive's ``cpu_ms`` and the hand-off's
        # ``waited_ms``; a status echo is no add, bind echo or delete
        assert frame["stats"].keys() - {"cpu_ms", "waited_ms"} == {
            "kind", "events", "echoes_ignored",
        }
        assert frame["stats"]["kind"] == "Pod"
        assert frame["stats"]["events"] == len(held) + 1
        assert frame["stats"]["echoes_ignored"] == len(held)
    finally:
        twin.close()
