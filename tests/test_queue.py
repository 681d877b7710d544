import threading
import time

import pytest

from kubernetes_tpu.framework.interface import PodInfo
from kubernetes_tpu.queue import events, scheduling_queue
from kubernetes_tpu.queue.heap import Heap
from kubernetes_tpu.queue.scheduling_queue import PriorityQueue
from kubernetes_tpu.testing import make_pod


def priority_less(a: PodInfo, b: PodInfo) -> bool:
    """PrioritySort semantics: higher priority first, then earlier queue time."""
    pa, pb = a.pod.spec.priority, b.pod.spec.priority
    if pa != pb:
        return pa > pb
    return a.timestamp < b.timestamp


def _pq(now):
    return PriorityQueue(priority_less, now=lambda: now[0])


def test_heap_basic():
    h = Heap(lambda x: x[0], lambda a, b: a[1] < b[1])
    h.add(("a", 3))
    h.add(("b", 1))
    h.add(("c", 2))
    assert h.pop() == ("b", 1)
    h.add(("c", 0))  # update key c
    assert h.pop() == ("c", 0)
    assert h.pop() == ("a", 3)
    assert len(h) == 0


def test_pop_orders_by_priority():
    now = [0.0]
    q = _pq(now)
    q.add(make_pod("low").priority(1).obj())
    q.add(make_pod("high").priority(10).obj())
    q.add(make_pod("mid").priority(5).obj())
    assert q.pop().pod.name == "high"
    assert q.pop().pod.name == "mid"
    assert q.pop().pod.name == "low"


def test_unschedulable_then_move_on_event():
    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    pi = q.pop()
    cycle = q.scheduling_cycle
    q.add_unschedulable_if_not_present(pi, cycle)
    assert q.num_pending()["unschedulable"] == 1

    # node-add event moves it; backoff (1s) still pending at t=0 -> backoffQ
    q.move_all_to_active_or_backoff_queue(events.NodeAdd)
    assert q.num_pending()["backoff"] == 1
    # after backoff expires, flush moves it to activeQ
    now[0] = 3.0
    q.flush_backoff_q_completed()
    assert q.num_pending()["active"] == 1
    assert q.pop().pod.name == "p1"


def test_move_request_cycle_prevents_lost_wakeup():
    """A move request during a pod's scheduling attempt must send the
    failed pod to backoffQ, not unschedulableQ (scheduling_queue.go:141)."""
    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    pi = q.pop()
    cycle = q.scheduling_cycle
    # concurrent event while p1 was being scheduled:
    q.move_all_to_active_or_backoff_queue(events.NodeAdd)
    q.add_unschedulable_if_not_present(pi, cycle)
    assert q.num_pending()["unschedulable"] == 0
    assert q.num_pending()["backoff"] == 1


def test_backoff_grows_exponentially():
    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    pi = q.pop()
    assert pi.attempts == 1
    assert q._backoff_duration(pi) == 1.0  # first failure: initial backoff
    pi.attempts = 3
    assert q._backoff_duration(pi) == 4.0  # 1s * 2^(attempts-1)
    pi.attempts = 10
    assert q._backoff_duration(pi) == 10.0  # capped at max


def test_flush_unschedulable_leftover():
    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    pi = q.pop()
    q.add_unschedulable_if_not_present(pi, q.scheduling_cycle)
    now[0] = 61.0
    q.flush_unschedulable_q_leftover()
    assert q.num_pending()["unschedulable"] == 0
    assert q.num_pending()["active"] == 1  # backoff long expired


def test_pop_batch_drains():
    now = [0.0]
    q = _pq(now)
    for i in range(5):
        q.add(make_pod(f"p{i}").priority(i).obj())
    batch = q.pop_batch(3)
    assert [pi.pod.name for pi in batch] == ["p4", "p3", "p2"]
    assert q.num_pending()["active"] == 2


def test_nominated_pods():
    now = [0.0]
    q = _pq(now)
    p = make_pod("p1").obj()
    q.update_nominated_pod_for_node(p, "n1")
    assert [x.name for x in q.nominated_pods_for_node("n1")] == ["p1"]
    q.delete_nominated_pod_if_exists(p)
    assert q.nominated_pods_for_node("n1") == []


def test_status_only_update_keeps_pod_parked():
    """The scheduler's own PodScheduled-condition write must not wake a
    parked unschedulable pod (isPodUpdated guard, scheduling_queue.go)."""
    from kubernetes_tpu.api.types import PodCondition

    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    pi = q.pop()
    q.add_unschedulable_if_not_present(pi, q.scheduling_cycle)
    old = pi.pod
    new = make_pod("p1").obj()
    new.status.conditions.append(PodCondition(type="PodScheduled", status="False"))
    new.metadata.resource_version = 99
    q.update(old, new)
    assert q.num_pending() == {"active": 0, "backoff": 0, "unschedulable": 1}
    # but a real spec change does wake it
    labeled = make_pod("p1").labels(x="1").obj()
    q.update(new, labeled)
    assert q.num_pending()["unschedulable"] == 0


def test_update_in_unschedulable_moves_to_active():
    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    pi = q.pop()
    q.add_unschedulable_if_not_present(pi, q.scheduling_cycle)
    now[0] = 5.0  # backoff expired
    updated = make_pod("p1").labels(v="2").obj()
    q.update(pi.pod, updated)
    assert q.num_pending()["active"] == 1


# -- update() for a pod by where the queue holds it -------------------------

# what an update changes: the first two nothing that _is_pod_updated
# reads (a status write's echo), the others something it does
_STATUS_ONLY = ("condition", "nominated_node_name")
_CHANGES = _STATUS_ONLY + (
    "spec", "labels", "annotations", "deletion_timestamp",
    "owner_references", "old_none",
)


def _changed(old, change):
    import copy

    from kubernetes_tpu.api.types import OwnerReference, PodCondition

    new = copy.deepcopy(old)
    new.metadata.resource_version = old.metadata.resource_version + 1
    if change == "condition":
        new.status.conditions.append(
            PodCondition(
                type="PodScheduled", status="False", reason="Unschedulable"
            )
        )
    elif change == "nominated_node_name":
        new.status.nominated_node_name = "n1"
    elif change == "spec":
        new.spec.priority = old.spec.priority + 1
    elif change == "labels":
        new.metadata.labels["v"] = "2"
    elif change == "annotations":
        new.metadata.annotations["note"] = "x"
    elif change == "deletion_timestamp":
        new.metadata.deletion_timestamp = 12.0
    elif change == "owner_references":
        new.metadata.owner_references.append(
            OwnerReference(kind="ReplicaSet", name="rs", uid="u1")
        )
    else:
        assert change == "old_none"
    return (None if change == "old_none" else old), new


@pytest.mark.parametrize("change", _CHANGES)
@pytest.mark.parametrize(
    "place", ["held", "active", "backoff", "unschedulable"]
)
def test_update_by_place_and_change(place, change):
    """``update`` for a pod in each of the queue's places. A pod in NONE
    of its maps is held by the scheduler (popped into a batch, parked
    for a preemption wave, at Permit) or has just bound: the echo of a
    status write adds nothing and is counted, a real change (or no old
    object to compare with) adds the pod as before. A queued pod
    behaves as it always has."""
    from kubernetes_tpu.utils import metrics

    now = [0.0]
    q = _pq(now)
    q.add(make_pod("p1").obj())
    if place != "active":
        pi = q.pop()
        if place == "backoff":
            # a move request during the attempt sends it to the backoffQ
            q.move_all_to_active_or_backoff_queue(events.NodeAdd)
        if place != "held":
            q.add_unschedulable_if_not_present(pi, q.scheduling_cycle)
    now[0] = 5.0  # past the 1 s backoff: a woken pod goes to the activeQ
    before = dict(active=0, backoff=0, unschedulable=0)
    if place != "held":
        before[place] = 1
    assert q.num_pending() == before
    counted = metrics.queue_echoes_ignored.value()
    old = (
        q.active_q.get_by_key("default/p1")
        or q.pod_backoff_q.get_by_key("default/p1")
        or q.unschedulable_q.get("default/p1")
        or pi
    ).pod
    old, new = _changed(old, change)

    q.update(old, new)

    status_only = change in _STATUS_ONLY
    ignored = int(place == "held" and status_only)
    assert q.echoes_ignored == ignored
    assert metrics.queue_echoes_ignored.value() - counted == ignored
    after = dict(before)
    if place == "held" and not status_only:
        after["active"] = 1
    elif place == "unschedulable" and not status_only:
        after = dict(active=1, backoff=0, unschedulable=0)
    assert q.num_pending() == after
    if place != "held":
        # a queued pod's record follows the newest object in every case
        held = (
            q.active_q.get_by_key("default/p1")
            or q.pod_backoff_q.get_by_key("default/p1")
            or q.unschedulable_q.get("default/p1")
        )
        assert held.pod is new
    elif not status_only:
        assert q.active_q.get_by_key("default/p1").pod is new


# -- the batch window runs from the oldest pod's own arrival ------------------

W = 0.010  # the window of the cases below, in the queue's seconds


class _Scripted:
    """A queue on a clock the test owns, whose condition variable does
    not block: every wait the pop asks for is recorded, then the next
    step of ``script`` runs (the clock moves on by ``step`` and ``then``
    happens: a wake-up, by an arrival or for nothing), or with the
    script used up the clock moves on by all that was asked for (a wait
    that timed out)."""

    def __init__(self, script=()):
        self.now = [0.0]
        self.q = _pq(self.now)
        self.asked = []
        self.script = list(script)
        self.q._cond.wait = self._wait

    def _wait(self, seconds=None):
        self.asked.append(seconds)
        if self.script:
            step, then = self.script.pop(0)
            self.now[0] += step
            if then is not None:
                then(self)
        else:
            assert seconds is not None, "the pop would block for ever"
            self.now[0] += seconds

    def add(self, name, at=None):
        if at is not None:
            self.now[0] = at
        self.q.add(make_pod(name).obj())

    def pop(self, window=W, size=8):
        return [pi.pod.name for pi in self.q.pop_batch(size, window=window)]


def _aged_past_the_window_leaves_with_no_wait():
    c = _Scripted()
    c.add("old", at=0.0)
    c.now[0] = 0.025
    assert c.pop() == ["old"]
    assert c.asked == [] and c.q.last_pop_wait_seconds == 0.0


def _aged_less_than_the_window_waits_what_is_left_of_it():
    c = _Scripted()
    c.add("a", at=0.0)
    c.now[0] = 0.004
    assert c.pop() == ["a"]
    assert c.asked == pytest.approx([W - 0.004])
    assert c.now[0] == pytest.approx(W)


def _an_arrival_at_an_idle_pop_waits_the_whole_window():
    c = _Scripted([(0.5, lambda c: c.add("new"))])
    assert c.pop() == ["new"]
    assert c.asked[0] is None  # the wait for a first pod
    assert c.asked[1:] == pytest.approx([W])
    assert c.now[0] == pytest.approx(0.5 + W)


def _a_younger_arrival_during_the_wait_does_not_extend_it():
    c = _Scripted([(0.003, lambda c: c.add("young"))])
    c.add("old", at=0.0)
    c.now[0] = 0.004
    assert c.pop() == ["old", "young"]
    assert c.asked == pytest.approx([0.006, 0.003])
    assert c.now[0] == pytest.approx(W)  # the old pod's window, no more


def _a_full_batch_never_reads_the_anchor():
    def read(pi):
        raise AssertionError("a full batch looked for its oldest pod")

    c = _Scripted()
    for name in ("a", "b", "c"):
        c.add(name, at=0.0)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scheduling_queue, "_timestamp", read)
        assert c.pop(size=2) == ["a", "b"]
    assert c.asked == []


def _requeue_per_pod(q, pi):
    q.add_unschedulable_if_not_present(
        pi, q.scheduling_cycle, skip_backoff=True
    )


def _requeue_bulk(q, pi):
    q.add_unschedulable_many([(pi, q.scheduling_cycle, True, "")])


def _a_requeued_nominee_keeps_its_age(requeue):
    def case():
        c = _Scripted()
        c.add("nominee", at=2.0)
        (pi,) = c.q.pop_batch(8, window=0.0)
        c.now[0] = 2.5  # its wave evicted the victims meanwhile
        requeue(c.q, pi)
        assert pi.timestamp == 2.0
        assert c.pop() == ["nominee"]
        assert c.asked == []

    return case


def _a_backed_off_pod_keeps_its_parking_time():
    c = _Scripted()
    c.add("failed", at=0.0)
    (pi,) = c.q.pop_batch(8, window=0.0)
    c.now[0] = 5.0
    c.q.add_unschedulable_if_not_present(pi, c.q.scheduling_cycle)
    c.q.move_all_to_active_or_backoff_queue(events.NodeAdd)
    assert c.q.num_pending()["backoff"] == 1
    c.now[0] = 6.001  # the first backoff is one second
    c.q.flush_backoff_q_completed()
    assert pi.timestamp == 5.0
    assert c.pop() == ["failed"]
    assert c.asked == []  # it has waited its second: none for company


def _then(first, later):
    """A window the controller moves once: ``first`` at the first read,
    ``later`` at every wake-up after it."""
    reads = [first]
    return lambda: reads.pop() if reads else later


def _a_shrink_applies_mid_wait_from_the_anchor():
    c = _Scripted([(0.001, None)])  # a wake-up for nothing at 0.003
    c.add("a", at=0.0)
    c.now[0] = 0.002
    assert c.pop(window=_then(W, 0.004)) == ["a"]
    assert c.asked == pytest.approx([0.008, 0.001])
    assert c.now[0] == pytest.approx(0.004)


def _a_grow_never_extends_the_armed_deadline():
    c = _Scripted([(0.001, None)])
    c.add("a", at=0.0)
    c.now[0] = 0.002
    assert c.pop(window=_then(W, 1.0)) == ["a"]
    assert c.asked == pytest.approx([0.008, 0.007])
    assert c.now[0] == pytest.approx(W)


def _no_window_never_waits():
    c = _Scripted()
    c.add("fresh", at=1.0)
    assert c.pop(window=0.0) == ["fresh"]
    assert c.asked == []


@pytest.mark.parametrize("case", [
    _aged_past_the_window_leaves_with_no_wait,
    _aged_less_than_the_window_waits_what_is_left_of_it,
    _an_arrival_at_an_idle_pop_waits_the_whole_window,
    _a_younger_arrival_during_the_wait_does_not_extend_it,
    _a_full_batch_never_reads_the_anchor,
    _a_requeued_nominee_keeps_its_age(_requeue_per_pod),
    _a_requeued_nominee_keeps_its_age(_requeue_bulk),
    _a_backed_off_pod_keeps_its_parking_time,
    _a_shrink_applies_mid_wait_from_the_anchor,
    _a_grow_never_extends_the_armed_deadline,
    _no_window_never_waits,
], ids=[
    "aged-past-the-window", "aged-less", "idle-pop", "younger-arrival",
    "full-batch", "nominee-per-pod", "nominee-bulk", "backed-off",
    "shrink", "grow", "no-window",
])
def test_the_window_runs_from_the_oldest_pods_arrival(case):
    """``pop_batch``'s window is the longest a pod waits for company,
    from its own arrival (``PodInfo.timestamp``), not from the pop: on
    the queue's injectable clock, where the clock decides."""
    case()


@pytest.mark.parametrize("aged, window, at_least, under", [
    (0.0, 0.15, 0.15, None),   # arrives at a blocked pop: the whole window
    (0.2, 0.5, 0.25, 0.45),    # aged 0.2 of 0.5: about 0.3 more, not 0.5
    (0.3, 0.25, 0.0, 0.2),     # aged past it: leaves at once
], ids=["idle-pop", "aged-less", "aged-past"])
def test_a_blocked_pop_keeps_the_window_on_the_real_clock(
    aged, window, at_least, under
):
    """The same rule where a thread must block: the pop's real wait,
    from the later of its own start and the pod's arrival."""
    q = PriorityQueue(priority_less)
    out = {}

    def drain():
        out["names"] = [
            pi.pod.name for pi in q.pop_batch(8, timeout=5.0, window=window)
        ]
        out["returned"] = time.monotonic()

    t = threading.Thread(target=drain)
    if aged:
        q.add(make_pod("p").obj())
        time.sleep(aged)
        began = time.monotonic()
        t.start()
    else:
        t.start()
        time.sleep(0.05)  # the pop blocks on the empty queue
        began = time.monotonic()
        q.add(make_pod("p").obj())
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert out["names"] == ["p"]
    waited = out["returned"] - began
    assert waited >= at_least - 0.01
    if under is not None:
        assert waited < under
    if not at_least:
        assert q.last_pop_wait_seconds == 0.0
