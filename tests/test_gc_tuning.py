"""The collector's policy (``kubernetes_tpu/utils/gc_tuning.py``): what
the guard freezes at the dispatcher's idle point, when the whole heap is
walked again, and that a closed guard leaves the process as it found it.
"""

import gc
import types
import weakref

import pytest

from kubernetes_tpu.utils import flightrecorder, gc_tuning
from kubernetes_tpu.utils.gc_tuning import (
    GCBatchGuard,
    freeze_steady_state_graph,
)


@pytest.fixture(autouse=True)
def collector_as_found(monkeypatch):
    """Each test gets a collector with nothing frozen and no whole walk
    on record, and hands back the one it found."""
    thresholds = gc.get_threshold()
    monkeypatch.setattr(gc_tuning, "_whole_walk_seconds", None)
    gc.unfreeze()
    yield
    gc.unfreeze()
    gc.enable()
    gc.set_threshold(*thresholds)


class Record:
    """An object the collector tracks (an instance with a ``__dict__``)."""


def burst(guard: GCBatchGuard) -> None:
    """One active phase and its idle point."""
    guard.active()
    guard.idle()


def quiet_poll(guard: GCBatchGuard) -> None:
    """The dispatcher's next pop came back empty too."""
    guard.idle()


def is_walked(obj) -> bool:
    # gc.get_objects() lists the three generations a collection walks,
    # not the permanent one
    return any(o is obj for o in gc.get_objects())


def test_a_survivor_of_the_idle_collection_is_out_of_every_later_walk():
    guard = GCBatchGuard()
    survivor = Record()
    survivor.held = [Record()]
    assert is_walked(survivor)
    burst(guard)
    assert guard.freezes == 1
    assert not is_walked(survivor) and not is_walked(survivor.held)
    arrived_since = Record()
    gc.collect()
    # a full collection promotes what it walked to the oldest generation:
    # the newcomer is there, the survivor still in none
    assert is_walked(arrived_since)
    assert not is_walked(survivor)
    # frozen is not immortal: its reference count still frees it
    gone = weakref.ref(survivor.held[0])
    del survivor.held[:]
    assert gone() is None


def test_a_cycle_that_dies_frozen_waits_for_the_next_whole_walk():
    guard = GCBatchGuard()
    burst(guard)
    quiet_poll(guard)  # no whole walk on record yet: this is the first
    assert guard.whole_walks == 1
    cycle = Record()
    cycle.me = cycle
    gone = weakref.ref(cycle)
    burst(guard)  # frozen alive
    assert not is_walked(cycle)
    del cycle
    gc.collect()
    assert gone() is not None  # no collection looks at it
    burst(guard)
    quiet_poll(guard)  # not paid for
    assert guard.whole_walks == 1 and gone() is not None
    guard._idle_seconds = gc_tuning._whole_walk_seconds  # paid for
    burst(guard)  # but the idle point itself never walks the whole heap
    assert guard.whole_walks == 1 and gone() is not None
    quiet_poll(guard)
    assert guard.whole_walks == 2
    assert gone() is None
    assert not is_walked(guard)  # and the survivors are frozen again
    quiet_poll(guard)  # nothing collected since: nothing to pay with
    assert guard.whole_walks == 2
    guard.close()


class ScriptedCollector:
    """Stands in for the clocks and for the module's ``gc``: each
    collection takes the next scripted duration, whoever times it."""

    def __init__(self, monkeypatch, durations):
        self.now = 0.0
        self.durations = list(durations)
        self.frozen = False
        #: one entry a collection: was the heap unfrozen under it
        self.whole = []
        clock = types.SimpleNamespace(
            perf_counter=self.read, monotonic=self.read
        )
        monkeypatch.setattr(gc_tuning, "_time", clock)
        monkeypatch.setattr(flightrecorder, "_clock", self.read)
        #: what was asked of the collector, in order
        self.calls = []
        monkeypatch.setattr(gc_tuning, "gc", types.SimpleNamespace(
            collect=self.collect,
            freeze=lambda: self.freeze(True),
            unfreeze=lambda: self.freeze(False),
            enable=lambda: self.calls.append("enable"),
            disable=lambda: self.calls.append("disable"),
            set_threshold=lambda *thresholds: None,
        ))

    def read(self) -> float:
        return self.now

    def freeze(self, frozen: bool) -> None:
        self.calls.append("freeze" if frozen else "unfreeze")
        self.frozen = frozen

    def collect(self, generation: int = 2) -> int:
        self.calls.append("collect")
        self.whole.append(not self.frozen)
        self.now += self.durations.pop(0)
        return 0


def test_the_whole_heap_is_walked_when_the_idle_collections_have_paid(
    monkeypatch,
):
    totals = flightrecorder.StageTotals()
    script = ScriptedCollector(
        monkeypatch,
        # the steady state's walk; three idle collections that together
        # reach it; the whole walk they paid for, which is longer; then
        # idle collections that have to reach that one
        # (in 64ths of a second, which the clock's differences keep exact)
        [n / 64 for n in (8, 2, 2, 4, 10, 5, 4, 1, 12)],
    )
    freeze_steady_state_graph()
    assert gc_tuning._whole_walk_seconds == 8 / 64
    guard = GCBatchGuard(totals)
    for _ in range(6):
        burst(guard)
        quiet_poll(guard)
    #       steady | 2      2      4   | whole | 5      4      1   | whole
    assert script.whole == [
        True, False, False, False, True, False, False, False, True,
    ]
    assert (guard.freezes, guard.whole_walks) == (8, 2)
    assert gc_tuning._whole_walk_seconds == 12 / 64
    assert script.frozen
    # every collection of the guard's is a gc stage, the whole ones too
    assert totals.calls() == {"gc": 8}
    assert totals.seconds()["gc"] == (2 + 2 + 4 + 10 + 5 + 4 + 1 + 12) / 64


def test_a_burst_a_second_is_never_under_a_whole_walk(monkeypatch):
    """However much the idle collections have paid: the idle point, half
    a second after the last pop, lies inside the next burst."""
    script = ScriptedCollector(monkeypatch, [0.25] + [0.5] * 8 + [1.0, 0.5])
    freeze_steady_state_graph()
    guard = GCBatchGuard()
    for _ in range(8):
        burst(guard)
    assert script.whole == [True] + [False] * 8
    assert guard.whole_walks == 0
    quiet_poll(guard)  # the first poll that finds the queue still empty
    assert script.whole[-1] is True and guard.whole_walks == 1
    quiet_poll(guard)
    assert guard.whole_walks == 1
    guard.close()


def test_no_whole_walk_in_the_active_phase(monkeypatch):
    script = ScriptedCollector(monkeypatch, [0.001] + [0.5] * 13)
    freeze_steady_state_graph()
    guard = GCBatchGuard()
    guard.active()
    for _ in range(2 * guard.FULL_COLLECT_EVERY):
        # sustained load: the queue never drains, a collection is overdue
        script.now += guard.ACTIVE_COLLECT_INTERVAL_S
        guard.active()
    # two of the twelve were full collections, none with the heap unfrozen
    assert script.whole == [True] + [False] * 12
    assert guard.whole_walks == 0 and guard.freezes == 0
    # nor do they pay for one: the idle point's own collection is short
    guard.idle()
    assert script.whole[-1] is False and guard.whole_walks == 0
    assert guard.freezes == 1


def test_the_collector_is_enabled_only_after_the_idle_pass(monkeypatch):
    """Enabled first, the first allocation after a burst finds the young
    generation over its threshold and walks it once more, unnamed, right
    before the guard's own pass."""
    script = ScriptedCollector(monkeypatch, [0.5, 0.25, 0.25, 0.5])
    freeze_steady_state_graph()
    guard = GCBatchGuard()
    del script.calls[:]
    burst(guard)
    assert script.calls == ["disable", "collect", "freeze", "enable"]
    del script.calls[:]
    guard.active()
    guard.close()
    assert script.calls == ["disable", "unfreeze", "collect", "enable"]


def test_close_leaves_nothing_frozen_and_the_collector_enabled():
    # what a process that never froze anything reads: this CPython's full
    # collection leaves a few hundred objects of its own in the permanent
    # generation
    gc.collect()
    never_froze = gc.get_freeze_count()
    freeze_steady_state_graph()
    guard = GCBatchGuard()
    cycle = Record()
    cycle.me = cycle
    gone = weakref.ref(cycle)
    burst(guard)
    del cycle
    guard.active()  # closed in the middle of a burst
    assert not gc.isenabled() and gc.get_freeze_count() > never_froze
    assert not is_walked(guard)
    guard.close()
    assert gc.isenabled()
    assert gc.get_freeze_count() == never_froze and is_walked(guard)
    assert gone() is None  # the last collection was a whole walk
    guard.idle()  # a closed guard is idle: nothing more to do
    assert gc.get_freeze_count() == never_froze
