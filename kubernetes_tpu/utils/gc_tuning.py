"""Cyclic-GC tuning for the scheduling hot path.

A synced control plane holds a large, long-lived object graph (nodes,
cached pods, informer stores), and every bound pod adds to it: a stored
event, a watch-history entry, the pod's own versions. CPython's cyclic
collector walks every object it tracks that is not frozen, so a full
collection costs by the size of that graph and finds next to nothing in
it: the burst's garbage is acyclic and reference counts reclaim it at
once. Two things keep the walks short. The graph that stands once the
caches are synced is frozen into the permanent generation and the
thresholds are stretched (``freeze_steady_state_graph``), and the
dispatcher's guard freezes what survives each collection at its idle
point (``GCBatchGuard``), so a later collection walks what arrived since.
The whole heap is walked again only when the short collections have
taken as long as it will. PERF.md section 5 has the chip's readings
(``sched/gc`` spans a wave, before and after); none is repeated here.
"""

from __future__ import annotations

import gc
import time as _time
from typing import Optional

from kubernetes_tpu.utils import flightrecorder

#: seconds the newest walk of the whole heap took, whoever made it
#: (``freeze_steady_state_graph`` or a guard); None until there was one.
#: The collector is the process's, so this is too.
_whole_walk_seconds: Optional[float] = None


def _walk_whole_heap() -> None:
    """Collect with nothing frozen, and time it for the guards' rule. A
    cycle that was frozen alive and died since is reclaimed here and
    nowhere else."""
    global _whole_walk_seconds
    t0 = _time.perf_counter()
    gc.unfreeze()
    gc.collect()
    _whole_walk_seconds = _time.perf_counter() - t0


def freeze_steady_state_graph(
    gen0: int = 100_000, gen1: int = 50, gen2: int = 50
) -> None:
    """Call once the long-lived state is built (after informer sync /
    before the measured burst). The collection walks the whole heap,
    whatever a guard froze before, and leaves its seconds as the cost a
    guard's next whole walk has to be paid for."""
    _walk_whole_heap()
    gc.freeze()
    gc.set_threshold(gen0, gen1, gen2)


class GCBatchGuard:
    """Collect-at-idle policy for the batch dispatcher.

    Even with the steady-state graph frozen and thresholds stretched, a
    burst allocates enough (clones, watch events, queue entries, solver
    bookkeeping) to trigger young-generation collections inside the
    measured window, each walking the whole unfrozen young set (what
    that cost a pod: not measured on the chip). The scheduler knows its
    own idle points (queue drained, nothing in flight), so cyclic
    collection is disabled while batches are being scheduled and runs
    once at the active->idle transition. Plain refcounting still
    reclaims the (acyclic) burst garbage immediately; the deferred pass
    only exists to catch stray cycles (tracebacks, closures).

    What that pass leaves is alive, and is frozen: the next collection,
    the guard's, the active phase's or an embedding process's own, walks
    what arrived since this idle point and not the stored events, the
    watch history and the pods of every wave before it. Frozen objects
    are still freed by their reference counts; only a cycle that dies
    after it was frozen waits, for the next walk of the whole heap
    (unfreeze, collect, freeze). That walk takes the collector through
    everything the process holds with the GIL held (0.7-1.7 s on the
    chip's host at the benchmark's clusters, PERF.md section 5), so it
    comes only where the queue has stayed empty through one more poll
    after the idle point, never in the active phase and never at the
    idle point itself, which in a cluster that takes a burst a second
    lies inside the next burst; and only once the idle collections
    since the last whole walk have taken the seconds that walk took:
    the whole walks then cost at most what the short ones did, with no
    interval to tune. A process whose queue is never empty for two
    polls on end keeps what died frozen until ``close()``."""

    #: under SUSTAINED load (the queue never drains) a bounded young-
    #: generation collect runs at most this often, so stray cycles from a
    #: long active phase cannot grow RSS without bound
    ACTIVE_COLLECT_INTERVAL_S = 10.0
    #: every Nth in-flight collect runs the FULL collector: gen-1-only
    #: passes promote surviving cycles to gen 2, which would otherwise
    #: wait for an idle transition that sustained load never reaches
    FULL_COLLECT_EVERY = 6

    def __init__(self, totals=None) -> None:
        #: the scheduler's StageTotals: every collection is a ``gc``
        #: stage (a ``sched/gc`` span on the dispatcher's line)
        self._totals = totals
        self._active = False
        self._last_collect = 0.0
        self._active_collects = 0
        #: seconds of idle collections since the last whole walk
        self._idle_seconds = 0.0
        #: how often the mechanism engaged: collections whose survivors
        #: were frozen, and those of them that walked the whole heap
        self.freezes = 0
        self.whole_walks = 0

    def active(self) -> None:
        if not self._active:
            gc.disable()
            self._active = True
            self._last_collect = _time.monotonic()
            self._active_collects = 0
            return
        now = _time.monotonic()
        if now - self._last_collect >= self.ACTIVE_COLLECT_INTERVAL_S:
            # explicit collect works while the collector is disabled;
            # gen-1 keeps the pause bounded (young objects only), with a
            # periodic full pass to drain gen-2 promotions
            self._active_collects += 1
            if self._active_collects % self.FULL_COLLECT_EVERY == 0:
                self._collect(2)
            else:
                self._collect(1)
            self._last_collect = now

    def idle(self) -> None:
        """Called at every pop that came back empty. The first after an
        active phase is the idle point; a later one finds the queue
        still empty a poll on."""
        if self._active:
            self._idle_seconds += self._collect(2)
            self._freeze()
            # enabled last: the burst's allocations stand over the young
            # threshold, and the first allocation under an enabled
            # collector would walk them once more before the pass above
            gc.enable()
            self._active = False
        elif self._idle_seconds > 0.0 and (
            _whole_walk_seconds is None
            or self._idle_seconds >= _whole_walk_seconds
        ):
            self._collect_whole()
            self._freeze()

    def _freeze(self) -> None:
        gc.freeze()
        self.freezes += 1

    def _collect(self, generation: int) -> float:
        with flightrecorder.stage(
            "gc", totals=self._totals, generation=generation, whole=0
        ) as timed:
            gc.collect(generation)
        return timed.seconds

    def _collect_whole(self) -> None:
        with flightrecorder.stage(
            "gc", totals=self._totals, generation=2, whole=1
        ):
            _walk_whole_heap()
        self._idle_seconds = 0.0
        self.whole_walks += 1

    def close(self) -> None:
        """The last collection walks the whole heap and leaves nothing
        frozen: a process that starts and stops schedulers keeps none of
        a stopped one's garbage out of the collector's sight."""
        self._collect_whole()
        gc.enable()
        self._active = False
