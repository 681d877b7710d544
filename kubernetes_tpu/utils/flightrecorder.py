"""Always-on flight recorder: the last K batch spans + control-plane
marks, dumpable as JSON when something goes wrong.

Twelve PRs of robustness machinery (ladders, breakers, waves,
partitions) degrade observably only as scattered counters; this module
is the single record that reconstructs *what happened to batch N*:

- ``BatchSpan``: one monotonically-numbered record per dispatch --
  batch size, pad shape, solver tier actually run, carry decision
  (reuse / delta scatter / full upload), per-stage wall clock, commit
  outcome, conflicts absorbed, per-pod linkage (uid -> batch id,
  queue-wait, attempt count).
- marks: breaker transitions, ladder fallbacks, fault points fired,
  fencing aborts, partition takeovers, preemption waves, mid-run jit
  recompiles, arrival-engine stalls, autobatch decisions.

The ring is bounded (``deque(maxlen=...)``) and lock-cheap: one short
lock hold per span begin / mark; span field updates are owned by the
single thread driving that batch (dispatcher, then committer -- the
pipeline hands the batch off, never shares it). ``KTPU_FLIGHTRECORDER=0``
compiles the spine out (begin_batch returns the no-op NullSpan, mark
returns immediately) -- the arm the overhead microbench compares
against.

Dump triggers: ``/debug/flightrecorder`` (scheduler/app.py), SIGUSR1,
and ``dump_on_degraded`` wherever a component raises the
degraded-health gauge. Chaos e2es assert against ``RECORDER.dump()``
instead of grepping logs.

``stage`` is the one way a stage of the hot path is timed: one ``with``
adds the seconds to the always-on totals (``StageTotals``, what
``BatchScheduler.stage_seconds`` reads), records them on the batch's
span, and while a profiler session runs (``jax.profiler.trace``) is a
``jax.profiler.TraceAnnotation("sched/<name>")`` for its whole duration,
so the session shows the scheduler's stages on the device trace's clock,
on the line of the thread that did the work, each with its ``cpu_ms``:
the thread's own CPU time. ``mark`` lands there too, as a zero-length
``sched/mark/<kind>``. The totals and the annotations do not depend on
``KTPU_FLIGHTRECORDER``: only the ring does.
"""

from __future__ import annotations

import ctypes
import functools
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

logger = logging.getLogger(__name__)

#: compile-out switch: the spine costs ~1us per op when on; off, the
#: begin/mark calls return immediately (the microbench's OFF arm)
ENABLED = os.environ.get("KTPU_FLIGHTRECORDER", "1") != "0"
SPAN_CAPACITY = int(os.environ.get("KTPU_FLIGHTRECORDER_SPANS", "512"))
MARK_CAPACITY = int(os.environ.get("KTPU_FLIGHTRECORDER_MARKS", "4096"))
#: where degraded-health / SIGUSR1 dumps land
DUMP_DIR = os.environ.get("KTPU_FLIGHTRECORDER_DIR", ".")


class BatchSpan:
    """One dispatch's record. Mutated only by the thread currently
    driving the batch (dispatcher -> committer hand-off; the async bulk
    bind bumps ``conflicts`` last). Lives in the ring from begin, so a
    dump mid-flight shows the batch in its current state."""

    __slots__ = (
        "batch_id", "t_start", "t_end", "size", "padded", "tier",
        "carry", "delta_rows", "stages", "placed", "no_node",
        "gang_masked", "spilled", "volume_retries", "conflicts",
        "routed", "pods", "thread", "extra",
    )

    def __init__(self, batch_id: int, size: int, pods) -> None:
        self.batch_id = batch_id
        self.t_start = time.perf_counter()
        self.t_end: Optional[float] = None
        self.size = size
        self.padded: Optional[int] = None
        self.tier: Optional[str] = None
        self.carry: Optional[str] = None
        self.delta_rows = 0
        self.stages: Dict[str, float] = {}
        self.placed = 0
        self.no_node = 0
        self.gang_masked = 0
        self.spilled = 0
        self.volume_retries = 0
        self.conflicts = 0
        self.routed: Optional[str] = None  # non-device disposition
        #: (pod uid, queue-wait seconds, attempt count) per pod
        self.pods: List[Tuple[str, float, int]] = pods
        self.thread = threading.current_thread().name
        self.extra: Optional[dict] = None

    def stage(self, name: str, seconds: float) -> None:
        """Accumulate one stage's wall clock."""
        self.stages[name] = self.stages.get(name, 0.0) + seconds

    def note(self, **fields) -> None:
        for k, v in fields.items():
            if k in BatchSpan.__slots__:
                setattr(self, k, v)
            else:
                if self.extra is None:
                    self.extra = {}
                self.extra[k] = v

    def bump(self, field: str, n: int = 1) -> None:
        setattr(self, field, getattr(self, field) + n)

    def finish(self, tier: Optional[str] = None,
               routed: Optional[str] = None) -> None:
        if tier is not None:
            self.tier = tier
        if routed is not None:
            self.routed = routed
        self.t_end = time.perf_counter()

    def __bool__(self) -> bool:
        return True

    def to_dict(self) -> dict:
        # copy the mutable members first: a dump can run concurrently
        # with the owning thread still stamping stages (mid-flight
        # batch on the debug endpoint / SIGUSR1 path) -- iterating the
        # live dicts would raise "changed size during iteration"
        stages = dict(self.stages)
        pods = list(self.pods)
        extra = dict(self.extra) if self.extra else None
        d = {
            "batch_id": self.batch_id,
            "t_start": self.t_start,
            "t_end": self.t_end,
            "duration_ms": (
                round((self.t_end - self.t_start) * 1000.0, 3)
                if self.t_end is not None else None
            ),
            "size": self.size,
            "padded": self.padded,
            "tier": self.tier,
            "carry": self.carry,
            "delta_rows": self.delta_rows,
            "stages_ms": {
                k: round(v * 1000.0, 3) for k, v in stages.items()
            },
            "placed": self.placed,
            "no_node": self.no_node,
            "gang_masked": self.gang_masked,
            "spilled": self.spilled,
            "volume_retries": self.volume_retries,
            "conflicts": self.conflicts,
            "routed": self.routed,
            "thread": self.thread,
            "pods": [
                {"uid": uid, "queue_wait_ms": round(w * 1000.0, 3),
                 "attempts": att}
                for uid, w, att in pods
            ],
        }
        if extra:
            d["extra"] = extra
        return d


class _NullSpan:
    """The compiled-out span: every spine call is a no-op attribute
    access. Falsy so callers can branch on it cheaply."""

    __slots__ = ()

    #: what a stage's ``batch`` stat reads when the ring is off
    batch_id = 0

    def stage(self, name, seconds):
        pass

    def note(self, **fields):
        pass

    def bump(self, field, n=1):
        pass

    def finish(self, tier=None, routed=None):
        pass

    def __bool__(self) -> bool:
        return False


NULL_SPAN = _NullSpan()


class FlightRecorder:
    """The bounded ring of spans + marks. One process-global instance
    (``RECORDER``); chaos harnesses may construct private ones."""

    def __init__(self, span_capacity: int = SPAN_CAPACITY,
                 mark_capacity: int = MARK_CAPACITY) -> None:
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=span_capacity)
        self._marks: deque = deque(maxlen=mark_capacity)
        self._next_id = 0

    def begin_batch(self, size: int, pods=()) -> BatchSpan:
        """Allocate the next batch id and enter the span into the ring
        immediately (a mid-flight dump must show in-flight batches)."""
        with self._lock:
            self._next_id += 1
            span = BatchSpan(self._next_id, size, list(pods))
            self._spans.append(span)
        return span

    def mark(self, kind: str, /, **fields) -> None:
        """One timestamped control-plane event (breaker transition,
        fallback, fault fired, fencing abort, takeover, recompile...).
        ``kind`` is positional-only so a field may also be named
        ``kind``; the event kind wins in the dump."""
        self._marks.append((time.perf_counter(), kind, fields))

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._marks.clear()
            self._next_id = 0

    # -- dumps ---------------------------------------------------------

    def dump(self) -> dict:
        """Snapshot the rings as plain data (JSON-serializable)."""
        with self._lock:
            spans = list(self._spans)
            marks = list(self._marks)
        return {
            "dumped_at": time.time(),
            "perf_counter": time.perf_counter(),
            "next_batch_id": self._next_id,
            "spans": [s.to_dict() for s in spans],
            "marks": [
                # event kind last: it wins over a field named "kind"
                {**fields, "t": t, "kind": kind}
                for t, kind, fields in marks
            ],
        }

    def dump_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.dump(), indent=indent, default=str)

    def dump_to_file(self, reason: str) -> str:
        """Write the dump next to the process (KTPU_FLIGHTRECORDER_DIR)
        and return the path; failures log, never raise (the recorder
        must not take down the path that tripped it)."""
        path = os.path.join(
            DUMP_DIR,
            f"flightrecorder-{int(time.time())}-{reason}.json",
        )
        try:
            with open(path, "w") as f:
                f.write(self.dump_json(indent=1))
            logger.warning("flight recorder dumped to %s (%s)", path, reason)
        except Exception:  # noqa: BLE001 - never take down the caller
            logger.exception("flight recorder dump to %s failed", path)
        return path


RECORDER = FlightRecorder()


def begin_batch(size: int, pods=()) -> BatchSpan:
    if not ENABLED:
        return NULL_SPAN  # type: ignore[return-value]
    return RECORDER.begin_batch(size, pods)


def mark(kind: str, /, **fields) -> None:
    # zero-length, so a recompile, fallback or breaker transition shows
    # in a profiler trace where it happened (marks are rare)
    with TraceAnnotation("sched/mark/" + kind):
        pass
    if not ENABLED:
        return
    RECORDER.mark(kind, **fields)


def name_thread() -> None:
    """Give the calling thread its Python name at the OS too (Linux
    keeps 15 bytes): the profiler names a trace's host line after the
    OS thread, and all of them read ``python3`` otherwise. Called once
    where the informer, dispatcher, committer and bind-pool threads
    start; never for the main thread, whose name is the process's."""
    thread = threading.current_thread()
    if thread is threading.main_thread():
        return
    try:
        _prctl()(15, thread.name.encode()[:15], 0, 0, 0)  # PR_SET_NAME
    except (OSError, AttributeError):  # no libc or no prctl: a label only
        pass


@functools.lru_cache(maxsize=None)
def _prctl():
    prctl = ctypes.CDLL(None).prctl
    prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong]
    prctl.restype = ctypes.c_int
    return prctl


def dump_on_degraded(reason: str) -> Optional[str]:
    """Called wherever a component sets the degraded-health gauge: the
    moment something goes degraded is exactly when the last-K record is
    worth keeping."""
    if not ENABLED:
        return None
    RECORDER.mark("degraded", reason=reason)
    return RECORDER.dump_to_file(reason)


# -- the stage primitive ---------------------------------------------------


class StageTotals:
    """Always-on seconds and calls per stage. Per-THREAD dicts merged at
    read: the dispatcher, the committer, the bind pool and the informer
    threads accumulate without sharing a read-modify-write, and the lock
    is taken once per thread, to register its dict."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        self._dicts: List[dict] = []

    def add(self, name: str, seconds: float) -> None:
        try:
            rec = self._local.d[name]
        except (AttributeError, KeyError):
            rec = self._first(name)
        rec[0] += seconds
        rec[1] += 1

    def _first(self, name: str) -> list:
        """This thread's first add, or its first of this stage."""
        d = getattr(self._local, "d", None)
        if d is None:
            d = self._local.d = {}
            with self._lock:
                self._dicts.append(d)
        rec = d[name] = [0.0, 0]
        return rec

    def _merged(self, field: int) -> dict:
        # list() of a dict's items is atomic under the GIL, so a
        # concurrent add never corrupts the merge -- at worst the
        # freshest increment lands in the next read
        with self._lock:
            items = [list(d.items()) for d in self._dicts]
        out: dict = {}
        for pairs in items:
            for name, rec in pairs:
                out[name] = out.get(name, 0) + rec[field]
        return out

    def seconds(self) -> dict:
        return self._merged(0)

    def calls(self) -> dict:
        return self._merged(1)


#: three totals keep the names the benchmark and the ring's ``stages_ms``
#: have always read; the trace says what they are (``device_solve`` is
#: the dispatch alone: JAX returns before the device finishes, and
#: ``download`` is the wait for it plus the copy)
_SPAN_NAMES = {
    "pop_batch": "pop",
    "device_solve": "solve_dispatch",
    "download": "solve_wait",
}

#: does a profiler session run? What only a session reads (a span, its
#: stats, ``cpu_ms``, ``waited_ms``) is built only then
tracing = TraceAnnotation.is_enabled
_clock = time.perf_counter
_cpu_clock = time.thread_time


def handoff_wait(since: float) -> dict:
    """The stat a receiving thread puts on its span for what another
    thread handed it at ``since`` (``time.perf_counter()``; 0.0: not
    stamped): how long the item waited for this thread. Nothing with no
    session, which alone would read it; the sender's stamp is one clock
    read a batch or transaction (0.09 us), no dearer than asking."""
    if not since or not tracing():
        return {}
    return {"waited_ms": round((_clock() - since) * 1e3, 3)}


class stage:
    """``with stage("pack", span, totals, **stats):`` times one stage,
    once: the wall clock's seconds go to ``totals`` (when given) and to
    the batch's ``span`` (when given), and while a profiler session runs
    the block is a ``sched/<name>`` span of it
    (``jax.profiler.TraceAnnotation``; ``_SPAN_NAMES`` renames three),
    with the span's ``batch`` id and ``stats`` on it (``set_metadata``
    adds what is known only later). With no session nothing of the
    annotation is built.

    While a session runs, a stage that is given ``totals`` is clocked a
    second time, on its thread's own CPU clock (``time.thread_time``),
    and the span carries the difference as ``cpu_ms``, so a stage tells
    its work from its waiting. Only while a session runs, because that
    clock is a system call of 5.7 us on the chip's host (0.3 on a
    developer's): read twice a stage, always, it cost the open-loop cell
    4 % of its median pod-to-bind (PERF.md section 6, PR 37). So the
    span is the only place the CPU time goes: a sum over a window is a
    sum of ``cpu_ms`` over the trace's spans. A stage without totals
    (the trace-only ``dispatch`` and ``commit.gather`` / ``.clone`` /
    ``.assume``) is never clocked: that is the rule, and no site takes
    the clock by hand.
    ``seconds`` holds the duration after exit. Spans are per batch, per
    informer frame, per bulk bind and per collection, never per pod."""

    __slots__ = ("name", "span", "totals", "seconds",
                 "_stats", "_trace", "_t0", "_c0")

    def __init__(self, name: str, span=NULL_SPAN,
                 totals: Optional[StageTotals] = None, **stats) -> None:
        self.name = name
        self.span = span
        self.totals = totals
        self._stats = stats
        self._trace = None
        self.seconds = 0.0

    def __enter__(self) -> "stage":
        if tracing():
            name, span, stats = self.name, self.span, self._stats
            if span is not NULL_SPAN:
                stats["batch"] = span.batch_id
            self._trace = TraceAnnotation(
                "sched/" + _SPAN_NAMES.get(name, name), **stats
            ).__enter__()
            if self.totals is not None:
                self._c0 = _cpu_clock()
        self._t0 = _clock()
        return self

    def set_metadata(self, **stats) -> None:
        if self._trace is not None:
            self._trace.set_metadata(**stats)

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = seconds = _clock() - self._t0
        trace, totals = self._trace, self.totals
        if trace is not None:
            if totals is not None:
                trace.set_metadata(
                    cpu_ms=round((_cpu_clock() - self._c0) * 1e3, 3)
                )
            trace.__exit__(exc_type, exc, tb)
        # the totals last: who waits for a total finds its span closed
        if totals is not None:
            totals.add(self.name, seconds)
        if self.span is not NULL_SPAN:
            self.span.stage(self.name, seconds)
