"""``arrivals``' open loop on a cluster whose nodes are rolled: while
pods arrive on ``arrivals``' own schedule, nodes of the general pool are
replaced under their own names, one started every ``1 / nodes_per_s``
seconds, as an in-place node-image upgrade does:

``cordon`` -> the node's pods listed, bulk-deleted, and as many pods of
the mix's class created at that instant, due then (a controller's
replacements: pods of the window, timed like any other) -> the deletes
seen by the watch -> ``remove`` -> ``join`` under the same name, not
Ready, with an empty image list (at once; ``rejoin_after_s``, where a
roll's parameters name it, as warm-up's do, puts an interval between the
two) -> ``ready_after_s`` later ``ready`` -> ``report_status`` with the
node's first image once a pod is bound there, or ``first_image.after_s``
after Ready.

Beside the rolls, ``kubelet_reports_per_s`` status writes a second that
change nothing of the spec, round-robin over every node.

The node ops run on a thread of their own, paced by the clock and never
by the scheduler: a step that runs late is run all the same and its
lateness is logged. What a drain has to list, the pods of one node, the
in-process apiserver has no index for, so the generator keeps its own
from a Pod watch of its own (``PodsByNode``), as a controller's informer
does.

What it leaves on the ``Run`` for the cell's comparison and readers:
``run.rolls``, one record a roll (``node``, ``warmup``, the names it
``drained`` and their ``replacements``).
"""

from __future__ import annotations

import heapq
import threading
import time

import numpy as np

from chipbench.generators import arrivals
from chipbench.harness import BenchError

MIB = 1 << 20
REDO_APP = "redo"  # the app label of a drained pod's replacement


class PodsByNode:
    """node name -> names of the pods bound there, from a list and a Pod
    watch of the generator's own."""

    def __init__(self, server) -> None:
        self._server = server
        self._lock = threading.Lock()
        self._on: dict = {}  # node -> set of pod names
        self._node_of: dict = {}  # pod name -> node
        self._stop = False
        self._open()
        self._thread = threading.Thread(
            target=self._run, name="chipbench-roll-pods", daemon=True
        )
        self._thread.start()

    def _open(self) -> None:
        pods, rv = self._server.list("Pod")
        self._watch = self._server.watch("Pod", since_rv=rv)
        with self._lock:
            self._on.clear()
            self._node_of.clear()
            for pod in pods:
                if pod.spec.node_name:
                    self._bound(pod.metadata.name, pod.spec.node_name)

    def _bound(self, name: str, node: str) -> None:
        if name not in self._node_of:
            self._node_of[name] = node
            self._on.setdefault(node, set()).add(name)

    def _run(self) -> None:
        while not self._stop:
            try:
                events = self._watch.next_batch(timeout=0.2)
            except Exception:  # noqa: BLE001 - 410 Gone: list again
                if self._stop:
                    return
                self._open()
                continue
            if not events:
                continue
            with self._lock:
                for ev in events:
                    name = ev.object.metadata.name
                    if ev.type == "DELETED":
                        node = self._node_of.pop(name, None)
                        if node is not None:
                            self._on[node].discard(name)
                    elif ev.object.spec.node_name:
                        self._bound(name, ev.object.spec.node_name)

    def on(self, node: str) -> list:
        with self._lock:
            return sorted(self._on.get(node, ()))

    def stop(self) -> None:
        self._stop = True
        self._watch.stop()
        self._thread.join(timeout=5)


def general_pool(run) -> list:
    """The nodes outside the ballast pool, by row: what may be rolled."""
    return [name for name, row in sorted(run.node_rows.items(),
                                         key=lambda kv: kv[1])
            if not run.in_ballast_pool(row)]


def draw(run, count: int) -> list:
    """``count`` nodes of the general pool that no roll of this run has
    taken, by the run's seed; fewer where the pool runs out."""
    taken = {roll["node"] for roll in run.rolls}
    free = [n for n in general_pool(run) if n not in taken]
    count = min(count, len(free))
    return [free[int(k)] for k in run.rng.permutation(len(free))[:count]]


class Roller:
    """The thread that writes the nodes. ``steps`` is a heap of
    ``(offset from the start, serial, kind, roll or None)``."""

    def __init__(self, run, params: dict, spec: dict, pods: PodsByNode,
                 starts: list, reports_per_s: float, warmup: bool) -> None:
        self.run, self.params, self.spec = run, params, spec
        self.pods = pods
        self.warmup = warmup
        self.late_ms: list = []  # (lateness, kind, node) of every step
        self.reports = self.reports_skipped = 0
        self.error = None
        self._steps: list = []
        self._serial = 0
        self._stop = threading.Event()
        self._absent: set = set()  # between remove and join
        self._open = len(starts)  # rolls that have not reported an image
        for offset, node in starts:
            roll = {"node": node, "warmup": warmup, "drained": [],
                    "replacements": []}
            run.rolls.append(roll)
            self._push(offset, "start", roll)
        if reports_per_s > 0:
            self._every = 1.0 / reports_per_s
            self._round = sorted(run.node_rows, key=run.node_rows.get)
            self._push(self._every, "report", None)
        self._thread = threading.Thread(
            target=self._run, name="chipbench-roll", daemon=True
        )

    def _push(self, offset: float, kind: str, roll) -> None:
        self._serial += 1
        heapq.heappush(self._steps, (offset, self._serial, kind, roll))

    def start(self) -> None:
        self.t0 = self.run.now()
        if self.spec.get("first_before_arrivals") and self._steps and (
            self._steps[0][2] == "start"
        ):
            # the first roll's cordon, drain and remove before the first
            # pod arrives: the first batch then meets a change of
            # membership, whatever the machine's speed
            offset, _, _, roll = heapq.heappop(self._steps)
            self._start(offset, roll)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def finish(self, timeout_s: float) -> None:
        """Every started roll is finished; the kubelets' reports end."""
        deadline = self.run.now() + timeout_s
        while self._thread.is_alive() and self._open:
            if self.run.now() > deadline:
                break
            time.sleep(0.01)
        self.stop()
        self._thread.join(timeout=timeout_s)
        if self.error is not None:
            raise self.error
        if self._open:
            raise BenchError(
                f"{self._open} rolls were not finished {timeout_s}s after "
                "the arrivals ended"
            )

    def _run(self) -> None:
        try:
            while self._steps and not self._stop.is_set():
                offset, _, kind, roll = self._steps[0]
                wait = self.t0 + offset - self.run.now()
                if wait > 0:
                    self._stop.wait(min(wait, 0.05))
                    continue
                heapq.heappop(self._steps)
                self.late_ms.append(
                    (-wait * 1e3, kind, roll["node"] if roll else "")
                )
                getattr(self, "_" + kind)(offset, roll)
        except Exception as e:  # noqa: BLE001 - raised by ``finish``
            self.error = e

    # -- the steps -----------------------------------------------------------

    def _drain(self, roll: dict) -> None:
        """The pods the index shows on the node: deleted in bulk, as many
        of the class created at that instant, the deletes awaited."""
        run = self.run
        theirs = self.pods.on(roll["node"])
        if not theirs:
            return
        run.harness_deleted.update(theirs)  # before the first event
        run.client.delete_pods_bulk([("default", n) for n in theirs])
        fresh = run.make_pods(self.params["class"], len(theirs), REDO_APP)
        run.create(fresh)
        roll["drained"] += theirs
        roll["replacements"] += [p.metadata.name for p in fresh]
        if not run.watcher.wait_deleted(
            theirs, run.now() + float(self.spec["drain_timeout_s"])
        ):
            raise BenchError(
                f"the watch did not show {roll['node']}'s drained pods "
                f"deleted within {self.spec['drain_timeout_s']}s"
            )

    def _start(self, offset: float, roll: dict) -> None:
        ops = self.run.node_ops
        ops.cordon(roll["node"])
        self._drain(roll)
        ops.remove(roll["node"])
        after = float(self.spec.get("rejoin_after_s", 0.0))
        if after > 0:
            self._absent.add(roll["node"])
            self._push(offset + after, "join", roll)
        else:
            self._join(offset, roll)

    def _join(self, offset: float, roll: dict) -> None:
        # a bind that was on its way when the node was cordoned landed on
        # a node that has gone since: the pod goes with it, as the pod
        # garbage collector has it, and is replaced like the others
        self._drain(roll)
        self.run.node_ops.join(roll["node"])
        self._absent.discard(roll["node"])
        self._push(offset + float(self.spec["ready_after_s"]), "ready", roll)

    def _ready(self, offset: float, roll: dict) -> None:
        roll["image_by"] = offset + float(self.spec["first_image"]["after_s"])
        self.run.node_ops.ready(roll["node"])
        self._push(offset + 0.02, "image", roll)

    def _image(self, offset: float, roll: dict) -> None:
        if offset < roll["image_by"] and not self.pods.on(roll["node"]):
            self._push(offset + 0.02, "image", roll)
            return
        image = self.spec["first_image"]
        self.run.node_ops.report_status(
            roll["node"], images=[(image["name"], image["size_mib"] * MIB)]
        )
        self._open -= 1

    def _report(self, offset: float, roll) -> None:
        name = self._round[self.reports % len(self._round)]
        self.reports += 1
        if name in self._absent:
            self.reports_skipped += 1  # no kubelet runs there just now
        else:
            self.run.node_ops.report_status(name)
        self._push(offset + self._every, "report", None)


def note(run, roller: Roller, what: str) -> None:
    rolls = [r for r in run.rolls if r["warmup"] == roller.warmup]
    late = np.array([ms for ms, kind, _ in roller.late_ms] or [0.0])
    print(f"nodes rolled ({what}): {len(rolls)} replaced under their own "
          f"names, {sum(len(r['drained']) for r in rolls)} pods drained and "
          f"offered again, {roller.reports} kubelet reports "
          f"({roller.reports_skipped} skipped: node absent); "
          f"{len(roller.late_ms)} steps late by p50 "
          f"{np.percentile(late, 50):.1f} p99 {np.percentile(late, 99):.1f} "
          f"max {late.max():.1f} ms", flush=True)
    for ms, kind, node in sorted(roller.late_ms, reverse=True)[:5]:
        if ms > 100.0:
            print(f"nodes rolled: late step {kind} {node} by {ms:.0f} ms",
                  flush=True)


def program_side(run, seconds0: dict, calls0: dict) -> None:
    """What the program's always-on totals say of the window's node
    writes (a program from before a stage has no such total): the node
    handlers, and the dispatcher's wait for the batches in flight to
    mirror before a change of membership is scattered."""
    sched = run.sched
    seconds, calls = dict(sched.stage_seconds), sched.stage_totals.calls()
    parts = []
    for stage in ("node_event", "mirror_wait"):
        if stage in seconds:
            n = calls[stage] - calls0.get(stage, 0)
            ms = (seconds[stage] - seconds0.get(stage, 0.0)) * 1e3
            parts.append(f"{stage} {n} calls, {ms:.1f} ms in all")
    print("nodes rolled, the program's side: " + ("; ".join(parts) or
          "no node_event or mirror_wait total") + "; membership_row_patches "
          f"{sched.membership_row_patches}", flush=True)


# -- the generator's three functions ------------------------------------------


def warmup(run, params: dict) -> None:
    """``arrivals``' rounds, each with ``warmup_roll.nodes`` nodes rolled
    through every step while its pods arrive, each round deleted; then
    one small burst, deleted.

    Which program a batch runs depends on what it finds: whether a node
    holds an image (the score family is then live and the constrained
    kernel runs), whether the resident carry is reused or uploaded whole
    (after a round's delete), and whether a change of membership is
    pending at an upload (the static state is then uploaded too). Every
    combination the window can meet has to run here first, on any
    machine, a cold compile cache included. So the first roll of a round
    begins before the round's first pod: round two's first batch finds
    round one's delete, a removed node and round one's images; the burst
    after round two finds round two's delete and no change of
    membership, as the window's first batch does. The waits here are
    ``warmup_timeout_s``, not a pod's deadline: a cold compile of the
    constrained kernel holds a round for tens of seconds."""
    run.rolls = []
    run.roll_pods = PodsByNode(run.server)
    spec = dict(params["roll"], **params["warmup_roll"])
    timeout = float(params["warmup_timeout_s"])
    try:
        for _ in range(params["warmup_rounds"]):
            offs = arrivals.offsets(
                params["rate"], params["warmup_seconds"], params["gap_seed"],
                run.rng,
            )
            pods = run.make_pods(params["class"], len(offs), "warm")
            names = [p.metadata.name for p in pods]
            starts = [
                (spec["start_s"] + k / float(spec["nodes_per_s"]), node)
                for k, node in enumerate(draw(run, int(spec["nodes"])))
            ]
            roller = Roller(run, params, spec, run.roll_pods, starts,
                            float(params["kubelet_reports_per_s"]), True)
            roller.start()
            arrivals._offer(run, params, pods, offs)
            roller.finish(timeout)
            redo = [n for r in run.rolls for n in r["replacements"]]
            _settled(run, names + redo, timeout, params)
            note(run, roller, "warm-up")
        burst = run.make_pods(
            params["class"], int(params["warmup_burst_pods"]), "warm")
        run.create(burst)
        _settled(run, [p.metadata.name for p in burst], timeout, params)
    except BaseException:
        run.roll_pods.stop()
        raise


def _settled(run, names: list, timeout: float, params: dict) -> None:
    """Warm-up pods bound, then deleted (those a drain has not already)."""
    if not run.wait_bound(names, timeout):
        raise BenchError(
            f"warm-up pods were not all bound after {timeout}s")
    run.delete(
        [n for n in names if n not in run.watcher.deleted_time],
        params["delete_timeout_s"],
    )


def roll_starts(run, spec: dict, seconds: float) -> list:
    """``(offset, node)`` of every roll of a window of ``seconds``: one
    every ``1 / nodes_per_s`` from ``start_s``, none in the window's last
    ``quiet_last_s``, the nodes drawn by the run's seed."""
    every = 1.0 / float(spec["nodes_per_s"])
    last = seconds - float(spec["quiet_last_s"])
    count = max(int((last - spec["start_s"]) / every + 1e-9) + 1, 0)
    return [(spec["start_s"] + k * every, node)
            for k, node in enumerate(draw(run, count))]


def prepare(run, params: dict, seconds: float):
    starts = roll_starts(run, params["roll"], seconds)
    return arrivals.prepare(run, params, seconds), starts


def window(run, params: dict, prepared, seconds: float) -> None:
    offered, starts = prepared
    roller = Roller(run, params, params["roll"], run.roll_pods, starts,
                    float(params["kubelet_reports_per_s"]), False)
    seconds0 = dict(run.sched.stage_seconds)
    calls0 = run.sched.stage_totals.calls()
    try:
        roller.start()
        arrivals.window(run, params, offered, seconds)
        roller.finish(params["deadline_s"])
        redo = [n for r in run.rolls if not r["warmup"]
                for n in r["replacements"]]
        with run.phase("drain_tail"):
            run.wait_bound(redo, params["deadline_s"])
        note(run, roller, "window")
        program_side(run, seconds0, calls0)
    finally:
        roller.stop()
        run.roll_pods.stop()
