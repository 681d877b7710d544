"""One run of one cell: build the cluster, warm up with the cell's own
traffic, measure for ``--seconds``, check, print the result line.

The program is driven as an operator drives it: ``load_config_from_dict``
-> ``new_scheduler_from_config`` over the in-process ``APIServer``,
``InformerFactory`` and ``Client``; pods and nodes through
``kubernetes_tpu.testing``; ``sched.start()``. ``sched.warmup()`` is never
called: the operator's binary does not call it, so set-up here is what a
cold start costs an operator. What the run reads from the program are
its counters (``stage_seconds``, ``batches_solved``, ``solves_by_tier``,
``pods_fallback``, the preemptor's and the like) and nothing else.
What decides ``correct`` is the comparisons the configuration names
(``check.py``), found by name under ``checks/`` as generators and
readers are.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

from chipbench import check, tracing
from chipbench.watcher import BindWatcher

ROOT = Path(__file__).resolve().parent.parent
ZONE_KEY = "topology.kubernetes.io/zone"
POOL_KEY = "chipbench/pool"  # "ballast" on the ballast pool's nodes
HALF_KEY = "chipbench/half"  # "0" / "1": the pool's two halves
HOST_KEY = "kubernetes.io/hostname"
CREATE_CHUNK = 256
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TIERS = ("pallas", "xla", "host_greedy", "sequential")  # best first
#: the keys of a pod class that ``Run.make_pods`` reads; a named
#: comparison adds its own (``POD_CLASS_KEYS`` of its module)
POD_CLASS_KEYS = ("cpu_milli", "memory_mib", "spread", "anti_affinity",
                  "priority")
#: the preemptor's counters that every run's ``Run.counters`` carries
PREEMPTOR_COUNTERS = ("device_preemptions", "host_preemptions",
                      "preempt_waves", "budget_denials")
#: the program's tier ledgers by the name a configuration's
#: ``expect_tiers`` gives them (``batch`` is the one ``expect_tier``
#: names): where the ledger lives, and the counter of ``Run.counters``,
#: if any, of the pods that fell through every tier of it
LEDGERS = {
    "batch": (lambda sched: sched.ladder.solves_by_tier, None),
    "preempt_wave": (
        lambda sched: sched.preemptor.ladder.solves_by_tier,
        "host_preemptions",  # the host oracle books no tier
    ),
}


class BenchError(Exception):
    """The run cannot produce a result."""


_COMPILES = []  # one entry for each program JAX handed to the backend
_LISTENING = []  # non-empty once the listener is on


def _on_duration(event: str, _seconds: float, **kw) -> None:
    if event == COMPILE_EVENT:
        _COMPILES.append(str(kw.get("fun_name", "?")))


def compile_events() -> int:
    """Programs compiled (or fetched from the persistent cache) in this
    process so far, counted from JAX's own monitoring events; JAX offers
    no way to take a listener off, so it goes on once."""
    import jax

    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING.append(True)
    return len(_COMPILES)


# -- the cell's data ---------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _overlay(out[key], value)
        else:
            out[key] = value
    return out


def load_cell(root: Path, workload: str, rehearsal: bool) -> dict:
    """Everything the cell names, found by the names ``BENCHMARK.json``
    gives. ``rehearsal`` lays each file's ``rehearsal`` block over it:
    the tiny sizes a CPU finishes."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(
            f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}"
        )
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(root / entry["file"])
    mix = load_json(root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
    if rehearsal:
        config = _overlay(config, config.get("rehearsal", {}))
        mix = _overlay(mix, mix.get("rehearsal", {}))
    check.validate(config)

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": config,
        "mix": mix,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# -- the stack under test ----------------------------------------------------


class Run:
    """One scheduler stack and what the harness knows about its own
    traffic. The generators drive it through the methods below."""

    def __init__(self, cell: dict, seed: int) -> None:
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.informer import InformerFactory
        from kubernetes_tpu.config.loader import load_config_from_dict
        from kubernetes_tpu.scheduler.scheduler import (
            new_scheduler_from_config,
        )

        self.config = cell["config"]
        self.mix = cell["mix"]
        self.rng = np.random.default_rng(seed)
        self.server = APIServer()
        self.client = Client(self.server)
        self.informers = InformerFactory(self.server)
        self.sched = new_scheduler_from_config(
            self.client, self.informers,
            load_config_from_dict(self.config["wire"]),
        )
        self.max_batch = int(self.config["wire"]["tpuSolver"]["maxBatch"])
        self.watcher = None
        #: pod name -> class name, as THIS harness created it: the
        #: replay's own source of truth
        self.created: dict = {}
        self.prebound: set = set()  # created bound; never scheduled
        #: names this harness deleted itself (``delete``); a pod the
        #: watch saw deleted that is not here left otherwise (``evicted``)
        self.harness_deleted: set = set()
        #: this run's counters as the window opened; the tier comparison
        #: reads every ledger against them
        self.window_counters: dict = {}
        self.due: dict = {}  # name -> perf_counter instant it was due
        self.issued: dict = {}  # name -> instant its create call began
        self.phases: list = []  # (name, start, end) host clock
        self.waves: list = []  # dicts: start, names, drain_s
        self.snapshots: list = []  # {pod name: node} read from the API
        self.window_names: list = []
        self.in_window = False
        self.window_start = 0.0  # perf_counter when the window opened
        self.window_end = 0.0  # ... and when the generator gave it back
        self._serial = 0

    # -- set-up ------------------------------------------------------------

    def build_cluster(self) -> None:
        from kubernetes_tpu.testing import make_node

        cluster = self.config["cluster"]
        shape = cluster["node"]
        nodes = [
            make_node(f"node-{i}")
            .capacity(
                cpu=shape["cpu"], memory=shape["memory"], pods=shape["pods"]
            )
            .label(ZONE_KEY, f"zone-{i % cluster['zones']}")
            .label(HOST_KEY, f"node-{i}")
            .label(POOL_KEY, "ballast" if self.in_ballast_pool(i) else "general")
            .label(HALF_KEY, str(self.pool_half(i)))
            .obj()
            for i in range(cluster["nodes"])
        ]
        for i in range(0, len(nodes), CREATE_CHUNK):
            self.server.create_bulk(nodes[i:i + CREATE_CHUNK])
        ballast = self.ballast_pods()
        for i in range(0, len(ballast), CREATE_CHUNK):
            self.server.create_bulk(ballast[i:i + CREATE_CHUNK])
        self.informers.start()
        self.informers.wait_for_cache_sync()
        self.watcher = BindWatcher(self.server)
        self.sched.start()
        init = cluster["init_pods"]
        pods = self.make_pods(init["class"], init["count"], "init")
        self.create(pods)
        if not self.wait_bound(
            [p.metadata.name for p in pods], self.config["setup_timeout_s"]
        ):
            raise BenchError("init pods did not all bind during set-up")

    def in_ballast_pool(self, i: int) -> bool:
        cluster = self.config["cluster"]
        spec = cluster.get("ballast")
        return bool(spec) and i // cluster["zones"] < spec["per_zone"]

    def pool_half(self, i: int) -> int:
        """Which half of the pool node ``i`` is in: alternate rows of the
        ballast grid, so that each half keeps every step of the first
        ballast class."""
        cluster = self.config["cluster"]
        grid = cluster.get("ballast", {}).get("grid", 1)
        return i // cluster["zones"] // grid % 2

    def ballast_pods(self) -> list:
        """Pods that already run when the scheduler starts, created
        bound (``spec.nodeName`` set), on the first ``per_zone`` nodes of
        every zone (labelled ``chipbench/pool=ballast``): the ``j``-th
        such node of a zone carries ``j % grid`` pods of the first
        ballast class and ``(j // grid) % grid`` of the second. They make
        the pool's nodes differ in score, which is what lets a check wave
        sent there tell one scoring precision from another."""
        cluster = self.config["cluster"]
        spec = cluster.get("ballast")
        if not spec:
            return []
        grid = spec["grid"]
        first, second = spec["classes"]
        pods = []
        for i in range(cluster["zones"] * spec["per_zone"]):
            j = i // cluster["zones"]
            for cls, count in ((first, j % grid), (second, (j // grid) % grid)):
                made = self.make_pods(
                    cls, count, f"ballast{i}", node=f"node-{i}"
                )
                self.prebound.update(p.metadata.name for p in made)
                pods += made
        return pods

    # -- what generators call ------------------------------------------------

    def now(self) -> float:
        return time.perf_counter()

    @contextlib.contextmanager
    def phase(self, name: str):
        """A span of the harness's own, on the host clock. The trace's
        reduction lays them over the device's idle gaps."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases.append((name, t0, time.perf_counter()))

    def make_pods(self, cls_name: str, count: int, app: str,
                  selector: dict = None, node: str = None) -> list:
        """``count`` identical pods of one class under one app label;
        ``selector`` is a node selector, ``node`` creates them already
        bound."""
        from kubernetes_tpu.testing import make_pod

        cls = self.config["pod_classes"][cls_name]
        self._serial += 1
        pods = []
        for i in range(count):
            name = f"{app}-{self._serial}-{i}"
            w = make_pod(name).container(
                cpu=f"{cls['cpu_milli']}m", memory=f"{cls['memory_mib']}Mi"
            ).labels(app=app)
            if "spread" in cls:
                w = w.spread_constraint(
                    max_skew=cls["spread"]["max_skew"],
                    topology_key=cls["spread"]["topology_key"],
                    when_unsatisfiable="DoNotSchedule",
                    match_labels={"app": app},
                )
            if "anti_affinity" in cls:
                w = w.pod_affinity(
                    cls["anti_affinity"]["topology_key"], {"app": app},
                    anti=True,
                )
            if "priority" in cls:
                w = w.priority(int(cls["priority"]))
            if selector:
                w = w.node_selector(**selector)
            if node is not None:
                w = w.node(node)
            self.created[name] = cls_name
            pods.append(w.obj())
        return pods

    def create(self, pods: list, due: float = None, threads: int = 1,
               chunk: int = CREATE_CHUNK, timed: bool = True) -> None:
        """Create ``pods`` through the API in bulk chunks. ``due`` is
        the instant they were due (default: now); each pod is timed from
        it, not from its own create call. ``timed=False`` keeps them out
        of the window's pods: what a generator puts back between waves
        and no user waits for."""
        start = time.perf_counter()
        dues = np.broadcast_to(start if due is None else due, (len(pods),))
        chunks = [pods[i:i + chunk] for i in range(0, len(pods), chunk)]
        for pod, t in zip(pods, dues.tolist()):
            self.due[pod.metadata.name] = t
        if self.in_window and timed:
            self.window_names.extend(p.metadata.name for p in pods)
        lock = threading.Lock()
        errors = []

        def work() -> None:
            while True:
                with lock:
                    if not chunks:
                        return
                    part = chunks.pop(0)
                t = time.perf_counter()
                try:
                    self.client.create_pods_bulk(part)
                except Exception as e:  # noqa: BLE001 - reported below
                    errors.append(e)
                    return
                for pod in part:
                    self.issued[pod.metadata.name] = t

        if threads <= 1:
            work()
        else:
            pool = [
                threading.Thread(target=work, name=f"chipbench-create-{k}")
                for k in range(threads)
            ]
            for t in pool:
                t.start()
            for t in pool:
                t.join()
        if errors:
            raise BenchError(f"create failed: {errors[0]!r}")

    def wait_bound(self, names, timeout_s: float) -> bool:
        return self.watcher.wait_bound(
            names, time.perf_counter() + timeout_s
        )

    def snapshot(self) -> dict:
        """pod name -> node of every bound pod, read back from the
        apiserver; kept for the replay after the window."""
        pods, _ = self.client.list_pods()
        snap = {
            p.metadata.name: p.spec.node_name
            for p in pods if p.spec.node_name
        }
        self.snapshots.append(snap)
        return snap

    def delete(self, names, timeout_s: float) -> None:
        """Delete pods in bulk and wait until the watch has seen each of
        them go, by name, and the scheduler's own cache no longer counts
        them."""
        before = self.sched.cache.pod_count()
        self.harness_deleted.update(names)  # before the first event
        keys = [("default", n) for n in names]
        gone = 0
        for i in range(0, len(keys), 1024):
            gone += self.client.delete_pods_bulk(keys[i:i + 1024])
        deadline = time.perf_counter() + timeout_s
        self.watcher.wait_deleted(names, deadline)
        while self.sched.cache.pod_count() > before - gone:
            if time.perf_counter() > deadline:
                raise BenchError(
                    "the scheduler still counts deleted pods after "
                    f"{timeout_s}s"
                )
            time.sleep(0.005)

    def evicted(self) -> dict:
        """name -> instant the watch saw it deleted, of every pod that
        left and that this harness did not delete: the client's side of
        an eviction, with no counter of the program trusted for it."""
        return {
            name: t for name, t in list(self.watcher.deleted_time.items())
            if name not in self.harness_deleted
        }

    def latencies_ms(self) -> list:
        """due -> bind event of every pod of the window that was bound."""
        bind = self.watcher.bind_time
        return [
            (bind[n] - self.due[n]) * 1e3
            for n in self.window_names if n in bind
        ]

    def record_wave(self, start: float, names: list) -> dict:
        """One wave's record; the generator adds the ``snapshot`` it
        reads from the apiserver before it deletes the wave."""
        times = sorted(self.watcher.bind_time[n] for n in names
                       if n in self.watcher.bind_time)
        wave = {
            "start": start, "pods": len(names), "names": names,
            "drain_s": times[-1] - start,
            # seconds after the start by which a quarter, a half, three
            # quarters and 99% of the wave were bound: a stall shows as
            # a step between two of them
            "bound_by_s": [
                times[max(len(times) * q // 100 - 1, 0)] - start
                for q in (25, 50, 75, 99)
            ],
            "in_window": self.in_window,
        }
        self.waves.append(wave)
        return wave

    # -- counters ----------------------------------------------------------

    def counters(self) -> dict:
        sched = self.sched
        preemptor = sched.preemptor
        return {
            "t": time.perf_counter(),
            "stage_seconds": dict(sched.stage_seconds),
            "batches": int(sched.batches_solved),
            "tiers": {name: dict(ledger(sched))
                      for name, (ledger, _) in LEDGERS.items()},
            "pods_fallback": int(sched.pods_fallback),
            # the preemptor's, in every run (0 where nothing preempts)
            "device_preemptions": int(preemptor.device_preemptions),
            "host_preemptions": int(preemptor.host_preemptions),
            "preempt_waves": int(preemptor.waves),
            "budget_denials": int(preemptor.budget_denials),
            "state_uploads": int(sched.state_uploads),
            "delta_rows_uploaded": int(sched.delta_rows_uploaded),
            "speculative_rewinds": int(sched.speculative_rewinds),
            "conflict_requeues": int(sched.conflict_requeues),
            "compiles": compile_events(),
            "pods_bound": len(self.watcher.bind_time),
        }

    def stop(self) -> None:
        if self.watcher is not None:
            self.watcher.stop()
        self.sched.stop()
        self.informers.stop()


# -- the device --------------------------------------------------------------


def configure_compile_cache(root: Path) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself);
    otherwise the cache sits at ``<checkout>/.jax_cache``, a fixed path:
    the directory is part of the cache key."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def find_device(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. Without ``--rehearsal`` anything
    but a TPU with enough chips ends the run."""
    import jax

    devices = jax.devices()
    device = {
        "platform": str(devices[0].platform),
        "kind": str(devices[0].device_kind),
        "count": len(devices),
    }
    if rehearsal:
        return device
    if device["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (platform {device['platform']})")
    if device["count"] < chips:
        raise BenchError(
            f"the cell asks for {chips} chip(s), JAX sees {device['count']}"
        )
    return device


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- one run -----------------------------------------------------------------


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def end_to_end(run: Run, setup_s: float) -> tuple:
    """The end-to-end metrics of the window, the pods attempted and the
    pods that missed their deadline."""
    latencies = run.latencies_ms()
    limit_ms = float(run.mix["params"]["deadline_s"]) * 1e3
    unbound = len(run.window_names) - sum(1 for v in latencies if v <= limit_ms)
    values = {"setup_s": setup_s}
    if latencies:
        values["pod_to_bind_p50_ms"] = percentile(latencies, 50)
        values["pod_to_bind_p99_ms"] = percentile(latencies, 99)
    window_s = run.window_end - run.window_start
    if latencies and window_s > 0:
        # all the work over all the time: every pod of the window that
        # was bound by its deadline, over the whole window, the gaps
        # between waves (delete, collect, building the next wave) and
        # the finishing of the last wave included
        values["bound_pods_per_s"] = (
            sum(1 for v in latencies if v <= limit_ms) / window_s
        )
    return values, len(run.window_names), unbound


def print_window_notes(run: Run, start: dict, end: dict, pauses: list) -> None:
    """Earlier lines for whoever reads a run: the collector's pauses in
    the window, every wave's drain, waves that stalled, and the
    program's upload and rewind counters."""
    for gen in (0, 1, 2):
        mine = [s for g, s in pauses if g == gen]
        print(f"gc in the window, generation {gen}: {len(mine)} "
              f"collections, {sum(mine):.3f}s, longest "
              f"{max(mine, default=0.0):.3f}s", flush=True)
    if run.waves:
        print("waves (drain s, * in the window): " + " ".join(
            f"{w['drain_s']:.3f}{'*' if w['in_window'] else ''}"
            for w in run.waves
        ), flush=True)
    drains = sorted(w["drain_s"] for w in run.waves)
    for k, w in enumerate(run.waves):
        if w["drain_s"] > 3 * drains[len(drains) // 2]:
            print(f"slow wave {k}: drained in {w['drain_s']:.3f}s; 25%, "
                  "50%, 75% and 99% of its pods were bound by " + " ".join(
                      f"{t:.3f}s" for t in w["bound_by_s"]), flush=True)
    print(f"programs compiled in the window: "
          f"{_COMPILES[start['compiles']:end['compiles']]}", flush=True)
    print("counters over the window: " + ", ".join(
        f"{key} {end[key] - start[key]}" for key in (
            "state_uploads", "delta_rows_uploaded", "speculative_rewinds",
            "conflict_requeues",
        )), flush=True)
    preemptor = {key: end[key] - start[key] for key in PREEMPTOR_COUNTERS}
    if any(preemptor.values()):
        print("preemptor over the window: " + ", ".join(
            f"{key} {value}" for key, value in preemptor.items()
        ) + f", {len(run.evicted())} pods left that the harness did not "
            "delete", flush=True)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             process_start: float, device: dict, rehearsal: bool = False,
             root: Path = ROOT, control: bool = False,
             keep_trace: str = "") -> dict:
    """Set-up, warm-up, window, check. Returns the result line's dict.
    ``control`` and ``keep_trace`` are for ``chipbench/proving/run.py``
    alone: the benchmark's command has no way to set them."""
    compile_events()  # start counting before the first program compiles
    run = Run(cell, seed)
    generator = importlib.import_module(
        f"chipbench.generators.{cell['mix']['generator']}"
    )
    params = cell["mix"]["params"]
    trace_dir = root / ".chipbench_trace" / cell["name"]
    try:
        marks = [("imports, device, scheduler", time.perf_counter())]
        run.build_cluster()
        marks.append(("cluster and init pods", time.perf_counter()))
        with run.phase("warmup"):
            generator.warmup(run, params)
        marks.append(("warm-up traffic", time.perf_counter()))
        prepared = generator.prepare(run, params, seconds)
        # as the operator's binary does once its caches are synced
        # (scheduler/app.py): freeze the long-lived graph, stretch the
        # collector's thresholds
        from kubernetes_tpu.utils.gc_tuning import freeze_steady_state_graph

        freeze_steady_state_graph()
        marks.append(("prepare and freeze", time.perf_counter()))
        print(f"set-up: {len(run.created)} pods created so far, compile events "
              f"{compile_events()}; " + ", ".join(
                  f"{what} {t - t0:.2f}s" for (what, t), t0 in
                  zip(marks, [process_start] + [t for _, t in marks])
              ), flush=True)

        slicer = None
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            slicer = tracing.Slice(
                str(trace_dir), float(cell["mix"].get("trace_seconds", 5.0))
            )
        pauses = []  # (generation, seconds) of collections in the window
        began = {}  # a collection runs in the thread that triggered it

        def on_gc(phase: str, info: dict) -> None:
            ident = threading.get_ident()
            if phase == "start":
                began[ident] = time.perf_counter()
            elif run.in_window and ident in began:
                pauses.append(
                    (info["generation"], time.perf_counter() - began.pop(ident))
                )

        gc.callbacks.append(on_gc)
        start = run.window_counters = run.counters()
        setup_s = time.perf_counter() - process_start
        run.in_window = True
        if slicer is not None:
            slicer.start()
        run.window_start = time.perf_counter()
        try:
            generator.window(run, params, prepared, seconds)
        finally:
            run.window_end = time.perf_counter()
            run.in_window = False
            gc.callbacks.remove(on_gc)
        end = run.counters()
        if slicer is not None:
            slicer.join()
        window_s = end["t"] - start["t"]

        values, attempted, unbound = end_to_end(run, setup_s)
        fallback = end["pods_fallback"] - start["pods_fallback"]
        failed = unbound + fallback
        below_all = ""
        for ledger in check.expected_tiers(run.config):
            # the configuration expects this ledger's work on the device:
            # a pod that fell through every tier of it (the preemption
            # wave's host oracle) got a slower answer, not the same one
            floor = LEDGERS[ledger][1]
            if floor is not None:
                failed += end[floor] - start[floor]
                below_all += f"{floor} {end[floor] - start[floor]}, "
        print_window_notes(run, start, end, pauses)
        print(f"window: {window_s:.3f}s, {attempted} pods due, {unbound} "
              f"not bound by their deadline, pods_fallback {fallback}, "
              f"{below_all}"
              f"batches {end['batches'] - start['batches']}; " + ", ".join(
                  f"{name} {value:.1f}" for name, value in values.items()
              ), flush=True)

        with run.phase("check"):
            correct = check.run_checks(run, control)
        memory = memory_peak_bytes()
    finally:
        run.stop()
    correct = correct and attempted > 0

    device = dict(device, memory_peak_bytes=memory)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed}
    if not trace:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        line["metrics"] = {
            name: {"value": values[name], "unit": unit}
            for name, unit in units.items() if name in values
        }
    else:
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(slicer.path(), keep_trace)
        reduced = tracing.reduce(
            slicer.path(), run.phases, slicer.host_start, rehearsal
        )
        sample = {
            "run": run, "cell": cell, "start": start, "end": end,
            "trace": reduced, "device": device, "root": root,
        }
        line["metrics"] = read_layer_metrics(cell, sample, root)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    line["device"] = device
    return line


def read_layer_metrics(cell: dict, sample: dict, root: Path) -> dict:
    """Each per-layer metric of the cell through its own reader. A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for metric in cell["per_layer"]:
        spec = load_json(
            root / "chipbench" / "layer_metrics" / f"{metric['name']}.json"
        )
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}"
        )
        value = reader.read(sample, spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {
                "value": float(value), "unit": metric["unit"]
            }
    return out


def public_arguments(prog: str) -> argparse.ArgumentParser:
    """The arguments of the benchmark's one command."""
    ap = argparse.ArgumentParser(prog=prog)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="run at the files' tiny rehearsal sizes on whatever device "
        "JAX has; the line names that device and is never a measurement",
    )
    return ap


def run_one(args, process_start: float, mix_over: dict = None,
            control: bool = False, keep_trace: str = "") -> int:
    """One run from parsed arguments to the result line and the exit
    code. ``mix_over`` is laid over the mix's file; it, ``control`` and
    ``keep_trace`` are set by ``chipbench/proving/run.py`` alone."""
    try:
        cell = load_cell(ROOT, args.workload, args.rehearsal)
        if mix_over:
            cell["mix"] = _overlay(cell["mix"], mix_over)
        # a rehearsal is never a measurement: it leaves no cache behind
        cache_dir = None if args.rehearsal else configure_compile_cache(ROOT)
        device = find_device(cell["chips"], args.rehearsal)
        print(f"device: {device}  compile cache: {cache_dir}", flush=True)
        line = run_cell(
            cell, args.seed, args.seconds, bool(args.trace), process_start,
            device, args.rehearsal, control=control, keep_trace=keep_trace,
        )
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:  # noqa: BLE001 - no result line on any failure
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None, process_start: float = None) -> int:
    if process_start is None:
        process_start = time.perf_counter()
    args = public_arguments("python3 -m chipbench").parse_args(argv)
    return run_one(args, process_start)
