"""Perf-matrix runner: drive every workload in the YAML config through
the full pipeline and emit DataItems JSON.

Mirrors the reference harness end to end:
- workload matrix     ~ test/integration/scheduler_perf/config/
                        performance-config.yaml
- throughput sampling ~ util.go:197 throughputCollector (1s windows)
- DataItems output    ~ util.go:109 (dataItems with labels + unit)
- init-pods warm fill ~ scheduler_perf_test.go:130 perfScheduling

Solver-path counters (pods on device, fallbacks, envelope fallbacks,
pipeline drains, carry reuse) ride in each item's labels so the
batch-path cliffs VERDICT r2 flagged are visible per workload.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# mesh workloads on a CPU box: KTPU_FORCE_HOST_DEVICES=8 splits the host
# platform into N virtual devices so the sharded path runs for real.
# Must land before jax initializes its backends (the kubernetes_tpu
# imports below pull jax in), and is a no-op on multi-chip hardware
# (jax.devices() returns the accelerators regardless).
_force_devs = os.environ.get("KTPU_FORCE_HOST_DEVICES")
if _force_devs and "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(_force_devs)}"
    ).strip()

from kubernetes_tpu.api.types import (
    POD_GROUP_LABEL,
    POD_RUNNING,
    ObjectMeta,
    PodGroup,
    Service,
)
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.ops.assignment import GreedyConfig
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod

ZONE_LABEL = "topology.kubernetes.io/zone"
HOSTNAME_LABEL = "kubernetes.io/hostname"


class BindCollector:
    """Event-driven throughput + latency collector over a Pod watch
    stream (the reference polls the informer once per second,
    util.go:228; a watch gives the same samples without polling)."""

    def __init__(self, server: APIServer, targets) -> None:
        self._watch = server.watch("Pod", since_rv=server.current_rv())
        self.bind_times: Dict[str, float] = {}
        self._cond = threading.Condition()
        self._stop = False
        self._targets = set(targets)
        self._outstanding = len(self._targets)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop:
            evs = self._watch.next_batch(timeout=0.2)
            if not evs:
                continue
            now = time.perf_counter()
            with self._cond:
                for ev in evs:
                    if ev.type != "MODIFIED":
                        continue
                    pod = ev.object
                    if not pod.spec.node_name:
                        continue
                    name = pod.metadata.name
                    if name in self.bind_times:
                        continue
                    self.bind_times[name] = now
                    if name in self._targets:
                        self._outstanding -= 1
                if self._outstanding <= 0:
                    self._cond.notify_all()

    def wait(self, timeout: float) -> bool:
        deadline = time.time() + timeout
        with self._cond:
            while self._outstanding > 0:
                remaining = deadline - time.time()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, 0.5))
            return True

    def wait_fraction(self, fraction: float, timeout: float) -> bool:
        """Wait until ``fraction`` of the targets have bound AND the
        bind rate has gone quiet (no new binds for one settle window) --
        the completion criterion for capacity-starved workloads where
        full placement is impossible by design."""
        need = int(fraction * len(self._targets))
        deadline = time.time() + timeout
        last_count = -1
        quiet_since = time.time()
        while time.time() < deadline:
            with self._cond:
                count = len(self._targets) - self._outstanding
            if count != last_count:
                last_count = count
                quiet_since = time.time()
            elif count >= need and time.time() - quiet_since >= 2.0:
                return True
            time.sleep(0.05)
        return last_count >= need

    def stop(self) -> None:
        self._stop = True
        self._watch.stop()
        self._thread.join(timeout=2)


def _percentile(sorted_vals: List[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(len(sorted_vals) * p / 100.0))
    return sorted_vals[idx]


def _build_pod(name: str, spec: Dict[str, Any], idx: int):
    w = make_pod(name)
    w.container(
        cpu=str(spec.get("cpu", "100m")),
        memory=str(spec.get("memory", "128Mi")),
        host_port=int(spec.get("host_port", 0)),
        **{
            k.replace("/", "__").replace(".", "_"): v
            for k, v in (spec.get("scalars") or {}).items()
        },
    )
    if spec.get("labels"):
        w.labels(**spec["labels"])
    if spec.get("priority_mix"):
        # weighted priority rotation, e.g.
        #   priority_mix: [{priority: 0, weight: 9}, {priority: 100,
        #   weight: 1}]
        # -- the priority-inversion-storm shape: a low-priority flood
        # with a high-priority tail interleaved through it, so the high
        # band must cut the queue AND preempt to meet its SLO
        pattern: List[int] = []
        for m in spec["priority_mix"]:
            pattern.extend(
                [int(m["priority"])] * int(m.get("weight", 1))
            )
        w.priority(pattern[idx % len(pattern)])
    elif spec.get("priority") is not None:
        w.priority(int(spec["priority"]))
    sp = spec.get("spread")
    if sp:
        w.spread_constraint(
            max_skew=int(sp.get("max_skew", 1)),
            topology_key=sp.get("topology_key", ZONE_LABEL),
            when_unsatisfiable=sp.get("when_unsatisfiable", "DoNotSchedule"),
            match_labels=sp.get("match_labels") or {},
        )
    af = spec.get("affinity")
    if af:
        if af.get("preferred"):
            w.preferred_pod_affinity(
                topology_key=af.get("topology_key", ZONE_LABEL),
                match_labels=af.get("match_labels") or {},
                weight=int(af.get("weight", 1)),
                anti=bool(af.get("anti")),
            )
        else:
            w.pod_affinity(
                topology_key=af.get("topology_key", ZONE_LABEL),
                match_labels=af.get("match_labels") or {},
                anti=bool(af.get("anti")),
            )
    if spec.get("node_selector"):
        w.node_selector(**spec["node_selector"])
    naff = spec.get("node_affinity_in")
    if naff:
        # required node affinity; values may rotate per pod index so a
        # 5k-node matrix entry exercises per-pod static-mask variety
        values = naff.get("values") or []
        if naff.get("rotate") and values:
            values = [values[idx % len(values)]]
        w.node_affinity_in(naff["key"], list(values))
    for s in range(int(spec.get("secret_volumes", 0))):
        w.secret_volume(f"secret-{idx % 16}-{s}")
    numa = spec.get("numa_aligned")
    if numa:
        w.pod.metadata.annotations[
            "numa.kubernetes-tpu.io/aligned"
        ] = str(numa)
    pvs = spec.get("pvs")
    if pvs:
        # one pre-bound PVC per pod (reference SchedulingInTreePVs /
        # SchedulingCSIPVs shape, scheduler_perf performance-config
        # :44/:87); the PVC/PV pair is created by run_workload
        for k in range(int(pvs.get("per_pod", 1))):
            w.pvc(f"pvc-{w.pod.metadata.name}-{k}")
    return w.obj()


def _wait_fraction_bound(coll: BindCollector, frac: float, timeout: float) -> bool:
    """Block until ``frac`` of the collector's targets have bound (the
    lifecycle scenarios trigger mid-burst, not at t=0)."""
    need = int(frac * len(coll._targets))
    deadline = time.time() + timeout
    while time.time() < deadline:
        with coll._cond:
            if len(coll._targets) - coll._outstanding >= need:
                return True
        time.sleep(0.05)
    return False


def _wait_live_bound(client: Client, timeout: float) -> bool:
    """Every pod currently in the apiserver is bound -- the lifecycle
    settle condition (respawned incarnations included, which the
    name-keyed collector cannot see)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if pods and all(p.spec.node_name for p in pods):
            return True
        time.sleep(0.1)
    return False


def _pdb_from_spec(spec: Dict[str, Any], name: str):
    """One PodDisruptionBudget from a workload's ``pdb:`` block
    ({match_labels, min_available, max_unavailable}) -- shared by the
    drain-wave, drain-via-preemption, and preemption-wave setups so the
    spec shape has one reader."""
    from kubernetes_tpu.api.types import LabelSelector, PodDisruptionBudget

    pdb = PodDisruptionBudget(
        selector=LabelSelector(
            match_labels=dict(spec.get("match_labels") or {})
        ),
        min_available=spec.get("min_available"),
        max_unavailable=spec.get("max_unavailable"),
    )
    pdb.metadata.name = name
    pdb.metadata.namespace = "default"
    return pdb


def _lifecycle_setup(
    lifecycle: Dict[str, Any],
    wl: Dict[str, Any],
    server: APIServer,
    client: Client,
    informers: InformerFactory,
    num_nodes: int,
    injector,
    sched=None,
):
    """Build the scenario actor for a ``lifecycle:`` workload. Returns
    (components-to-stop, scenario(coll, timeout_s) callable, counters,
    stop event that aborts an in-progress scenario)."""
    from kubernetes_tpu.controllers import DisruptionController, NodeDrainer
    from kubernetes_tpu.robustness.faults import (
        FaultInjector, FaultPoint, FaultProfile, PointConfig,
    )
    from kubernetes_tpu.robustness.lifecycle import (
        ClusterLifecycleDriver, PodRespawner,
    )

    mode = lifecycle.get("mode", "drain_wave")
    at_fraction = float(lifecycle.get("at_fraction", 0.3))
    stoppers = []
    counters: Dict[str, Any] = {"mode": mode}
    # teardown signal: the scenario thread (and any in-progress drain)
    # must be interruptible, or an exception path leaves a daemon
    # draining nodes under the settle checks for minutes
    stop_evt = threading.Event()

    if mode == "drain_via_preemption":
        # ISSUE-11 acceptance shape: cordoned nodes empty by DEVICE-
        # CHOSEN per-pod evictees (the preemptor's victim-search kernel
        # run as a plan) instead of whole-node eviction. The row's
        # counters carry the whole-node BASELINE (every resident at
        # drain start) next to what was actually evicted, so the
        # strictly-fewer claim is a label, not a vibe.
        disruption = DisruptionController(client, informers)
        disruption.start()
        stoppers.append(disruption)
        pdb_spec = lifecycle.get("pdb")
        if pdb_spec:
            client.create_pdb(
                _pdb_from_spec(pdb_spec, "drain-preempt-budget")
            )
        if sched is not None and getattr(sched, "preemptor", None):
            sched.preemptor.disruption = disruption
        respawner = PodRespawner(client)
        respawner.start()
        stoppers.append(respawner)
        drainer = NodeDrainer(
            client, disruption=disruption,
            should_abort=stop_evt.is_set,
            preemptor=getattr(sched, "preemptor", None),
        )
        counters["drainer"] = drainer
        counters["respawner"] = respawner
        counters["baseline_pods"] = 0

        def scenario(coll, timeout_s):
            _wait_fraction_bound(coll, at_fraction, timeout_s)
            waves = int(lifecycle.get("waves", 3))
            per = int(lifecycle.get("nodes_per_wave", 2))
            wave_timeout = float(lifecycle.get("wave_timeout_s", 60))
            idx = 0
            for _w in range(waves):
                if stop_evt.is_set():
                    return
                victims = [
                    f"node-{(idx + j) % num_nodes}" for j in range(per)
                ]
                idx += per
                for v in victims:
                    if stop_evt.is_set():
                        return
                    pods, _rv = client.list_pods()
                    counters["baseline_pods"] += sum(
                        1 for p in pods if p.spec.node_name == v
                    )
                    drainer.drain_via_preemption(v, timeout=wave_timeout)
                if lifecycle.get("uncordon", True):
                    for v in victims:
                        drainer.uncordon(v)

        return stoppers, scenario, counters, stop_evt

    if mode == "drain_wave":
        disruption = DisruptionController(client, informers)
        disruption.start()
        stoppers.append(disruption)
        pdb_spec = lifecycle.get("pdb")
        if pdb_spec:
            client.create_pdb(_pdb_from_spec(pdb_spec, "wave-budget"))
        respawner = PodRespawner(client)
        respawner.start()
        stoppers.append(respawner)
        drainer = NodeDrainer(
            client, disruption=disruption, should_abort=stop_evt.is_set
        )

        counters["drainer"] = drainer
        counters["respawner"] = respawner

        def scenario(coll, timeout_s):
            _wait_fraction_bound(coll, at_fraction, timeout_s)
            waves = int(lifecycle.get("waves", 3))
            per = int(lifecycle.get("nodes_per_wave", 2))
            wave_timeout = float(lifecycle.get("wave_timeout_s", 60))
            idx = 0
            for _w in range(waves):
                if stop_evt.is_set():
                    return
                victims = [
                    f"node-{(idx + j) % num_nodes}" for j in range(per)
                ]
                idx += per
                for v in victims:
                    if stop_evt.is_set():
                        return
                    drainer.drain(v, timeout=wave_timeout)
                # the wave is "upgraded": back into service before the
                # next wave cordons -- rolling, never net capacity loss
                if lifecycle.get("uncordon", True):
                    for v in victims:
                        drainer.uncordon(v)

        return stoppers, scenario, counters, stop_evt

    if mode in ("reclaim_storm", "chaos"):
        if mode == "reclaim_storm":
            # a private injector (never installed): deterministic storm
            # count, no solver faults
            injector = FaultInjector(FaultProfile(
                name="bench-reclaim", seed=int(wl.get("fault_seed", 0)),
                points={FaultPoint.RECLAIM_STORM: PointConfig(
                    rate=1.0,
                    max_fires=int(lifecycle.get("storms", 1)),
                )},
            ))
        assert injector is not None, "chaos mode needs fault_profile"
        driver = ClusterLifecycleDriver(
            client,
            injector=injector,
            tick_interval=float(lifecycle.get("tick_interval", 0.2)),
            flap_down_seconds=float(lifecycle.get("flap_down_seconds", 0.5)),
            storm_fraction=float(lifecycle.get("storm_fraction", 0.1)),
            storm_down_seconds=float(
                lifecycle.get("storm_down_seconds", 1.0)
            ),
        )
        stoppers.append(driver)

        counters["driver"] = driver  # resolved to numbers at teardown

        def scenario(coll, timeout_s):
            _wait_fraction_bound(coll, at_fraction, timeout_s)
            driver.start()
            # hold the scenario open until the chaos actually landed
            # (teardown stops the driver; a fast burst would otherwise
            # outrun the first tick) and the reclaimed capacity is back
            min_events = int(lifecycle.get("min_events", 1))
            deadline = time.time() + float(lifecycle.get("duration_s", 30))
            while time.time() < deadline and not stop_evt.is_set():
                if (
                    driver.flaps + driver.storms >= min_events
                    and driver.down_count() == 0
                ):
                    break
                time.sleep(0.1)

        return stoppers, scenario, counters, stop_evt

    if mode == "scale_up":
        node_spec = wl.get("node") or {}

        def scenario(coll, timeout_s):
            # the trigger: the burst saturates the starved cluster
            _wait_fraction_bound(coll, at_fraction, timeout_s)
            add = int(lifecycle.get("add_nodes", num_nodes // 10))
            for i in range(add):
                nw = make_node(f"cold-{i}").capacity(
                    cpu=str(node_spec.get("cpu", "32")),
                    memory=str(node_spec.get("memory", "64Gi")),
                    pods=int(node_spec.get("pods", 110)),
                )
                nw.label(ZONE_LABEL, f"zone-{i % 10}")
                nw.label(HOSTNAME_LABEL, f"cold-{i}")
                client.create_node(nw.obj())
            counters["nodes_added"] = add

        return stoppers, scenario, counters, stop_evt

    raise ValueError(f"unknown lifecycle mode {mode!r}")


def run_partition_workload(
    wl: Dict[str, Any], defaults: Dict[str, Any]
) -> Dict[str, Any]:
    """A perf-matrix workload through N ACTIVE partitioned stacks
    (scheduler/partition.py) instead of one scheduler -- the matrix
    shape of ``bench.py --partitions``, here so partition modes get
    standing rows (ROADMAP item-4d: zone-aligned partitioning was
    wired but had no perf-matrix number). Workload key::

        partitions: {count: 2, zone_aligned: true}

    With ``zone_aligned`` the node space splits by the zone label
    (crc32 over the zone instead of the node name), so a whole zone
    homes on -- and fails over with -- one partition; the workload's
    ``zones`` count therefore bounds the useful partition count. The
    result rows carry the conflict ledger (absorbed == requeues +
    stale, the PR-8 tier-1 invariant) and the spill count next to the
    throughput so an imbalanced or conflict-heavy run is visible in
    the matrix, not just slow."""
    from kubernetes_tpu.config.types import (
        KubeSchedulerConfiguration,
        PartitionConfiguration,
    )
    from kubernetes_tpu.scheduler.app import SchedulerApp

    name = wl["name"]
    num_nodes = int(wl["nodes"])
    zones = int(wl.get("zones", defaults.get("zones", 10)))
    max_batch = int(wl.get("max_batch", defaults.get("max_batch", 1024)))
    timeout_s = float(wl.get("timeout_s", defaults.get("timeout_s", 420)))
    node_spec = wl.get("node") or {}
    pt = wl["partitions"]
    n_parts = int(pt.get("count", 2))
    zone_aligned = bool(pt.get("zone_aligned", False))

    server = APIServer()

    def cfg():
        c = KubeSchedulerConfiguration(
            partition=PartitionConfiguration(
                enabled=True,
                num_partitions=n_parts,
                zone_aligned=zone_aligned,
                # generous leases: the measured burst saturates the box,
                # and a starved renew thread mid-burst would turn the
                # row into a takeover storm (bench.py --partitions
                # rationale); takeover latency has its own chaos harness
                lease_duration_seconds=10.0,
                retry_period_seconds=1.0,
            )
        )
        c.tpu_solver.max_batch = max_batch
        return c

    apps = []
    coll = None
    try:
        apps = [
            SchedulerApp(config=cfg(), server=server)
            for _ in range(n_parts)
        ]
        client = apps[0].client
        for i in range(num_nodes):
            nw = make_node(f"node-{i}").capacity(
                cpu=str(node_spec.get("cpu", defaults.get("node_cpu", "32"))),
                memory=str(
                    node_spec.get("memory", defaults.get("node_memory", "64Gi"))
                ),
                pods=int(node_spec.get("pods", defaults.get("node_pods", 110))),
            )
            nw.label(ZONE_LABEL, f"zone-{i % zones}")
            nw.label(HOSTNAME_LABEL, f"node-{i}")
            client.create_node(nw.obj())
        for app in apps:
            app.start()
        # settle: every partition claimed by exactly one stack. A claim
        # that never lands would otherwise surface 900s later as an
        # opaque bind timeout (pods homed to the unclaimed partition
        # sit forever), so an unsettled map is an explicit error row.
        deadline = time.time() + 15
        held: List[int] = []
        while time.time() < deadline:
            held = sorted(
                k for app in apps for k in app.coordinator.held_partitions()
            )
            if held == list(range(n_parts)):
                break
            time.sleep(0.05)
        if held != list(range(n_parts)):
            return {
                "name": name,
                "error": (
                    f"partition map never settled: held {held} of "
                    f"{n_parts} partitions after 15s"
                ),
            }
        # warmup AFTER start+settle: app.start() is what syncs the
        # informers, and each stack's cache scopes to its held
        # partitions -- warming earlier sees zero nodes and compiles
        # nothing (the measured burst would then pay the JIT). jit
        # caches are process-global and the stacks' ~N/P node tensors
        # bucket-pad to the same capacity, so one warmup covers every
        # stack
        apps[0].sched.warmup()

        init_n = int(wl.get("init_pods", 0))
        init_spec = wl.get("init_pod") or wl.get("pod") or {}
        if init_n:
            init_names = [f"init-{i}" for i in range(init_n)]
            icoll = BindCollector(server, init_names)
            for i, nm in enumerate(init_names):
                client.create_pod(_build_pod(nm, init_spec, i))
            if not icoll.wait(timeout_s):
                icoll.stop()
                return {"name": name, "error": "init pods did not all schedule"}
            icoll.stop()

        measure_pods = int(wl["measure_pods"])
        pod_spec = wl.get("pod") or {}
        pods = [
            _build_pod(f"measure-{i}", pod_spec, i)
            for i in range(measure_pods)
        ]
        target_names = [p.metadata.name for p in pods]
        coll = BindCollector(server, target_names)
        create_times: Dict[str, float] = {}
        start = time.perf_counter()
        for p in pods:
            create_times[p.metadata.name] = time.perf_counter()
            client.create_pod(p)
        ok = coll.wait(timeout_s)
        elapsed = time.perf_counter() - start
        for app in apps:
            app.sched.wait_for_inflight_binds(timeout=60)

        bound = sum(1 for n in target_names if n in coll.bind_times)
        result: Dict[str, Any] = {
            "name": name,
            "ok": bool(ok and bound >= measure_pods),
            "bound": bound,
            "total": measure_pods,
            "elapsed_s": round(elapsed, 3),
            "throughput_pods_per_s": (
                round(bound / elapsed, 1) if elapsed else 0.0
            ),
        }
        lat = sorted(
            coll.bind_times[n] - create_times[n]
            for n in target_names
            if n in coll.bind_times and n in create_times
        )
        if lat:
            result["latency_ms"] = {
                "Perc50": round(_percentile(lat, 50) * 1000, 1),
                "Perc90": round(_percentile(lat, 90) * 1000, 1),
                "Perc99": round(_percentile(lat, 99) * 1000, 1),
            }
        absorbed = sum(a.sched.bind_conflicts_absorbed for a in apps)
        requeues = sum(a.sched.conflict_requeues for a in apps)
        stale = sum(a.sched.conflict_stale_binds for a in apps)
        result["partition"] = {
            "count": n_parts,
            "zone_aligned": zone_aligned,
            "bind_conflicts_absorbed": absorbed,
            "conflict_requeues": requeues,
            "conflict_stale_binds": stale,
            "ledger_balanced": absorbed == requeues + stale,
            "pods_spilled": sum(a.sched.pods_spilled for a in apps),
            "takeovers": sum(a.coordinator.takeovers for a in apps),
            "pods_fallback": sum(a.sched.pods_fallback for a in apps),
        }
        return result
    finally:
        if coll is not None:
            coll.stop()
        for app in apps:
            try:
                app.stop()
            except Exception:  # noqa: BLE001 - teardown keeps going
                pass


def run_workload(wl: Dict[str, Any], defaults: Dict[str, Any]) -> Dict[str, Any]:
    if wl.get("partitions"):
        return run_partition_workload(wl, defaults)
    name = wl["name"]
    num_nodes = int(wl["nodes"])
    zones = int(wl.get("zones", defaults.get("zones", 10)))
    max_batch = int(wl.get("max_batch", defaults.get("max_batch", 1024)))
    timeout_s = float(wl.get("timeout_s", defaults.get("timeout_s", 420)))
    node_spec = wl.get("node") or {}

    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    # a row's ``solver:`` overrides the profile's own resource score rule
    # (the profile decides it on the operator's path; these rows build no
    # profile, so they say the weights themselves)
    solver_cfg = GreedyConfig(**wl["solver"]) if wl.get("solver") else None
    # workload-scoped node-axis mesh (the sharded delta path). A row
    # that asks for more devices than this process has still runs, on a
    # mesh of what is visible (the matrix stays runnable on a 1-chip
    # box), but says so: the row carries both the requested and the
    # actual device count, and a warning goes to stderr. CPU boxes can
    # force virtual devices with KTPU_FORCE_HOST_DEVICES=N (read before
    # jax initializes, see main()).
    mesh = None
    mesh_devices_requested = mesh_devices = int(wl.get("mesh_devices", 0))
    if mesh_devices > 0:
        import jax
        from jax.sharding import Mesh

        import numpy as _np

        devs = jax.devices()
        if mesh_devices > len(devs):
            print(
                f"{name}: mesh_devices={mesh_devices} requested but only "
                f"{len(devs)} visible; running on a mesh of {len(devs)}",
                file=sys.stderr, flush=True,
            )
            mesh_devices = len(devs)
        mesh = Mesh(_np.array(devs[:mesh_devices]), axis_names=("nodes",))
    # `fleet:` closes the bind loop (ISSUE 17): a sharded
    # HollowNodeFleet acks every bind into Running, the scheduler's
    # BindAckTracker treats a bind as pending until that ack lands (and
    # rebinds on timeout), and the row's success gate becomes
    # pods RUNNING, not pods bound
    fleet_cfg = wl.get("fleet")
    bind_ack_config = None
    if fleet_cfg is not None and fleet_cfg.get("bind_ack") is not False:
        from kubernetes_tpu.config.types import BindAckConfiguration

        ba = dict(fleet_cfg.get("bind_ack") or {})
        bind_ack_config = BindAckConfiguration(enabled=True, **ba)
    sched = new_scheduler(
        client,
        informers,
        batch=True,
        max_batch=max_batch,
        solver_config=solver_cfg,
        solver_mode=wl.get("solver_mode", "greedy"),
        mesh=mesh,
        bind_ack_config=bind_ack_config,
    )

    # workload-scoped open-loop streaming (kubernetes_tpu/streaming/):
    # the measured pods arrive as a seeded trace through the
    # ArrivalEngine instead of one t=0 bulk create, the SLO-adaptive
    # controller replaces the static batch window, and the backpressure
    # bound gates the engine. Attached BEFORE warmup so the controller's
    # latency solve pad is compiled off the clock.
    streaming = None
    controller = None
    if wl.get("streaming"):
        from kubernetes_tpu.config.loader import streaming_from_dict
        from kubernetes_tpu.streaming.autobatch import AutoBatchController

        # same camelCase schema as the top-level config's streaming:
        # block; in a workload block the controller defaults ON
        streaming = streaming_from_dict(
            {"enabled": True, **wl["streaming"]}
        )
        if streaming.enabled:
            controller = AutoBatchController(
                slo_p99_seconds=streaming.slo_p99_seconds,
                min_window=streaming.min_window_seconds,
                max_window=streaming.max_window_seconds,
                latency_batch=streaming.latency_batch,
                max_batch=max_batch,
                interval_seconds=streaming.controller_interval_seconds,
                auto_rungs=getattr(streaming, "auto_rungs", False),
            )
            sched.attach_autobatch(controller)
        if streaming.band_priority_threshold is not None:
            sched.queue.band_threshold = streaming.band_priority_threshold

    # workload-scoped multi-tenant fairness plane (ISSUE 15): pods
    # spread over `namespaces:` tenants, optional per-namespace
    # ResourceQuota hard caps, the QuotaController admission gate, and
    # the DRF dominant-share solve-order bias. Counters land in the
    # row's tenant_* labels (Jain bind-fairness index, dominant-share
    # spread, quota denials/refunds/parked).
    n_namespaces = int(wl.get("namespaces", 1))
    tenancy_cfg = wl.get("tenancy")
    quota_ctrl = None
    tenancy_stoppers: List[Any] = []
    if tenancy_cfg is not None or n_namespaces > 1:
        from kubernetes_tpu.scheduler.tenancy import arm_tenancy

        tenancy_cfg = tenancy_cfg or {}
        quota_ctrl = arm_tenancy(sched, client, informers)
        tenancy_stoppers.append(quota_ctrl)
    quota_spec = wl.get("quota")
    if quota_spec:
        from kubernetes_tpu.api.resource import parse_cpu, parse_memory
        from kubernetes_tpu.api.types import ResourceQuota
        from kubernetes_tpu.api.types import ObjectMeta as _QOM

        hard: Dict[str, int] = {}
        for rname, qty in quota_spec.items():
            if rname == "cpu":
                hard["cpu"] = parse_cpu(qty)
            elif rname == "memory":
                hard["memory"] = parse_memory(qty)
            else:
                hard[rname] = int(qty)
        for t in range(max(1, n_namespaces)):
            server.create(ResourceQuota(
                metadata=_QOM(name="quota", namespace=f"tenant-{t}"),
                hard=dict(hard),
            ))

    # workload-scoped preemption wave wiring (ISSUE 11): the shared
    # DisruptionController PDB gate on the scheduler's Preemptor (every
    # wave eviction spends can_disrupt -- zero overspend by
    # construction), an optional PDB over the fill, and a respawner so
    # evicted victims re-enter as pending arrivals (the cascade shape).
    # Counters land in the row's preemption_* labels.
    preempt_cfg = wl.get("preemption")
    preempt_stoppers: List[Any] = []
    preempt_metrics0: Dict[str, float] = {}
    if preempt_cfg:
        from kubernetes_tpu.controllers import DisruptionController
        from kubernetes_tpu.robustness.lifecycle import PodRespawner
        from kubernetes_tpu.utils import metrics as _metrics

        disruption = DisruptionController(client, informers)
        disruption.start()
        sched.preemptor.disruption = disruption
        preempt_stoppers.append(disruption)
        pdb_spec = preempt_cfg.get("pdb")
        if pdb_spec:
            client.create_pdb(
                _pdb_from_spec(pdb_spec, "preemption-budget")
            )
        rsp_prefix = preempt_cfg.get("respawn_prefix")
        if rsp_prefix:
            respawner = PodRespawner(
                client,
                should_respawn=(
                    lambda p: p.metadata.name.startswith(rsp_prefix)
                ),
            )
            respawner.start()
            preempt_stoppers.append(respawner)
        preempt_metrics0 = {
            "blocked": _metrics.evictions_blocked_by_pdb.value(),
            "nominations_set": _metrics.nominations_set.value(),
            "nominations_cleared": _metrics.nominations_cleared.value(),
        }

    for i in range(num_nodes):
        nw = make_node(f"node-{i}").capacity(
            cpu=str(node_spec.get("cpu", defaults.get("node_cpu", "32"))),
            memory=str(node_spec.get("memory", defaults.get("node_memory", "64Gi"))),
            pods=int(node_spec.get("pods", defaults.get("node_pods", 110))),
            **{
                k.replace("/", "__").replace(".", "_"): v
                for k, v in (node_spec.get("scalars") or {}).items()
            },
        )
        nw.label(ZONE_LABEL, f"zone-{i % zones}")
        nw.label(HOSTNAME_LABEL, f"node-{i}")
        if node_spec.get("numa_groups"):
            nw.label(
                "numa.kubernetes-tpu.io/gpu-groups",
                str(node_spec["numa_groups"]),
            )
        client.create_node(nw.obj())

    # per-node CSINode objects (nodevolumelimits/csi.go attach limits):
    # the volume-count device columns read allocatable from these, so a
    # CSI workload exercises the limit columns end to end. Absent
    # CSINodes mean "no limit known" (the reference allows).
    csn = wl.get("csi_node") or node_spec.get("csi_node")
    if csn:
        from kubernetes_tpu.api.types import CSINode, CSINodeDriver
        from kubernetes_tpu.api.types import ObjectMeta as _OM

        for i in range(num_nodes):
            server.create(
                CSINode(
                    metadata=_OM(name=f"node-{i}", namespace=""),
                    drivers=[
                        CSINodeDriver(
                            name=csn.get("driver", "ebs.csi.aws.com"),
                            node_id=f"node-{i}",
                            allocatable_count=int(
                                csn.get("allocatable", 8)
                            ),
                        )
                    ],
                )
            )

    for svc in wl.get("services") or []:
        server.create(
            Service(
                metadata=ObjectMeta(name=svc["name"], namespace="default"),
                selector=dict(svc.get("selector") or {}),
            )
        )

    # SchedulingSecrets (reference performance-config.yaml): pods mount
    # secret volumes; the pool matches _build_pod's secret-{idx%16}-{s}
    # naming so every reference resolves to a stored Secret
    # pre-bound PVC/PV pairs for PV workloads: every pod with a "pvs"
    # spec references pvc-{podname}-{k}, bound 1:1 to a PV. "csi" PVs
    # carry a csi driver source (attach limits resolve them -> exact
    # host path); "simple" PVs have no source/zone/affinity (provably
    # node-independent -> the solver takes them)
    def _make_pv_pairs(names: List[str], pvs_spec: Dict[str, Any]) -> None:
        from kubernetes_tpu.api.types import (
            PersistentVolume, PersistentVolumeClaim,
        )

        per_pod = int(pvs_spec.get("per_pod", 1))
        kind = pvs_spec.get("type", "simple")
        for nm in names:
            for k in range(per_pod):
                cn = f"pvc-{nm}-{k}"
                vn = f"pv-{nm}-{k}"
                server.create(
                    PersistentVolumeClaim(
                        metadata=ObjectMeta(
                            name=cn, namespace="default"
                        ),
                        volume_name=vn,
                        requested_bytes=1 << 30,
                    )
                )
                pv = PersistentVolume(
                    # cluster-scoped: the PV lister looks up namespace ""
                    metadata=ObjectMeta(name=vn, namespace=""),
                    capacity_bytes=1 << 30,
                    claim_ref_namespace="default",
                    claim_ref_name=cn,
                )
                if kind == "csi":
                    pv.csi_driver = "ebs.csi.aws.com"
                    pv.csi_volume_handle = vn
                server.create(pv)

    n_sec = int((wl.get("pod") or {}).get("secret_volumes", 0) or 0)
    if n_sec:
        from kubernetes_tpu.api.types import Secret

        for i in range(16):
            for s in range(n_sec):
                server.create(
                    Secret(
                        metadata=ObjectMeta(
                            name=f"secret-{i}-{s}", namespace="default"
                        ),
                        data={"token": f"t-{i}-{s}"},
                    )
                )

    gang = wl.get("gang")
    measure_pods = int(wl["measure_pods"])
    if gang:
        group_size = int(gang.get("group_size", 10))
        for g in range(-(-measure_pods // group_size)):
            server.create(
                PodGroup(
                    metadata=ObjectMeta(name=f"group-{g}", namespace="default"),
                    min_member=int(gang.get("min_member", group_size)),
                )
            )

    # workload-scoped fault profile (the chaos-profile variants): the
    # injector is installed for the whole run and ALWAYS uninstalled on
    # exit so the next matrix entry starts clean
    injector = None
    if wl.get("fault_profile"):
        from kubernetes_tpu.robustness.faults import (
            FaultInjector, install_injector, load_profile,
        )

        injector = FaultInjector(load_profile(
            wl["fault_profile"], seed=int(wl.get("fault_seed", 0))
        ))
        install_injector(injector)

    lifecycle = wl.get("lifecycle")
    lifecycle_stoppers: List[Any] = []
    lifecycle_scenario = None
    lifecycle_counters: Dict[str, Any] = {}
    lifecycle_stop = None
    if lifecycle:
        (
            lifecycle_stoppers, lifecycle_scenario,
            lifecycle_counters, lifecycle_stop,
        ) = _lifecycle_setup(
            lifecycle, wl, server, client, informers, num_nodes,
            injector, sched=sched,
        )

    hollow = None
    if wl.get("hollow"):
        # hollow-node pool (kubemark pattern, hollow_kubelet.go:64):
        # bound pods get acked Running and nodes heartbeat, so churn
        # workloads exercise the full control loop
        from kubernetes_tpu.kubelet import HollowNodePool

        hollow = HollowNodePool(
            client, [f"node-{i}" for i in range(num_nodes)]
        )
        hollow.start()

    fleet = None
    fleet_lifecycle = None
    fleet_disruption = None
    fleet_respawner = None
    zombie_nodes: List[str] = []
    if fleet_cfg is not None:
        from kubernetes_tpu.kubelet import FleetConfig, HollowNodeFleet

        _fc_keys = (
            "shard_size", "ack_latency_seconds", "ack_latency_jitter",
            "heartbeat_interval_seconds", "lease_duration_seconds",
            "allocatable_drift", "seed",
        )
        fleet = HollowNodeFleet(
            client,
            [f"node-{i}" for i in range(num_nodes)],
            FleetConfig(**{
                k: fleet_cfg[k] for k in _fc_keys if k in fleet_cfg
            }),
        )
        n_zombie = int(fleet_cfg.get("zombies", 0))
        if n_zombie:
            # zombie kubelets: lease renews forever, acks never come --
            # only the bind-ack timeout can route around them
            zombie_nodes = [f"node-{i}" for i in range(n_zombie)]
            fleet.mark_zombie(zombie_nodes)
        fleet.start()
        lc = fleet_cfg.get("lifecycle")
        if lc:
            from kubernetes_tpu.controllers import DisruptionController
            from kubernetes_tpu.controllers.nodelifecycle import (
                NodeLifecycleController,
            )

            fleet_disruption = DisruptionController(client, informers)
            fleet_disruption.start()
            fleet_lifecycle = NodeLifecycleController(
                client, informers,
                grace_period=float(lc.get("grace_period", 40.0)),
                monitor_interval=float(lc.get("monitor_interval", 5.0)),
                disruption=fleet_disruption,
            )
            fleet_lifecycle.start()
        if fleet_cfg.get("respawn_evicted"):
            # heartbeat-lapse evictions DELETE pods; the respawner
            # feeds each one back as a fresh pending arrival so the
            # closed loop must land it somewhere alive
            from kubernetes_tpu.robustness.lifecycle import PodRespawner

            fleet_respawner = PodRespawner(
                client,
                should_respawn=(
                    lambda p: p.metadata.name.startswith("measure-")
                ),
            )
            fleet_respawner.start()

    coll = None
    engine = None
    try:
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        if quota_ctrl is not None:
            quota_ctrl.sync_all()
            quota_ctrl.start()
        sched.warmup()

        # -- init fill (off the clock) ------------------------------------------
        init_spec = wl.get("init_pod") or wl.get("pod") or {}
        init_n = int(wl.get("init_pods", 0))
        if init_n and init_spec.get("pvs"):
            _make_pv_pairs(
                [f"init-{i}" for i in range(init_n)], init_spec["pvs"]
            )
        if (wl.get("pod") or {}).get("pvs"):
            _make_pv_pairs(
                [f"measure-{i}" for i in range(int(wl["measure_pods"]))],
                (wl.get("pod") or {})["pvs"],
            )
        if init_n:
            init_names = [f"init-{i}" for i in range(init_n)]
            coll = BindCollector(server, init_names)
            for i, nm in enumerate(init_names):
                client.create_pod(_build_pod(nm, init_spec, i))
            t = sched.start()
            if not coll.wait(timeout_s):
                return {"name": name, "error": "init pods did not all schedule"}
            coll.stop()
        else:
            t = sched.start()

        # warm the preemption path off the clock (kernel compile +
        # victim-pack build): a few high-priority pods preempt before
        # the measured burst -- steady-state clusters preempt routinely,
        # and the reference harness likewise schedules warm-up pods
        # before ResetTimer (scheduler_perf_test.go:130)
        n_warm_preempt = int(wl.get("init_preempt", 0))
        if n_warm_preempt:
            warm_spec = dict(wl.get("pod") or {})
            warm_names = [f"warmpre-{i}" for i in range(n_warm_preempt)]
            wcoll = BindCollector(server, warm_names)
            for i, nm in enumerate(warm_names):
                client.create_pod(_build_pod(nm, warm_spec, i))
            wcoll.wait(timeout_s)
            wcoll.stop()
            sched.wait_for_inflight_binds(timeout=60)

        # freeze the init-fill object graph out of cyclic-GC scans
        # (utils/gc_tuning.py rationale)
        from kubernetes_tpu.utils.gc_tuning import freeze_steady_state_graph

        freeze_steady_state_graph()

        # -- measured burst -------------------------------------------------------
        pod_spec = wl.get("pod") or {}
        selector_mix = int(wl.get("selector_mix", 0))
        pods = []
        for i in range(measure_pods):
            spec_i = pod_spec
            if wl.get("daemonset"):
                # DaemonSet-style fan-out: pod i pins to node i -- every
                # pod carries a DISTINCT nodeSelector, so the static
                # mask is per-pod, not per-batch
                spec_i = dict(pod_spec)
                spec_i["node_selector"] = {
                    HOSTNAME_LABEL: f"node-{i % num_nodes}"
                }
            elif selector_mix:
                # mask-diversity mix: pods rotate through selector_mix
                # distinct zone nodeSelectors, so every batch carries
                # ~selector_mix deduplicated [U, N] static-mask rows --
                # at the 100k-node mesh tier that is exactly the
                # payload the sharded (column-split, bool) mask upload
                # exists to cut (PR 10)
                spec_i = dict(pod_spec)
                spec_i["node_selector"] = {
                    ZONE_LABEL: f"zone-{i % selector_mix}"
                }
            p = _build_pod(f"measure-{i}", spec_i, i)
            if gang:
                p.metadata.labels[POD_GROUP_LABEL] = (
                    f"group-{i // int(gang.get('group_size', 10))}"
                )
            if quota_ctrl is not None:
                # tenant identity IS the namespace: round-robin so every
                # batch spans tenants (the fairness plane's arbitration
                # surface)
                p.metadata.namespace = (
                    f"tenant-{i % max(1, n_namespaces)}"
                )
            pods.append(p)

        # quota-churn scenario: raise every tenant's hard caps mid-run
        # (`quota_scenario: {mode: raise, at_fraction: F, factor: K}`)
        # -- the parked remainder must wake on the quota events and
        # bind, pinning the event-driven release path end to end
        quota_scenario = wl.get("quota_scenario")
        if quota_scenario and quota_ctrl is not None:

            def _run_quota_scenario(coll_ref=None):
                frac = float(quota_scenario.get("at_fraction", 0.5))
                factor = int(quota_scenario.get("factor", 2))
                _wait_fraction_bound(coll_ref, frac, timeout_s)
                for t in range(max(1, n_namespaces)):
                    def grow(obj, _f=factor):
                        obj.hard = {
                            name: qty * _f
                            for name, qty in obj.hard.items()
                        }
                    try:
                        client.update_resource_quota_status(
                            f"tenant-{t}", "quota", grow
                        )
                    except KeyError:
                        pass

        # -- poison seeding (blast-radius containment, ISSUE 14) -----------
        # `poison: {count: N, seed: S}` stamps N measured pods at seeded
        # random offsets; they must end QUARANTINED (parked, typed
        # condition), never bound, while every healthy pod still binds
        # -- so they are excluded from the bind targets and the workload
        # additionally fails unless all of them parked.
        poison_cfg = wl.get("poison")
        poison_names: set = set()
        if poison_cfg:
            import random as _random

            from kubernetes_tpu.robustness.faults import (
                FaultInjector,
                FaultProfile,
                POISON_ANNOTATION,
                install_injector,
            )

            prng = _random.Random(int(poison_cfg.get("seed", 0)))
            count = min(int(poison_cfg.get("count", 1)), len(pods))
            for i in sorted(prng.sample(range(len(pods)), count)):
                pods[i].metadata.annotations[POISON_ANNOTATION] = "true"
                poison_names.add(pods[i].metadata.name)
            if injector is None:
                # poison manifests only with an injector installed
                injector = FaultInjector(FaultProfile(
                    "poison-workload", seed=0, points={}
                ))
                install_injector(injector)

        churn = wl.get("churn")
        target_names = [
            p.metadata.name for p in pods
            if p.metadata.name not in poison_names
        ]
        coll = BindCollector(server, target_names)
        create_times: Dict[str, float] = {}

        start = time.perf_counter()
        scenario_thread = None
        if lifecycle_scenario is not None:
            scenario_thread = threading.Thread(
                target=lifecycle_scenario,
                args=(coll, timeout_s),
                name="lifecycle-scenario",
                daemon=True,
            )
            scenario_thread.start()
        quota_thread = None
        if quota_scenario and quota_ctrl is not None:
            quota_thread = threading.Thread(
                target=_run_quota_scenario, args=(coll,),
                name="quota-scenario", daemon=True,
            )
            quota_thread.start()
        fleet_storm = (fleet_cfg or {}).get("dark")
        fleet_dark_state = None
        if fleet_storm and fleet is not None:
            # heartbeat-lapse storm: N hollow agents go fully dark
            # mid-burst (no acks, no lease renewals); the nodelifecycle
            # monitor must notice the lapsed leases, taint NoExecute,
            # and evict through the shared disruption budget
            fleet_dark_state = {"fired": False, "nodes": []}

            def _run_fleet_storm(coll_ref, _fleet=fleet,
                                 _skip=len(zombie_nodes),
                                 _state=fleet_dark_state):
                frac = float(fleet_storm.get("at_fraction", 0.5))
                _wait_fraction_bound(coll_ref, frac, timeout_s)
                count = int(fleet_storm.get("count", 0))
                dark = [f"node-{i}" for i in range(_skip, _skip + count)]
                _state["nodes"] = dark
                _fleet.go_dark(dark)
                _state["fired"] = True

            threading.Thread(
                target=_run_fleet_storm, args=(coll,),
                name="fleet-storm", daemon=True,
            ).start()
        ok = True
        streaming_rec: Dict[str, Any] = {}
        if streaming:
            from kubernetes_tpu.streaming.arrivals import (
                ArrivalEngine, trace_from_config,
            )

            # generate until the trace covers every measured pod, then
            # trim: the workload measures exactly measure_pods arrivals.
            # A replay trace is FIXED -- growing the duration cannot add
            # arrivals, so an undersized recording is a config error,
            # not a retry loop
            dur = measure_pods / streaming.rate_pods_per_sec
            offsets = trace_from_config(streaming, duration=dur)
            if streaming.trace == "replay":
                if offsets.size < measure_pods:
                    return {
                        "name": name,
                        "error": (
                            f"replay trace holds {offsets.size} arrivals "
                            f"< measure_pods {measure_pods}"
                        ),
                    }
            else:
                while offsets.size < measure_pods:
                    dur *= 1.3
                    offsets = trace_from_config(streaming, duration=dur)
            offsets = offsets[:measure_pods]
            engine = ArrivalEngine(
                client, offsets, lambda i: pods[i],
                depth_fn=sched.queue.active_count,
                max_queue_depth=streaming.max_queue_depth,
            )
            engine.start()
            frac = float(wl.get("min_bound_fraction", 1.0))
            if frac < 1.0:
                ok = coll.wait_fraction(frac, timeout_s)
            else:
                ok = coll.wait(timeout_s)
            engine.stop()
            create_times.update(engine.created_ts)
            streaming_rec = {
                "trace": streaming.trace,
                "rate": streaming.rate_pods_per_sec,
                "seed": streaming.seed,
                "arrived": engine.created,
                "backpressure_stalls": engine.backpressure_stalls,
                "stall_seconds": round(engine.stall_seconds, 3),
            }
        elif churn:
            # BASELINE #5: steady-state churn -- delete a slice of running
            # pods and schedule replacements, round after round
            rounds = int(churn.get("rounds", 5))
            per_round = int(churn.get("delete_per_round", len(pods) // rounds))
            chunks = [
                pods[r * len(pods) // rounds: (r + 1) * len(pods) // rounds]
                for r in range(rounds)
            ]
            running, _ = client.list_pods()
            victims = [p for p in running if p.spec.node_name]
            vi = 0
            for r, chunk in enumerate(chunks):
                for _ in range(min(per_round, len(victims) - vi)):
                    v = victims[vi]
                    vi += 1
                    client.delete_pod(v.metadata.namespace, v.metadata.name)
                for p in chunk:
                    create_times[p.metadata.name] = time.perf_counter()
                    client.create_pod(p)
                # wait for this round's chunk before the next delete wave
                round_deadline = time.time() + timeout_s / rounds
                while time.time() < round_deadline:
                    with coll._cond:
                        if all(
                            p.metadata.name in coll.bind_times for p in chunk
                        ):
                            break
                    time.sleep(0.02)
            ok = coll.wait(timeout_s)
        else:
            for p in pods:
                create_times[p.metadata.name] = time.perf_counter()
                client.create_pod(p)
            frac = float(wl.get("min_bound_fraction", 1.0))
            if frac < 1.0:
                ok = coll.wait_fraction(frac, timeout_s)
            else:
                ok = coll.wait(timeout_s)
        elapsed = time.perf_counter() - start
        if float(wl.get("min_bound_fraction", 1.0)) < 1.0 and coll.bind_times:
            # wait_fraction needs a 2s quiet window to decide the system
            # settled; the measured window ends at the LAST BIND, not at
            # the detector's return
            elapsed = max(coll.bind_times.values()) - start
        sched.wait_for_inflight_binds(timeout=60)

        if poison_names:
            # settle: every stamped pod must finish its strike budget
            # and park (the containment acceptance half of the row)
            q_deadline = time.time() + 120
            while (
                time.time() < q_deadline
                and sched.queue.quarantine_parked_count()
                < len(poison_names)
            ):
                time.sleep(0.1)
            ok = ok and (
                sched.queue.quarantine_parked_count()
                == len(poison_names)
            )

        if lifecycle:
            # teardown restores reclaimed capacity (driver.stop());
            # THEN every live incarnation must place -- respawned
            # clones are invisible to the name-keyed collector
            if scenario_thread is not None:
                scenario_thread.join(timeout=timeout_s)
                if scenario_thread.is_alive():
                    lifecycle_stop.set()  # deadline passed: abort it
                    scenario_thread.join(timeout=30)
            for comp in lifecycle_stoppers:
                comp.stop()
            lifecycle_stoppers = []
            settled = _wait_live_bound(client, 120.0)
            sched.wait_for_inflight_binds(timeout=60)
            drv = lifecycle_counters.pop("driver", None)
            if drv is not None:
                lifecycle_counters.update(
                    flaps=drv.flaps, storms=drv.storms,
                    nodes_reclaimed=drv.nodes_reclaimed,
                    pods_killed=drv.pods_killed,
                    pods_respawned=drv.pods_respawned,
                )
            drn = lifecycle_counters.pop("drainer", None)
            if drn is not None:
                lifecycle_counters.update(
                    evictions=drn.evictions,
                    evictions_blocked=drn.evictions_blocked,
                    drains_completed=drn.drains,
                )
                if drn.preempt_planned or drn.preempt_left_running:
                    lifecycle_counters.update(
                        preempt_planned=drn.preempt_planned,
                        preempt_left_running=drn.preempt_left_running,
                    )
            rsp = lifecycle_counters.pop("respawner", None)
            if rsp is not None:
                lifecycle_counters["pods_respawned"] = rsp.respawned
            lifecycle_counters["settled"] = settled
            ok = ok and settled

        pods_running = 0
        if fleet is not None:
            # the closed-loop settle: a bind only COUNTS once the hollow
            # kubelet acked it into Running. Ack-timeout rebinds and
            # respawned evictees keep landing after the last first-bind,
            # so the Running census converges later than the collector.
            need_running = int(
                float(wl.get("min_bound_fraction", 1.0))
                * len(target_names)
            )

            def _count_running():
                return sum(
                    1 for p in client.list_pods()[0]
                    if p.metadata.name.startswith("measure-")
                    and p.status.phase == POD_RUNNING
                    and p.metadata.deletion_timestamp is None
                )

            def _running_on_dark():
                # a dark-storm row only settles once the eviction loop
                # has actually run: the storm fired AND no surviving
                # Running pod still rests on a dark node
                if fleet_dark_state is None:
                    return 0
                dark = set(fleet_dark_state["nodes"])
                return sum(
                    1 for p in client.list_pods()[0]
                    if p.metadata.name.startswith("measure-")
                    and p.status.phase == POD_RUNNING
                    and p.metadata.deletion_timestamp is None
                    and p.spec.node_name in dark
                )

            def _settled():
                if pods_running < need_running:
                    return False
                if fleet_dark_state is not None and (
                    not fleet_dark_state["fired"]
                    or _running_on_dark() > 0
                ):
                    return False
                return True

            settle_deadline = time.time() + min(timeout_s, 300.0)
            pods_running = _count_running()
            while time.time() < settle_deadline and not _settled():
                time.sleep(0.25)
                pods_running = _count_running()

        bound = sum(1 for n in target_names if n in coll.bind_times)
        # capacity-starved workloads (GangContention) EXPECT a fraction
        # of pods to stay pending; they pass on reaching the fraction
        # with clean bookkeeping instead of full placement
        min_frac = float(wl.get("min_bound_fraction", 1.0))
        # same floor as wait_fraction's need so the detector and the ok
        # verdict can't disagree on fractional thresholds
        need = int(min_frac * len(target_names))
        result: Dict[str, Any] = {
            "name": name,
            "ok": bool(ok and bound >= need),
            "bound": bound,
            "total": len(target_names),
            "elapsed_s": round(elapsed, 3),
            "throughput_pods_per_s": round(bound / elapsed, 1) if elapsed else 0.0,
        }

        lat = sorted(
            coll.bind_times[n] - create_times[n]
            for n in target_names
            if n in coll.bind_times and n in create_times
        )
        if lat:
            result["latency_ms"] = {
                "Perc50": round(_percentile(lat, 50) * 1000, 1),
                "Perc90": round(_percentile(lat, 90) * 1000, 1),
                "Perc99": round(_percentile(lat, 99) * 1000, 1),
            }
        # 1s-window throughput samples (reference throughputCollector)
        if coll.bind_times:
            t0 = min(coll.bind_times.values())
            windows: Dict[int, int] = {}
            for v in coll.bind_times.values():
                windows[int((v - t0))] = windows.get(int(v - t0), 0) + 1
            samples = sorted(windows.values())
            result["throughput_samples"] = {
                "Average": round(sum(samples) / len(samples), 1),
                "Perc50": _percentile(samples, 50),
                "Perc90": _percentile(samples, 90),
                "Perc99": _percentile(samples, 99),
            }
        # placement-quality: per-node cpu utilization spread (the churn
        # workloads exist to compare greedy vs the sinkhorn global
        # prior; throughput alone can't show placement quality)
        from kubernetes_tpu.api.types import (
            RESOURCE_CPU,
            pod_resource_requests,
        )

        node_cpu: Dict[str, int] = {}
        for p in client.list_pods()[0]:
            if p.spec.node_name:
                node_cpu[p.spec.node_name] = node_cpu.get(
                    p.spec.node_name, 0
                ) + pod_resource_requests(p).get(RESOURCE_CPU, 0)
        utils = []
        for node_obj in client.list_nodes()[0]:
            cap = node_obj.status.allocatable.get(RESOURCE_CPU, 0)
            if cap:
                utils.append(
                    node_cpu.get(node_obj.metadata.name, 0) / cap
                )
        if utils:
            mean = sum(utils) / len(utils)
            var = sum((u - mean) ** 2 for u in utils) / len(utils)
            result["utilization_cpu"] = {
                "mean": round(mean, 4),
                "std": round(var ** 0.5, 4),
                "max": round(max(utils), 4),
            }
        result["solver"] = {
            "mesh_devices": mesh_devices,
            "mesh_devices_requested": mesh_devices_requested,
            # which mesh tier the workload ACTUALLY solved on:
            # "pallas" = the shard_map'd per-shard tier (PR 10),
            # "xla" = the GSPMD twin (KTPU_MESH_PALLAS=0, ineligible
            # shape, or breaker-routed fallback), "" = no mesh
            "mesh_tier": getattr(sched, "mesh_solver_tier", ""),
            "batches": sched.batches_solved,
            "pods_on_device": sched.pods_solved_on_device,
            "pods_fallback": sched.pods_fallback,
            "classified": getattr(sched, "admissions_classified", 0),
            "reclassified": getattr(sched, "reclassifications", 0),
            "volume_reject_retries": getattr(
                sched, "volume_reject_retries", 0
            ),
            "envelope_fallbacks": sched.envelope_fallbacks,
            "pipeline_drains": sched.pipeline_drains,
            "state_reuses": sched.state_reuses,
            "state_uploads": sched.state_uploads,
            "delta_rows_uploaded": getattr(
                sched, "delta_rows_uploaded", 0
            ),
            "carry_divergences": getattr(
                sched, "carry_divergences", 0
            ),
            "membership_row_patches": getattr(
                sched, "membership_row_patches", 0
            ),
            "gang_resolves": sched.gang_resolves,
        }
        tc = getattr(sched, "tensor_cache", None)
        if tc is not None:
            # churn observability: slot adds/retires vs counted full
            # repacks (a lifecycle workload should move the first two
            # and leave full_repacks at the one cold pack)
            result["solver"]["tensor_full_repacks"] = tc.full_repacks
            result["solver"]["tensor_rows_added"] = tc.rows_added
            result["solver"]["tensor_rows_retired"] = tc.rows_retired
        qm = getattr(sched, "quarantine", None)
        if poison_names or (qm is not None and qm.isolations):
            # blast-radius containment labels (the poison-chaos row's
            # own numbers): bisection work done, the strike ledger, and
            # the parked outcome the ok verdict above depends on
            result["containment"] = {
                "poison_pods": len(poison_names),
                "bisections": getattr(sched, "bisections", 0),
                "isolations": qm.isolations if qm is not None else 0,
                "holds": qm.holds if qm is not None else 0,
                "parks": qm.parks if qm is not None else 0,
                "quarantine_parked": (
                    sched.queue.quarantine_parked_count()
                ),
                "carry_audit_heals": getattr(
                    sched, "carry_audit_heals", 0
                ),
            }
        if preempt_cfg:
            from kubernetes_tpu.utils import metrics as _metrics

            pre = sched.preemptor
            prec: Dict[str, Any] = {
                "waves": pre.waves,
                # which tier the LAST wave actually solved on (the
                # solver_mesh_tier analogue: pallas / xla / host)
                "wave_tier": pre.wave_solver_tier,
                "budget_denials": pre.budget_denials,
                "victims_slow_death": pre.victims_slow_death,
                "device_preemptions": pre.device_preemptions,
                "host_preemptions": pre.host_preemptions,
                "evictions_blocked_by_pdb": int(
                    _metrics.evictions_blocked_by_pdb.value()
                    - preempt_metrics0["blocked"]
                ),
                "nominations_set": int(
                    _metrics.nominations_set.value()
                    - preempt_metrics0["nominations_set"]
                ),
                "nominations_cleared": int(
                    _metrics.nominations_cleared.value()
                    - preempt_metrics0["nominations_cleared"]
                ),
            }
            for tier, n in sorted(pre.victims_by_tier.items()):
                prec[f"victims_{tier}"] = n
            thr = preempt_cfg.get("high_priority_threshold")
            if thr is not None:
                # the inversion pin: with a threshold declared, EVERY
                # high-band pod must have bound -- an unbound high pod
                # fails the row even when the bulk fraction passed
                unbound = sum(
                    1 for p in client.list_pods()[0]
                    if p.spec.priority >= int(thr)
                    and not p.spec.node_name
                    and p.metadata.deletion_timestamp is None
                )
                prec["high_priority_unbound"] = unbound
                result["ok"] = bool(result["ok"]) and unbound == 0
            result["preemption"] = prec
        if quota_ctrl is not None:
            # fairness + ledger labels: Jain index over per-tenant bind
            # counts, the min-tenant share of fair share, the dominant-
            # share spread, and the quota ledger's counters. Overspend
            # (any quota's used > hard) fails the row outright -- the
            # zero-overspend invariant is the acceptance bar.
            thr0 = (tenancy_cfg or {}).get("high_priority_threshold")
            if thr0 is not None:
                # settle: the high band binds through PREEMPTION waves
                # (evict -> victim termination -> nominee rebind), which
                # keep landing after the bulk fraction went quiet --
                # read the inversion verdict only once the band settled
                # (bounded; a genuinely starved band still fails below)
                settle_deadline = time.time() + 120
                while time.time() < settle_deadline:
                    if not any(
                        p.spec.priority >= int(thr0)
                        and not p.spec.node_name
                        and p.metadata.deletion_timestamp is None
                        for p in client.list_pods()[0]
                    ):
                        break
                    time.sleep(0.25)
                sched.wait_for_inflight_binds(timeout=60)
            per_ns: Dict[str, int] = {}
            overspend = False
            all_pods, _rv = client.list_pods()
            for p in all_pods:
                if p.spec.node_name and p.metadata.namespace.startswith(
                    "tenant-"
                ):
                    per_ns[p.metadata.namespace] = per_ns.get(
                        p.metadata.namespace, 0
                    ) + 1
            for q, _rv2 in [client.list_resource_quotas()]:
                for quota_obj in q:
                    for rname, hard_qty in quota_obj.hard.items():
                        if quota_obj.status.used.get(rname, 0) > hard_qty:
                            overspend = True
            counts = [
                per_ns.get(f"tenant-{t}", 0)
                for t in range(max(1, n_namespaces))
            ]
            total_bound = sum(counts)
            jain = 0.0
            if total_bound:
                jain = (total_bound ** 2) / (
                    len(counts) * sum(c * c for c in counts)
                )
            fair = total_bound / max(1, len(counts))
            min_fair_frac = (
                min(counts) / fair if fair > 0 else 1.0
            )
            tt = getattr(sched, "tenant_shares", None)
            trec: Dict[str, Any] = {
                "namespaces": n_namespaces,
                "jain_bind_index": round(jain, 4),
                "min_fair_fraction": round(min_fair_frac, 4),
                "max_dominant_share": (
                    round(tt.max_share(), 4) if tt is not None else 0.0
                ),
                "dominant_share_spread": (
                    round(tt.share_spread(), 4) if tt is not None else 0.0
                ),
                "quota_denials": quota_ctrl.admissions_denied,
                "quota_grants": quota_ctrl.admissions_granted,
                "quota_refunds": quota_ctrl.refunds,
                "quota_releases": quota_ctrl.releases,
                "quota_parked": sched.queue.quota_parked_count(),
                "overspend": overspend,
            }
            result["tenant"] = trec
            result["ok"] = bool(result["ok"]) and not overspend
            min_jain = (tenancy_cfg or {}).get("min_jain")
            if min_jain is not None:
                result["ok"] = bool(result["ok"]) and (
                    jain >= float(min_jain)
                )
            min_ff = (tenancy_cfg or {}).get("min_fair_fraction")
            if min_ff is not None:
                result["ok"] = bool(result["ok"]) and (
                    min_fair_frac >= float(min_ff)
                )
            thr = (tenancy_cfg or {}).get("high_priority_threshold")
            if thr is not None:
                # the multi-tenant inversion pin: every high-band pod
                # binds even while the bulk flood contends across
                # tenants and quotas
                unbound_high = sum(
                    1 for p in all_pods
                    if p.spec.priority >= int(thr)
                    and not p.spec.node_name
                    and p.metadata.deletion_timestamp is None
                )
                trec["high_priority_unbound"] = unbound_high
                result["ok"] = bool(result["ok"]) and unbound_high == 0
        if fleet is not None:
            # closed-loop labels + the Running gate: the row fails
            # unless the needed fraction of measured pods is RUNNING
            # (not merely bound), none of them sits on a zombie, and
            # the ack/rebind/eviction ledgers ride along for the
            # dashboard
            tracker = sched.bind_ack_tracker
            frec: Dict[str, Any] = {
                "pods_running": pods_running,
                "pods_acked": fleet.pods_acked,
                "heartbeats": fleet.heartbeats_sent,
                "heartbeat_lapses": fleet.heartbeat_lapses,
                "stale_acks": fleet.stale_acks,
                "acks_suppressed": fleet.acks_suppressed,
            }
            if tracker is not None:
                frec.update(
                    acks=tracker.acks,
                    acks_late=tracker.acks_late,
                    ack_timeouts=tracker.timeouts,
                    rebinds=tracker.rebinds,
                    ack_pending=tracker.pending_count(),
                )
            if fleet_lifecycle is not None:
                frec.update(
                    evictions=fleet_lifecycle.evictions,
                    evictions_blocked=fleet_lifecycle.evictions_blocked,
                )
            if fleet_respawner is not None:
                frec["pods_respawned"] = fleet_respawner.respawned
            if zombie_nodes:
                zset = set(zombie_nodes)
                on_zombie = sum(
                    1 for p in client.list_pods()[0]
                    if p.spec.node_name in zset
                    and p.metadata.deletion_timestamp is None
                )
                frec["pods_on_zombies"] = on_zombie
                result["ok"] = bool(result["ok"]) and on_zombie == 0
            if fleet_dark_state is not None:
                on_dark = _running_on_dark()
                frec["storm_fired"] = bool(fleet_dark_state["fired"])
                frec["pods_on_dark"] = on_dark
                result["ok"] = bool(
                    result["ok"]
                    and fleet_dark_state["fired"]
                    and on_dark == 0
                )
            result["fleet"] = frec
            result["ok"] = bool(result["ok"]) and pods_running >= need
        if lifecycle_counters:
            result["lifecycle"] = lifecycle_counters
        if streaming_rec:
            if controller is not None:
                streaming_rec.update(
                    window_ms=round(controller.window * 1000, 2),
                    batch_cap=controller.batch_cap,
                    window_changes=controller.window_changes,
                    cap_changes=controller.cap_changes,
                )
            result["streaming"] = streaming_rec
        return result
    finally:
        # EVERY component stops on EVERY exit path (including exceptions
        # mid-churn): leaked scheduler/informer/collector/heartbeat
        # threads would keep running against the abandoned server and
        # perturb every later workload in the matrix
        if coll is not None:
            coll.stop()
        if engine is not None:
            engine.stop()
        if lifecycle_stop is not None:
            lifecycle_stop.set()
        for comp in lifecycle_stoppers:
            try:
                comp.stop()
            except Exception:  # noqa: BLE001 - teardown keeps going
                pass
        for comp in preempt_stoppers:
            try:
                comp.stop()
            except Exception:  # noqa: BLE001 - teardown keeps going
                pass
        for comp in tenancy_stoppers:
            try:
                comp.stop()
            except Exception:  # noqa: BLE001 - teardown keeps going
                pass
        if injector is not None:
            from kubernetes_tpu.robustness.faults import install_injector

            install_injector(None)
        sched.stop()
        if hollow is not None:
            hollow.stop()
        for comp in (fleet_respawner, fleet_lifecycle,
                     fleet_disruption, fleet):
            if comp is not None:
                try:
                    comp.stop()
                except Exception:  # noqa: BLE001 - teardown keeps going
                    pass
        informers.stop()


def to_data_items(results: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The reference dashboard JSON shape (util.go:109 DataItems). Every
    item names the device JAX ran on, so a CPU row never reads as a
    chip row."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": str(len(devices)),
    }
    items = []
    for r in results:
        labels = {"Name": r["name"], **device}
        labels.update(
            {f"solver_{k}": str(v) for k, v in (r.get("solver") or {}).items()}
        )
        labels.update(
            {
                f"containment_{k}": str(v)
                for k, v in (r.get("containment") or {}).items()
            }
        )
        labels.update(
            {
                f"lifecycle_{k}": str(v)
                for k, v in (r.get("lifecycle") or {}).items()
            }
        )
        labels.update(
            {
                f"streaming_{k}": str(v)
                for k, v in (r.get("streaming") or {}).items()
            }
        )
        labels.update(
            {
                f"partition_{k}": str(v)
                for k, v in (r.get("partition") or {}).items()
            }
        )
        labels.update(
            {
                f"preemption_{k}": str(v)
                for k, v in (r.get("preemption") or {}).items()
            }
        )
        labels.update(
            {
                f"tenant_{k}": str(v)
                for k, v in (r.get("tenant") or {}).items()
            }
        )
        labels.update(
            {
                f"fleet_{k}": str(v)
                for k, v in (r.get("fleet") or {}).items()
            }
        )
        if r.get("error") or not r.get("ok", False):
            labels["error"] = r.get("error", f"{r.get('bound')}/{r.get('total')} bound")
        items.append(
            {
                # "Average" keeps the reference semantics (mean of 1s
                # window samples, util.go:197); the end-to-end
                # bound/elapsed rate rides its own "Overall" key
                "data": {
                    **(r.get("throughput_samples") or {}),
                    "Overall": r.get("throughput_pods_per_s", 0.0),
                },
                "unit": "pods/s",
                "labels": {**labels, "Metric": "SchedulingThroughput"},
            }
        )
        if r.get("latency_ms"):
            items.append(
                {
                    "data": dict(r["latency_ms"]),
                    "unit": "ms",
                    "labels": {**labels, "Metric": "PodToBindLatency"},
                }
            )
        if r.get("utilization_cpu"):
            # placement quality (the Churn vs ChurnSinkhorn A/B hinges
            # on spread, not throughput): per-node cpu utilization
            # mean / stddev / max after the workload settles
            items.append(
                {
                    "data": dict(r["utilization_cpu"]),
                    "unit": "fraction",
                    "labels": {**labels, "Metric": "NodeCpuUtilization"},
                }
            )
    return {"version": "v1", "dataItems": items}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    import yaml

    ap = argparse.ArgumentParser(prog="benchmarks")
    ap.add_argument(
        "--config",
        default=os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "config",
            "performance-config.yaml",
        ),
    )
    ap.add_argument("--out", default="BENCHMARKS.json")
    ap.add_argument("--only", default="", help="substring filter on workload name")
    args = ap.parse_args(argv)

    from kubernetes_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    with open(args.config) as f:
        cfg = yaml.safe_load(f)
    defaults = cfg.get("defaults") or {}
    results = []
    for wl in cfg.get("workloads") or []:
        if args.only and args.only not in wl["name"]:
            continue
        print(f"=== {wl['name']} ===", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        try:
            r = run_workload(wl, defaults)
        except Exception as e:  # noqa: BLE001 - keep the matrix running
            import traceback

            traceback.print_exc()
            r = {"name": wl["name"], "ok": False, "error": repr(e)}
        r["wall_s"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(r), file=sys.stderr, flush=True)
        results.append(r)

    # cross-row throughput floors (`throughput_floor: {of: <row>,
    # fraction: F}`): the closed-loop BigClusterBasic row must keep
    # >= F of its bind-and-forget sibling's throughput -- the ack spine
    # may not eat the pipeline. Evaluated after the matrix so the
    # reference row's number exists; a missing/failed reference row
    # skips the floor rather than inventing one.
    by_name = {r["name"]: r for r in results}
    for wl in cfg.get("workloads") or []:
        floor = wl.get("throughput_floor")
        if not floor or wl["name"] not in by_name:
            continue
        row = by_name[wl["name"]]
        ref = by_name.get(floor.get("of", ""))
        if ref is None or not ref.get("ok"):
            continue
        frac = float(floor.get("fraction", 0.8))
        ref_thr = float(ref.get("throughput_pods_per_s", 0.0))
        row_thr = float(row.get("throughput_pods_per_s", 0.0))
        row["throughput_floor"] = {
            "of": floor.get("of"), "fraction": frac,
            "reference_pods_per_s": ref_thr,
        }
        if ref_thr > 0 and row_thr < frac * ref_thr:
            row["ok"] = False
            row["error"] = (
                f"closed-loop throughput {row_thr} < {frac} x "
                f"{ref_thr} ({floor.get('of')})"
            )

    out = to_data_items(results)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if all(r.get("ok") for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
