"""chip_smoke.py: the quickest proof that the scheduler still starts and
solves on the chip.

One process drives the batch-solver path through the entry points a
cluster operator would use -- ``load_config_from_dict`` ->
``new_scheduler_from_config`` over the in-process ``APIServer``,
``informers.start()``, ``sched.warmup()``, ``sched.start()``, creates
through ``client.create_pods_bulk`` -- at the largest ``scheduler_perf``
scale (5,000 nodes of 32 CPU / 64 Gi / 110 pods, BASELINE.md) with a
10,000-pod burst at ``maxBatch: 4096``, then a constrained wave, a
preemption wave and (with >= 4 devices) a 4-device mesh burst.

It cannot pass while the device is hidden: it refuses to run unless JAX
reports a TPU and the native host plane is built, and every phase
asserts that its batches were solved on the ``pallas`` tier with the
``xla`` / ``host_greedy`` / ``sequential`` tiers and ``pods_fallback``
all at zero. Each phase also checks its placements by independent
means (capacity replay from the pods this script created, skew and
anti-affinity from the bound pods, Pallas vs XLA vs numpy on one batch).
The first failed check ends the run with a non-zero exit code.

The last line of stdout is one JSON object with exactly two keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it. The line before it, ``report: {...}``,
holds the per-phase counters, this process's warm-up seconds (a set-up
observation, not a metric) and the compile-cache hit count.

    python chip_smoke.py [--phases plain,constrained,preempt,mesh] [--seed N]

``--phases`` exists so the mesh phase can be run alone on a four-chip
host without paying for the single-chip phases four times over; the
default runs everything the visible devices allow.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
POOL = "smoke/pool"
PHASES = ("plain", "constrained", "preempt", "mesh")
NODE_CPU_MILLI = 32000
NODE_MEM_BYTES = 64 << 30
NODE_PODS = 110


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Cluster and wave sizes. ``FULL`` is what ``main`` runs; the
    tier-1 test drives the same phases at a size a CPU finishes."""

    nodes: int = 5000
    zones: int = 10
    burst_pods: int = 10000
    max_batch: int = 4096
    parity_pods: int = 4096
    spread_apps: int = 4
    spread_per_app: int = 200
    anti_apps: int = 3
    anti_per_app: int = 64
    pool_nodes: int = 200
    fill_cpu_milli: int = 3500  # 9 fillers saturate a 32-CPU node
    high_pods: int = 400
    mesh_devices: int = 4
    mesh_pods: int = 4096
    timeout_s: float = 300.0


FULL = Sizes()


# -- the cluster ----------------------------------------------------------


class Stack:
    """One scheduler stack built through the config surface."""

    def __init__(
        self, sizes: Sizes, mesh_devices: int = 0,
        expect_tier: str = "pallas",
    ) -> None:
        """``expect_tier``: the ladder tier every batch must solve on.
        ``main`` always expects ``pallas``; the tier-1 test, which has
        no chip, drives the same phases expecting ``xla``."""
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.informer import InformerFactory
        from kubernetes_tpu.config.loader import load_config_from_dict
        from kubernetes_tpu.scheduler.scheduler import (
            new_scheduler_from_config,
        )
        from kubernetes_tpu.testing import make_node

        self.sizes = sizes
        self.expect_tier = expect_tier
        self.server = APIServer()
        self.client = Client(self.server)
        self.informers = InformerFactory(self.server)
        solver = {"maxBatch": sizes.max_batch}
        if mesh_devices:
            solver["meshDevices"] = mesh_devices
        self.sched = new_scheduler_from_config(
            self.client, self.informers,
            load_config_from_dict({"tpuSolver": solver}),
        )
        for i in range(sizes.nodes):
            pool = "preempt" if i >= sizes.nodes - sizes.pool_nodes else "general"
            self.client.create_node(
                make_node(f"node-{i}")
                .capacity(cpu="32", memory="64Gi", pods=NODE_PODS)
                .label(ZONE, f"zone-{i % sizes.zones}")
                .label(HOSTNAME, f"node-{i}")
                .label(POOL, pool)
                .obj()
            )
        self.informers.start()
        self.informers.wait_for_cache_sync()
        #: pod name -> (cpu milli, memory bytes) as THIS script created
        #: it: the capacity replay's independent source of truth
        self.created: dict = {}
        self.warmup_s = 0.0
        self._sealed_sizes: dict = {}
        self._fallbacks_at_start = _fallback_samples()

    def warm_and_start(self) -> None:
        from kubernetes_tpu.ops import assignment

        t0 = time.perf_counter()
        self.sched.warmup()
        self.warmup_s = time.perf_counter() - t0
        # warm-up holds every kernel it compiled to the XLA scan
        # (BatchScheduler._pallas_canary); one that disagreed is off
        check(
            not assignment._PALLAS_DISTRUST,
            "warm-up canary: compiled Pallas kernels disagree with the "
            f"XLA scan at {sorted(assignment._PALLAS_DISTRUST)}",
        )
        self._sealed_sizes = self.jit_sizes()
        self.sched.start()

    def jit_sizes(self) -> dict:
        from kubernetes_tpu.ops.assignment import jit_cache_sizes

        return jit_cache_sizes(self.sched.mesh)

    def compiles_since_seal(self) -> dict:
        """Solver signatures compiled after ``_jit_watch.seal()``."""
        now = self.jit_sizes()
        return {
            k: v - self._sealed_sizes.get(k, 0)
            for k, v in now.items()
            if v != self._sealed_sizes.get(k, 0)
        }

    def stop(self) -> None:
        self.sched.stop()
        self.informers.stop()

    # -- pods -------------------------------------------------------------

    def pod(self, name: str, cpu_milli: int, mem_mib: int):
        from kubernetes_tpu.testing import make_pod

        self.created[name] = (cpu_milli, mem_mib << 20)
        return make_pod(name).container(
            cpu=f"{cpu_milli}m", memory=f"{mem_mib}Mi"
        )

    def submit_and_wait(self, pods, what: str) -> None:
        """Create ``pods`` in bulk chunks and wait until every one is
        bound, from a watch stream opened before the first create."""
        from bench import BindWatcher

        names = [p.metadata.name for p in pods]
        watcher = BindWatcher(self.server, names)
        try:
            for i in range(0, len(pods), 256):
                self.client.create_pods_bulk(pods[i:i + 256])
            done = watcher.wait_for_targets(
                time.time() + self.sizes.timeout_s
            )
            self.sched.wait_for_inflight_binds(timeout=60)
        finally:
            watcher.stop()
        bound = sum(1 for n in names if n in watcher.bind_times)
        check(
            done and bound == len(names),
            f"{what}: {bound} of {len(names)} pods bound within "
            f"{self.sizes.timeout_s:.0f}s",
        )

    def bound_pods(self) -> dict:
        """name -> node of every bound pod, from the apiserver."""
        pods, _ = self.client.list_pods()
        return {
            p.metadata.name: p.spec.node_name
            for p in pods if p.spec.node_name
        }

    # -- checks -----------------------------------------------------------

    def tier_ledger(self) -> dict:
        return dict(self.sched.ladder.solves_by_tier)

    def check_tiers(self, before: dict, what: str) -> dict:
        """Since ``before``: some batch solved on the expected device
        tier, none on any tier below it, nothing fell to the host path."""
        from kubernetes_tpu.robustness.ladder import TIERS

        now = self.tier_ledger()
        delta = {t: now[t] - before.get(t, 0) for t in now}
        check(
            delta[self.expect_tier] > 0,
            f"{what}: no batch solved on tier {self.expect_tier}: {delta}",
        )
        for tier in TIERS[TIERS.index(self.expect_tier) + 1:]:
            check(
                delta[tier] == 0,
                f"{what}: {delta[tier]} batch(es) degraded to tier "
                f"{tier}: {delta}",
            )
        check(
            self.sched.pods_fallback == 0,
            f"{what}: pods_fallback={self.sched.pods_fallback}",
        )
        return delta

    def check_capacity(self, what: str) -> int:
        """Independent host replay: sum what this script asked for, per
        node, from the apiserver's bindings; no node may end over its
        allocatable on cpu, memory or pod count."""
        cpu: dict = {}
        mem: dict = {}
        count: dict = {}
        for name, node in self.bound_pods().items():
            c, m = self.created[name]
            cpu[node] = cpu.get(node, 0) + c
            mem[node] = mem.get(node, 0) + m
            count[node] = count.get(node, 0) + 1
        over = [
            n for n in count
            if cpu[n] > NODE_CPU_MILLI or mem[n] > NODE_MEM_BYTES
            or count[n] > NODE_PODS
        ]
        check(not over, f"{what}: nodes over allocatable: {over[:5]}")
        return len(count)


def _fallback_samples() -> list:
    """The ``scheduler_solver_fallback_total`` sample lines so far."""
    from kubernetes_tpu.utils import metrics

    return [
        line for line in metrics.solver_fallbacks.collect()
        if not line.startswith("#")
    ]


# -- phases ---------------------------------------------------------------


def phase_plain(stack: Stack, rng) -> dict:
    """The 10k-pod burst of plain pods."""
    sizes = stack.sizes
    before = stack.tier_ledger()
    pods = [
        stack.pod(
            f"burst-{i}",
            int(rng.choice([100, 250, 500, 1000])),
            int(rng.choice([128, 256, 512, 1024])),
        ).obj()
        for i in range(sizes.burst_pods)
    ]
    stack.submit_and_wait(pods, "plain")
    tiers = stack.check_tiers(before, "plain")
    fallbacks = _fallback_samples()
    check(
        fallbacks == stack._fallbacks_at_start,
        f"plain: scheduler_solver_fallback_total moved: {fallbacks}",
    )
    recompiled = stack.compiles_since_seal()
    check(not recompiled, f"plain: compiled after seal(): {recompiled}")
    nodes_used = stack.check_capacity("plain")
    return {
        "bound": sizes.burst_pods,
        "nodes": sizes.nodes,
        "nodes_used": nodes_used,
        "tiers": tiers,
        "pods_fallback": stack.sched.pods_fallback,
        "compiles_after_seal": 0,
    }


def phase_parity(stack: Stack, rng) -> dict:
    """One batch at the burst's shape, against the node state the burst
    left, solved three ways: the chip's Pallas kernel, the XLA scan and
    the numpy replay. The ladder calls its tiers interchangeable; this
    is where that is held to the chip's float32."""
    import math

    import numpy as np

    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.ops.assignment import pallas_candidate, solve_packed
    from kubernetes_tpu.ops.host_masks import static_mask_compact
    from kubernetes_tpu.robustness.ladder import host_greedy_assign
    from kubernetes_tpu.tensors import NodeTensorCache, pack_pod_batch
    from kubernetes_tpu.testing import make_pod

    sizes = stack.sizes
    snapshot = Snapshot()
    stack.sched.cache.update_snapshot(snapshot)
    nt = NodeTensorCache().update(snapshot)
    pods = [
        make_pod(f"parity-{i}").container(
            cpu=f"{int(rng.choice([100, 250, 500, 1000, 2000]))}m",
            memory=f"{int(rng.choice([128, 256, 512, 1024, 4096]))}Mi",
        ).obj()
        for i in range(sizes.parity_pods)
    ]
    batch = pack_pod_batch(pods, nt.dims)
    mask_rows, mask_index = static_mask_compact(pods, snapshot, nt)
    b = batch.size
    padded = max(sizes.max_batch, 64 * math.ceil(b / 64))
    order = batch.order
    req = np.zeros((padded, nt.dims.num_dims), dtype=np.int32)
    nzr = np.zeros((padded, 2), dtype=np.int32)
    midx = np.zeros(padded, dtype=np.int32)
    active = np.zeros(padded, dtype=bool)
    req[:b] = batch.requests[order]
    nzr[:b] = batch.non_zero_requests[order]
    midx[:b] = mask_index[order]
    active[:b] = True
    rows = np.zeros((8 * math.ceil(mask_rows.shape[0] / 8), nt.capacity), bool)
    rows[: mask_rows.shape[0]] = mask_rows
    pieces = [
        ("req", req), ("nzr", nzr), ("midx", midx),
        ("active", active.astype(np.int32)),
        ("rows", rows.astype(np.int32)),
        ("alloc", nt.allocatable), ("valid", nt.valid.astype(np.int32)),
        ("req_state", nt.requested), ("nzr_state", nt.non_zero_requested),
    ]
    config = stack.sched.solver_config
    check(
        pallas_candidate(
            "greedy", padded, nt.capacity, nt.dims.num_dims, rows.shape[0]
        ),
        "parity: this shape would not run the Pallas kernel",
    )

    def device(allow_pallas: bool):
        out = solve_packed(
            pieces, None, None, None, None, config=config, mode="greedy",
            allow_pallas=allow_pallas,
        )
        return tuple(np.asarray(x) for x in out[:3])

    pallas = device(True)
    xla = device(False)
    host = host_greedy_assign(
        nt.allocatable, nt.requested, nt.non_zero_requested, nt.valid,
        req, nzr, rows, midx, active, config=config,
    )
    result = {"pods": b, "placed": int((pallas[0][:b] >= 0).sum())}
    for name, other in (("xla", xla), ("host_greedy", host)):
        diff = np.flatnonzero(pallas[0] != other[0])
        result[f"pallas_vs_{name}_mismatches"] = int(diff.size)
        check(
            diff.size == 0,
            f"parity: pallas and {name} disagree on {diff.size} of "
            f"{padded} slots, first at {diff[:5].tolist()}: pallas "
            f"{pallas[0][diff[:5]].tolist()} vs {other[0][diff[:5]].tolist()}",
        )
        check(
            np.array_equal(pallas[1], other[1])
            and np.array_equal(pallas[2], other[2]),
            f"parity: pallas and {name} agree on placements but not on "
            "the post-batch node state",
        )
    check(result["placed"] > 0, "parity: nothing placed")
    return result


def phase_constrained(stack: Stack, rng) -> dict:
    """Hard zone spread plus required per-host anti-affinity: compiles
    and runs ``pallas_constrained_solve``."""
    sizes = stack.sizes
    before = stack.tier_ledger()
    pods = []
    for a in range(sizes.spread_apps):
        for i in range(sizes.spread_per_app):
            pods.append(
                stack.pod(f"spread-{a}-{i}", 200, 256)
                .labels(app=f"spread-{a}")
                .spread_constraint(
                    max_skew=1, topology_key=ZONE,
                    when_unsatisfiable="DoNotSchedule",
                    match_labels={"app": f"spread-{a}"},
                ).obj()
            )
    for a in range(sizes.anti_apps):
        for i in range(sizes.anti_per_app):
            pods.append(
                stack.pod(f"anti-{a}-{i}", 200, 256)
                .labels(app=f"anti-{a}")
                .pod_affinity(HOSTNAME, {"app": f"anti-{a}"}, anti=True)
                .obj()
            )
    order = rng.permutation(len(pods))
    pods = [pods[int(k)] for k in order]
    stack.submit_and_wait(pods, "constrained")
    tiers = stack.check_tiers(before, "constrained")
    bound = stack.bound_pods()
    worst_skew = 0
    for a in range(sizes.spread_apps):
        per_zone = [0] * sizes.zones
        for i in range(sizes.spread_per_app):
            node = bound[f"spread-{a}-{i}"]
            per_zone[int(node.split("-")[1]) % sizes.zones] += 1
        skew = max(per_zone) - min(per_zone)
        worst_skew = max(worst_skew, skew)
        check(skew <= 1, f"constrained: spread-{a} zone skew {skew}: {per_zone}")
    for a in range(sizes.anti_apps):
        hosts = [bound[f"anti-{a}-{i}"] for i in range(sizes.anti_per_app)]
        check(
            len(set(hosts)) == len(hosts),
            f"constrained: anti-{a} shares a host: "
            f"{len(hosts) - len(set(hosts))} collisions",
        )
    stack.check_capacity("constrained")
    return {
        "bound": len(pods),
        "tiers": tiers,
        "pods_fallback": stack.sched.pods_fallback,
        "worst_zone_skew": worst_skew,
        "compiles_after_seal": stack.compiles_since_seal(),
    }


def _retire_anti_affinity_pods(stack: Stack) -> None:
    """The anti-affinity set finishes and is deleted. While pods with
    required anti-affinity exist, the device victim search is not exact
    (Preemptor.device_eligible) and waves take the host oracle."""
    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.ops.affinity import (
        cluster_has_required_anti_affinity,
    )

    sizes = stack.sizes
    for a in range(sizes.anti_apps):
        for i in range(sizes.anti_per_app):
            name = f"anti-{a}-{i}"
            stack.client.delete_pod("default", name)
            del stack.created[name]
    deadline = time.time() + 60
    while time.time() < deadline:
        snapshot = Snapshot()
        stack.sched.cache.update_snapshot(snapshot)
        if not cluster_has_required_anti_affinity(snapshot):
            return
        time.sleep(0.1)
    raise SmokeFailure("preempt: anti-affinity pods never left the cache")


def phase_preempt(stack: Stack, rng) -> dict:
    """Saturate the ``preempt`` pool with priority-0 pods, then land a
    high-priority burst on it: compiles and runs
    ``pallas_preempt_solve``."""
    sizes = stack.sizes
    _retire_anti_affinity_pods(stack)
    pre = stack.sched.preemptor
    # fill the pool to the brim: with the pool's nodes already carrying
    # whatever the earlier phases put there, submit fillers in rounds
    # until one no longer fits anywhere in the pool
    per_node = NODE_CPU_MILLI // sizes.fill_cpu_milli
    fillers = [
        stack.pod(f"fill-{i}", sizes.fill_cpu_milli, 512)
        .node_selector(**{POOL: "preempt"}).priority(0).obj()
        for i in range(sizes.pool_nodes * (per_node - 1))
    ]
    stack.submit_and_wait(fillers, "preempt fill")
    before = stack.tier_ledger()
    waves_before = pre.waves
    device_before = pre.device_preemptions
    wave_tiers_before = dict(pre.ladder.solves_by_tier)
    # every node of the pool now has less than 2 fillers' worth of CPU
    # free, so sizes.high_pods requests of 2 fillers each cannot fit
    # without evictions
    high_cpu = 2 * sizes.fill_cpu_milli
    high = [
        stack.pod(f"high-{i}", high_cpu, 512)
        .node_selector(**{POOL: "preempt"}).priority(1000).obj()
        for i in range(sizes.high_pods)
    ]
    stack.submit_and_wait(high, "preempt")
    wave_tiers = {
        t: pre.ladder.solves_by_tier[t] - wave_tiers_before.get(t, 0)
        for t in pre.ladder.solves_by_tier
    }
    check(
        pre.device_preemptions > device_before,
        "preempt: no pod took the device victim search "
        f"(host_preemptions={pre.host_preemptions})",
    )
    check(
        pre.wave_solver_tier == stack.expect_tier,
        f"preempt: wave tier {pre.wave_solver_tier!r}, ledger {wave_tiers}",
    )
    check(
        wave_tiers[stack.expect_tier] > 0
        and (stack.expect_tier == "xla" or wave_tiers["xla"] == 0),
        f"preempt: wave ledger {wave_tiers}",
    )
    # the victims were deleted: drop them from the replay's ledger
    live = stack.bound_pods()
    evicted = [n for n in stack.created if n not in live]
    check(
        all(n.startswith(("fill-", "burst-", "spread-")) for n in evicted),
        f"preempt: unexpected pods missing: {evicted[:5]}",
    )
    for name in evicted:
        del stack.created[name]
    stack.check_capacity("preempt")
    now = stack.tier_ledger()
    return {
        "high_priority_bound": sizes.high_pods,
        "fillers": len(fillers),
        "evicted": len(evicted),
        "waves": pre.waves - waves_before,
        "device_preemptions": pre.device_preemptions - device_before,
        "wave_tier": pre.wave_solver_tier,
        "wave_tiers": wave_tiers,
        "batch_tiers": {t: now[t] - before.get(t, 0) for t in now},
        "pods_fallback": stack.sched.pods_fallback,
    }


def phase_mesh(sizes: Sizes, rng, expect_tier: str = "pallas") -> dict:
    """The same cluster on a ``meshDevices``-device mesh: the carry
    sharded over the node axis, the shard_map'd Pallas tier.
    ``expect_tier`` is what the ledger has to say: off a TPU the
    shard_map tier runs without its kernel and is counted ``xla``."""
    stack = Stack(
        sizes, mesh_devices=sizes.mesh_devices, expect_tier=expect_tier
    )
    try:
        stack.warm_and_start()
        sched = stack.sched
        pods = [
            stack.pod(
                f"mesh-{i}",
                int(rng.choice([100, 250, 500, 1000])),
                int(rng.choice([128, 256, 512, 1024])),
            ).obj()
            for i in range(sizes.mesh_pods)
        ]
        stack.submit_and_wait(pods, "mesh")
        tiers = stack.check_tiers({}, "mesh")
        check(
            sched.mesh_solver_tier == "pallas",
            f"mesh: tier {sched.mesh_solver_tier!r}",
        )
        carry = sched.device_state.req_dev
        check(carry is not None, "mesh: no resident carry after the burst")
        shards = carry.addressable_shards
        devices = {s.device for s in shards}
        rows = {s.data.shape[0] for s in shards}
        check(
            len(devices) == sizes.mesh_devices
            and rows == {carry.shape[0] // sizes.mesh_devices},
            f"mesh: carry on {len(devices)} device(s), shard rows {rows} "
            f"of {carry.shape[0]}",
        )
        check(
            sched.state_uploads <= 1,
            f"mesh: {sched.state_uploads} full state uploads",
        )
        check(
            sched.carry_divergences == 0,
            f"mesh: {sched.carry_divergences} carry divergences",
        )
        recompiled = stack.compiles_since_seal()
        check(not recompiled, f"mesh: compiled after warm-up: {recompiled}")
        stack.check_capacity("mesh")
        return {
            "bound": sizes.mesh_pods,
            "devices": len(devices),
            "shard_rows": rows.pop(),
            "tier": sched.mesh_solver_tier,
            "tiers": tiers,
            "state_uploads": sched.state_uploads,
            "carry_divergences": sched.carry_divergences,
            "compiles_after_warmup": 0,
            "warmup_s": round(stack.warmup_s, 1),
        }
    finally:
        stack.stop()


# -- the run --------------------------------------------------------------


def run(sizes: Sizes, phases, seed: int, device_count: int) -> dict:
    """Run ``phases`` in order; returns the per-phase counters. Raises
    SmokeFailure at the first check that does not hold."""
    import numpy as np

    rng = np.random.default_rng(seed)
    report: dict = {}
    single = [p for p in phases if p != "mesh"]
    if single:
        stack = Stack(sizes)
        try:
            stack.warm_and_start()
            report["warmup_s"] = round(stack.warmup_s, 1)
            for phase in single:
                print(f"phase {phase} ...", file=sys.stderr, flush=True)
                if phase == "plain":
                    report["plain"] = phase_plain(stack, rng)
                    report["parity"] = phase_parity(stack, rng)
                elif phase == "constrained":
                    report["constrained"] = phase_constrained(stack, rng)
                elif phase == "preempt":
                    report["preempt"] = phase_preempt(stack, rng)
                print(
                    f"phase {phase}: {json.dumps(report[phase])}",
                    file=sys.stderr, flush=True,
                )
        finally:
            stack.stop()
    if "mesh" in phases:
        if device_count >= sizes.mesh_devices:
            print("phase mesh ...", file=sys.stderr, flush=True)
            report["mesh"] = phase_mesh(sizes, rng)
        else:
            print(f"mesh: not run ({device_count} device)", flush=True)
            report["mesh"] = f"not run ({device_count} device)"
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset of " + ",".join(PHASES),
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")
    if "preempt" in phases and "constrained" not in phases:
        # the preemption phase retires the constrained wave's pods
        ap.error("phase preempt needs phase constrained")

    import jax

    from kubernetes_tpu import native
    from kubernetes_tpu.utils.compile_cache import (
        compile_cache_stats,
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"device_count: {device['count']}  jax: {jax.__version__}",
        flush=True,
    )
    if device["platform"] != "tpu":
        print(
            "chip_smoke: JAX found no TPU; refusing to run",
            file=sys.stderr,
        )
        return 2
    if native.hotpath is None:
        print(
            "chip_smoke: the native host plane did not build, and the "
            f"Python twins are a different program: {native.build_error}",
            file=sys.stderr,
        )
        return 2
    print(f"native: {os.path.basename(native.hotpath.__file__)}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)

    t0 = time.perf_counter()
    try:
        report = run(FULL, phases, args.seed, device["count"])
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        print("report: " + json.dumps({"error": str(e)}))
        print(result_line(False, device), flush=True)
        return 1
    cache = compile_cache_stats()
    print("report: " + json.dumps({
        "warmup_s": report.pop("warmup_s", None),
        "phases": report,
        "compile_cache": {**cache, "hit": cache["hits"] > 0, "dir": cache_dir},
        "total_s": round(time.perf_counter() - t0, 1),
    }))
    print(result_line(True, device), flush=True)
    return 0


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly ``ok`` and ``device``, the device
    exactly ``platform`` / ``kind`` / ``count``. Whoever checks the run
    reads this line and nothing else, so everything else the run learned
    goes on the ``report:`` line before it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


if __name__ == "__main__":
    sys.exit(main())
