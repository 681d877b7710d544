"""Tier-1 guard for the SHARDED mesh delta path (PR 9): the mesh
dispatch rides the same device-resident-carry + generation-handshake +
per-shard delta-scatter machinery as the single-device path.

- a steady 1k-pod burst on a simulated 2-device mesh performs AT MOST
  one full [N, R] node-state upload (``state_uploads`` must not scale
  with batch count), with zero handshake divergences, and places every
  pod IDENTICALLY to the sequential oracle;
- the randomized event-stream differential (interleaved membership
  churn, external pod churn, bind failures) extends to the sharded
  carry: after the stream settles, the device-resident ``req_state``
  must equal a fresh full pack of the host snapshot per node name, and
  the resident arrays must actually live sharded over the node axis.

Tests run on the virtual 8-device CPU mesh from conftest; a 2-device
sub-mesh keeps the GSPMD compiles cheap while still exercising real
cross-shard argmax collectives and shard-local scatters.
"""

import random
import time

import numpy as np
import pytest

import jax

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod

NUM_NODES = 16
NUM_PODS = 1000


def _mesh(n=2):
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n]), axis_names=("nodes",))


class _KeepFirstRng:
    """Deterministic tie-break for the sequential oracle (selectHost
    reservoir sampling): always keep the first candidate == the device
    argmax's lowest-index rule."""

    def randrange(self, n):
        return 1 if n > 1 else 0

    def randint(self, a, b):
        return b


def _wait_all_bound(client, count, timeout=180.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        pods, _ = client.list_pods()
        bound = [p for p in pods if p.spec.node_name]
        if len(bound) >= count:
            return pods
        time.sleep(0.05)
    bound = [p for p in client.list_pods()[0] if p.spec.node_name]
    raise AssertionError(f"only {len(bound)}/{count} pods bound")


def _run(seed, *, mesh, warmup=False):
    """Drive a seeded 1k-pod burst; with ``warmup`` (mesh runs) the
    solver variants compile first and the returned dict carries the
    mesh jit-cache size before/after the measured burst (the
    zero-mid-run-recompile probe, covering BOTH mesh tiers -- they
    share the one jitted mesh solver)."""
    rng = random.Random(seed)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=mesh is not None, max_batch=256,
        mesh=mesh, rng=_KeepFirstRng(),
    )
    for i in range(NUM_NODES):
        client.create_node(
            make_node(f"m{i}")
            .capacity(cpu="64", memory="256Gi", pods=120)
            .obj()
        )
    pods = []
    for i in range(NUM_PODS):
        pods.append(
            make_pod(f"b{i}")
            .creation_timestamp(float(i))
            .container(
                cpu=f"{rng.choice([100, 200, 250])}m",
                memory=f"{rng.choice([128, 256])}Mi",
            )
            .obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    probe = {}
    if warmup and mesh is not None:
        from kubernetes_tpu.ops.assignment import mesh_packed_cache_size

        sched.warmup()
        probe["cache_before"] = mesh_packed_cache_size(mesh)
    for p in pods:
        client.create_pod(p)
    sched.start()
    _wait_all_bound(client, NUM_PODS)
    sched.wait_for_inflight_binds()
    if warmup and mesh is not None:
        from kubernetes_tpu.ops.assignment import mesh_packed_cache_size

        probe["cache_after"] = mesh_packed_cache_size(mesh)
    placements = {
        p.metadata.name: p.spec.node_name
        for p in client.list_pods()[0]
    }
    sched.stop()
    informers.stop()
    return placements, sched, probe


def _assert_steady_guard(sched):
    """The PR-9 steady-state invariants, tier-independent."""
    # the whole burst rode the sharded device path
    assert sched.pods_fallback == 0
    assert sched.pods_solved_on_device == NUM_PODS
    assert sched.batches_solved >= 2, (
        "burst completed in one batch; the guard needs a multi-batch "
        "steady state to prove anything"
    )
    # THE guard: full [N, R] uploads do not scale with batch count on
    # the mesh either -- one cold upload, then pure per-shard reuse
    assert sched.state_uploads <= 1, (
        f"{sched.state_uploads} full node-state uploads for "
        f"{sched.batches_solved} mesh batches -- the sharded carry is "
        f"not resident"
    )
    assert sched.state_reuses >= sched.batches_solved - 1
    assert sched.carry_divergences == 0
    # steady-state link traffic is bounded by churn (zero churn here)
    assert sched.delta_rows_uploaded == 0


def test_mesh_steady_burst_uploads_bounded_and_oracle_parity():
    """Steady burst on the default mesh path -- the shard_map'd PALLAS
    tier (PR 10) -- AND on the GSPMD XLA twin (KTPU_MESH_PALLAS=0):
    both must place every pod identically to the sequential oracle,
    hold the PR-9 carry invariants, and hit ZERO mid-run recompiles
    against the warmed signature set."""
    mesh = _mesh(2)
    want, _oracle, _ = _run(42, mesh=None)
    got, sched, probe = _run(42, mesh=mesh, warmup=True)

    # zero placement divergence vs the sequential oracle
    assert all(want.values()), "oracle failed to place a fitting pod"
    assert got == want
    # the greedy burst must have solved on the shard_map'd Pallas tier
    assert sched.mesh_solver_tier == "pallas", (
        f"tier {sched.mesh_solver_tier!r}, "
        f"by_tier={sched.ladder.solves_by_tier}"
    )
    _assert_steady_guard(sched)
    # zero mid-run recompiles: warmup compiled every layout BOTH tiers
    # can hit; a new signature inside the burst is a regression
    assert probe["cache_after"] == probe["cache_before"], probe


def test_mesh_xla_twin_burst_parity(monkeypatch):
    """The same steady burst pinned to the GSPMD XLA twin
    (KTPU_MESH_PALLAS=0 preserves the pre-PR-10 behavior): identical
    placements, same carry invariants, zero mid-run recompiles."""
    monkeypatch.setenv("KTPU_MESH_PALLAS", "0")
    mesh = _mesh(2)
    want, _oracle, _ = _run(42, mesh=None)
    got_twin, sched, probe = _run(42, mesh=mesh, warmup=True)
    assert got_twin == want
    assert sched.mesh_solver_tier == "xla"
    assert sched.ladder.solves_by_tier.get("pallas", 0) == 0
    _assert_steady_guard(sched)
    assert probe["cache_after"] == probe["cache_before"], probe


def test_mesh_event_stream_differential_sharded_carry(monkeypatch):
    """The PR-5 randomized event-stream differential extended to the
    SHARDED carry: interleaved pod bursts, external pod deletes, a bind
    failure, and membership churn (a cold node joining mid-stream) on a
    2-device mesh must leave the device-resident ``req_state`` equal to
    a fresh full pack of the settled host snapshot -- per node name,
    across both shards -- with membership riding the slot scatter (no
    extra full upload) and every resident array actually node-sharded.
    """
    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.tensors import NodeTensorCache

    mesh = _mesh(2)
    rng = random.Random(20260803)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=True, max_batch=32, mesh=mesh,
    )
    for i in range(8):
        client.create_node(
            make_node(f"dm-n{i}")
            .capacity(cpu="64", memory="128Gi", pods=200)
            .obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()

    # one injected bind failure: the host diverges from the mirrored
    # expectation (the scatter-fix / counted-divergence case)
    orig_bulk = client.bind_assumed_bulk
    calls = {"n": 0}

    def flaky_bulk(assumed):
        calls["n"] += 1
        if calls["n"] == 3 and assumed:
            errs = orig_bulk(assumed[1:])
            return [(0, RuntimeError("synthetic bind failure"))] + [
                (i + 1, e) for i, e in errs
            ]
        return orig_bulk(assumed)

    monkeypatch.setattr(client, "bind_assumed_bulk", flaky_bulk)

    seq = 0
    uploads_after_cold = None
    for k in range(8):
        for _ in range(rng.randint(3, 8)):
            seq += 1
            client.create_pod(
                make_pod(f"dm-p{seq}")
                .container(
                    cpu=f"{rng.choice([100, 250, 500])}m",
                    memory="128Mi",
                )
                .obj()
            )
        if k == 3:
            # external churn: a controller deletes a bound pod behind
            # the scheduler's back
            bound = [
                p for p in client.list_pods()[0] if p.spec.node_name
            ]
            if bound:
                victim = rng.choice(bound)
                client.delete_pod(
                    victim.metadata.namespace, victim.metadata.name
                )
        if k == 5:
            # membership churn: a cold node claims a headroom slot --
            # on the mesh this must ride the shard-local slot scatter,
            # never a full re-upload
            client.create_node(
                make_node("dm-cold")
                .capacity(cpu="64", memory="128Gi", pods=200)
                .obj()
            )
            deadline = time.time() + 10
            while time.time() < deadline:
                if "dm-cold" in sched.cache._nodes:
                    break
                time.sleep(0.02)
            uploads_after_cold = sched.state_uploads
        deadline = time.time() + 30
        while time.time() < deadline:
            if sched.schedule_batch(timeout=0.2):
                break
    monkeypatch.setattr(client, "bind_assumed_bulk", orig_bulk)
    for _ in range(10):
        sched.schedule_batch(timeout=0.1)
    sched.wait_for_inflight_binds(timeout=60)

    # one quiet batch reconciles any leftover external change
    client.create_pod(
        make_pod("dm-final").container(cpu="100m", memory="64Mi").obj()
    )
    deadline = time.time() + 30
    while time.time() < deadline:
        if sched.schedule_batch(timeout=0.2):
            break
    sched.wait_for_inflight_binds(timeout=60)

    ds = sched.device_state
    assert ds.req_dev is not None, "sharded carry was dropped"
    # the resident state actually lives sharded over the node axis
    shard_rows = ds.req_dev.addressable_shards[0].data.shape[0]
    assert shard_rows * 2 == ds.req_dev.shape[0], (
        "resident req_state is not sharded over the 2-device mesh"
    )
    assert (
        ds.alloc_dev.addressable_shards[0].data.shape[0] * 2
        == ds.alloc_dev.shape[0]
    )

    # membership churn rode the slot scatter: no additional full upload
    # after the one the cold node observed
    assert uploads_after_cold is not None
    assert sched.state_uploads == uploads_after_cold, (
        "the cold node's slot claim forced a full upload on the mesh"
    )
    assert sched.membership_row_patches >= 1

    # the differential: device carry == fresh full pack, per name
    dev_req = np.asarray(ds.req_dev)
    dev_nzr = np.asarray(ds.nzr_dev)
    names = sched.tensor_cache._names
    snap2 = Snapshot()
    sched.cache.update_snapshot(snap2)
    fresh = NodeTensorCache(
        sched.tensor_cache.dims, sched.tensor_cache.topology
    ).update(snap2)
    assert sorted(n for n in names if n) == sorted(fresh.names)
    for name in names:
        if not name:
            continue
        i = names.index(name)
        j = fresh.row(name)
        assert np.array_equal(dev_req[i], fresh.requested[j]), (
            f"sharded req_state row for {name} diverged from the full "
            f"pack: {dev_req[i]} != {fresh.requested[j]}"
        )
        assert np.array_equal(
            dev_nzr[i], fresh.non_zero_requested[j]
        ), f"sharded nzr_state row for {name} diverged"

    # the stream drove the interesting paths -- on the PALLAS mesh
    # tier (the differential's scatters, membership patches, and
    # divergence repairs must all compose with the shard_map'd solver)
    assert calls["n"] >= 3
    assert sched.pods_fallback == 0
    assert sched.mesh_solver_tier == "pallas", (
        f"differential ran on tier {sched.mesh_solver_tier!r}"
    )
    sched.stop()
    informers.stop()


def test_mask_rows_shard_threshold_split_parity(monkeypatch):
    """The [U, N] mask rows ship as their own column-sharded bool
    operand only ABOVE ``MESH_MASK_SHARD_MIN_BYTES`` (below it, a
    second device_put operand's link round trip costs more than the
    bytes save and the rows stay in the replicated buffer). Both forms
    must solve identically on both mesh tiers -- the cutoff is a pure
    link-cost decision, never a semantic one."""
    import kubernetes_tpu.ops.assignment as assignment
    from kubernetes_tpu.ops.assignment import solve_packed
    from kubernetes_tpu.ops.host_masks import mask_rows_upload

    mesh = _mesh(2)
    n, r, b, u = 256, 4, 64, 8
    rng = np.random.default_rng(3)
    alloc = np.zeros((n, r), dtype=np.int32)
    alloc[:, 0] = rng.choice([4000, 8000], n)
    alloc[:, 1] = rng.choice([8, 16], n) * 1024 * 1024
    alloc[:, 3] = 110
    pod_req = np.zeros((b, r), dtype=np.int32)
    pod_req[:, 0] = rng.choice([100, 250, 500], b)
    pod_req[:, 1] = rng.choice([128, 256], b) * 1024
    pod_req[:, 3] = 1
    rows = rng.random((u, n)) > 0.2
    pieces = lambda: [  # noqa: E731 - rebuilt per call (device_put consumes)
        ("req", pod_req),
        ("nzr", pod_req[:, :2].copy()),
        ("midx", rng.integers(0, u, b).astype(np.int32)),
        ("active", np.ones(b, dtype=np.int32)),
        ("rows", mask_rows_upload(rows, mesh)),
        ("alloc", alloc),
        ("valid", np.ones(n, dtype=np.int32)),
        ("req_state", np.zeros((n, r), dtype=np.int32)),
        ("nzr_state", np.zeros((n, 2), dtype=np.int32)),
    ]
    rng = np.random.default_rng(3)  # same midx stream per variant
    results = {}
    for cutoff, tier in ((0, True), (0, False), (1 << 30, True)):
        rng = np.random.default_rng(3)
        monkeypatch.setattr(
            assignment, "MESH_MASK_SHARD_MIN_BYTES", cutoff
        )
        out = solve_packed(
            pieces(), None, None, None, None,
            allow_pallas=tier, mesh=mesh,
        )
        results[(cutoff, tier)] = np.asarray(out[0])
    # cutoff 0 => rows forced onto the sharded operand; 1<<30 => rows
    # forced into the buffer: identical placements either way, on
    # either tier
    assert np.array_equal(results[(0, True)], results[(0, False)])
    assert np.array_equal(results[(0, True)], results[(1 << 30, True)])


def test_mesh_pallas_fault_falls_back_to_xla_twin():
    """Breaker e2e for the mesh ladder [pallas-shard_map, xla]: an
    injected device fault on the Pallas attempt routes the SAME
    dispatch to the GSPMD XLA twin (batch completes, nothing falls to
    the sequential path), a forced-open pallas breaker keeps routing
    every later batch to the twin, and the carry ledger stays intact
    through both -- still one cold upload, zero divergences, oracle
    placement parity."""
    from kubernetes_tpu.robustness.faults import (
        FaultInjector,
        FaultPoint,
        FaultProfile,
        PointConfig,
        install_injector,
    )
    from kubernetes_tpu.robustness.ladder import TIER_PALLAS, TIER_XLA

    from kubernetes_tpu.utils import metrics

    mesh = _mesh(2)
    rng = random.Random(7)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=True, max_batch=64, mesh=mesh,
        rng=_KeepFirstRng(),
    )
    oracle_server = APIServer()
    oracle_client = Client(oracle_server)
    oracle_informers = InformerFactory(oracle_server)
    oracle = new_scheduler(
        oracle_client, oracle_informers, batch=False, rng=_KeepFirstRng(),
    )
    for i in range(8):
        client.create_node(
            make_node(f"bf-n{i}")
            .capacity(cpu="64", memory="128Gi", pods=200).obj()
        )
        oracle_client.create_node(
            make_node(f"bf-n{i}")
            .capacity(cpu="64", memory="128Gi", pods=200).obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    oracle_informers.start()
    oracle_informers.wait_for_cache_sync()
    oracle.queue.run()
    sched.start()
    oracle.start()
    total = 0

    def burst(tag, n):
        nonlocal total
        for i in range(n):
            spec = (
                make_pod(f"bf-{tag}-{i}")
                .creation_timestamp(float(total + i))
                .container(
                    cpu=f"{rng.choice([100, 250, 500])}m",
                    memory="128Mi",
                )
            )
            client.create_pod(spec.obj())
            oracle_client.create_pod(spec.obj())
        total += n
        _wait_all_bound(client, total)
        _wait_all_bound(oracle_client, total)
        sched.wait_for_inflight_binds(timeout=60)

    charged_key = dict(tier=TIER_XLA, reason=f"{TIER_PALLAS}_error")
    charged_before = metrics.solver_fallbacks.value(**charged_key)
    try:
        # phase 1: one injected fault BURST sized to exhaust the pallas
        # tier's in-place retries (ladder retry policy) -- the first
        # batch's pallas attempt must step down to the XLA twin inside
        # the SAME dispatch, with the failure charged to the pallas
        # breaker; the injector then heals, so later batches solve on
        # pallas again
        max_fires = sched.ladder.config.retry.max_attempts
        install_injector(FaultInjector(FaultProfile(
            name="mesh-pallas-fault", seed=0,
            points={FaultPoint.DEVICE_SOLVE: PointConfig(
                rate=1.0, max_fires=max_fires
            )},
        )))
        burst("p1", 100)
        by_tier = dict(sched.ladder.solves_by_tier)
        # this mesh is the CPU's: the shard_map tier runs without its
        # kernel, so the ledger counts its batches ``xla`` beside the
        # twin's, and ``mesh_shard_solves`` says which program ran
        assert by_tier.get(TIER_PALLAS, 0) == 0, by_tier
        assert by_tier.get(TIER_XLA, 0) > sched.mesh_shard_solves, (
            f"the faulted batch did not land on the XLA twin: {by_tier}, "
            f"shard_map solves {sched.mesh_shard_solves}"
        )
        assert sched.mesh_shard_solves >= 1, (
            f"the healed injector never let the shard_map tier solve "
            f"again: {by_tier}"
        )
        assert sched.pods_fallback == 0, (
            "a pallas fault fell through to the sequential path "
            "instead of the XLA twin"
        )
        assert metrics.solver_fallbacks.value(**charged_key) > (
            charged_before
        ), "the fault was not charged to the pallas tier"

        # phase 2: pallas breaker OPEN -- batches route straight to the
        # twin while it cools off, nothing sequential
        install_injector(None)
        sched.ladder.breakers[TIER_PALLAS].force_open()
        assert not sched.ladder.breakers[TIER_PALLAS].allow()
        xla_before = sched.ladder.solves_by_tier.get(TIER_XLA, 0)
        burst("p2", 100)
        assert sched.ladder.solves_by_tier.get(TIER_XLA, 0) > xla_before
        assert sched.pods_fallback == 0

        # the carry ledger survived the faults: one cold upload total,
        # zero divergences, and placement parity with the sequential
        # oracle held across the tier hops
        assert sched.state_uploads <= 1
        assert sched.carry_divergences == 0
        got = {
            p.metadata.name: p.spec.node_name
            for p in client.list_pods()[0]
        }
        want = {
            p.metadata.name: p.spec.node_name
            for p in oracle_client.list_pods()[0]
        }
        assert all(want.values()), "oracle failed to place a fitting pod"
        assert got == want
    finally:
        install_injector(None)
        sched.stop()
        informers.stop()
        oracle.stop()
        oracle_informers.stop()
