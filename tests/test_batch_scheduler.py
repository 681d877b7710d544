"""BatchScheduler end-to-end tests: device-solved placement through the
full apiserver/informer/bind pipeline, plus fallback routing."""

import time

import pytest

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.scheduler.batch import solver_supported
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod


def _wait_all_bound(client, count, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        pods, _ = client.list_pods()
        bound = [p for p in pods if p.spec.node_name]
        if len(bound) >= count:
            return pods
        time.sleep(0.05)
    raise AssertionError(
        f"only {len([p for p in client.list_pods()[0] if p.spec.node_name])}"
        f"/{count} pods bound"
    )


@pytest.fixture
def cluster():
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=64)
    yield server, client, informers, sched
    sched.stop()
    informers.stop()


class TestBatchScheduling:
    def test_burst_scheduled_on_device(self, cluster):
        server, client, informers, sched = cluster
        for i in range(8):
            client.create_node(
                make_node(f"n{i}").capacity(cpu="8", memory="16Gi", pods=30).obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        for i in range(40):
            client.create_pod(
                make_pod(f"p{i}").container(cpu="250m", memory="256Mi").obj()
            )
        t = sched.start()
        pods = _wait_all_bound(client, 40)
        sched.wait_for_inflight_binds()
        assert sched.pods_solved_on_device >= 40
        assert sched.pods_fallback == 0
        # capacity respected on every node
        per_node = {}
        for p in pods:
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + 1
        assert all(v <= 30 for v in per_node.values())

    def test_infeasible_pod_recorded_unschedulable(self, cluster):
        server, client, informers, sched = cluster
        client.create_node(make_node("n").capacity(cpu="1", memory="1Gi").obj())
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        client.create_pod(make_pod("big").container(cpu="64", memory="1Ti").obj())
        client.create_pod(make_pod("ok").container(cpu="500m").obj())
        sched.start()
        _wait_all_bound(client, 1)
        sched.wait_for_inflight_binds()
        deadline = time.time() + 5
        big = None
        while time.time() < deadline:
            big = client.get_pod("default", "big")
            if any(c.type == "PodScheduled" and c.status == "False"
                   for c in big.status.conditions):
                break
            time.sleep(0.05)
        assert big is not None
        assert not big.spec.node_name
        assert any(
            c.type == "PodScheduled" and c.status == "False" and
            c.reason == "Unschedulable"
            for c in big.status.conditions
        )

    def test_fallback_pods_routed_to_sequential_path(self, cluster):
        server, client, informers, sched = cluster
        for name, zone in [("a", "z1"), ("b", "z2")]:
            client.create_node(
                make_node(name).labels(zone=zone)
                .capacity(cpu="8", memory="16Gi", pods=20).obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        # volume-bound pods can't solve on device -> sequential fallback
        # (host-port pods now solve on device via the NodePorts static
        # mask; volumes remain the host-side family)
        for i in range(4):
            client.create_pod(
                make_pod(f"s{i}").labels(app="s")
                .container(cpu="100m")
                .gce_pd(f"disk-{i}")
                .obj()
            )
        for i in range(4):
            client.create_pod(make_pod(f"r{i}").container(cpu="100m").obj())
        sched.start()
        pods = _wait_all_bound(client, 8)
        sched.wait_for_inflight_binds()
        assert sched.pods_fallback >= 4
        assert sched.pods_solved_on_device >= 4

    def test_node_selector_respected_via_static_mask(self, cluster):
        server, client, informers, sched = cluster
        client.create_node(
            make_node("gpu").labels(pool="gpu")
            .capacity(cpu="8", memory="16Gi").obj()
        )
        client.create_node(
            make_node("cpu").labels(pool="cpu")
            .capacity(cpu="64", memory="128Gi").obj()
        )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        for i in range(3):
            client.create_pod(
                make_pod(f"g{i}").container(cpu="1")
                .node_selector(pool="gpu").obj()
            )
        sched.start()
        pods = _wait_all_bound(client, 3)
        for p in pods:
            assert p.spec.node_name == "gpu"

    def test_tainted_node_avoided(self, cluster):
        server, client, informers, sched = cluster
        client.create_node(
            make_node("t").taint("dedicated", "infra")
            .capacity(cpu="64", memory="64Gi").obj()
        )
        client.create_node(make_node("ok").capacity(cpu="2", memory="4Gi").obj())
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        for i in range(3):
            client.create_pod(make_pod(f"p{i}").container(cpu="100m").obj())
        client.create_pod(
            make_pod("tolerant").container(cpu="100m")
            .toleration("dedicated", value="infra").obj()
        )
        sched.start()
        pods = _wait_all_bound(client, 4)
        for p in pods:
            if p.name == "tolerant":
                continue
            assert p.spec.node_name == "ok"


class TestRegressions:
    def test_unknown_extended_resource_is_unschedulable_not_crash(self, cluster):
        server, client, informers, sched = cluster
        client.create_node(make_node("n").capacity(cpu="8", memory="16Gi").obj())
        informers.start()
        informers.wait_for_cache_sync()
        client.create_pod(
            make_pod("gpu").container(cpu="1", **{"example_com__gpu": 2}).obj()
        )
        client.create_pod(make_pod("ok").container(cpu="1").obj())
        sched.start()
        _wait_all_bound(client, 1)
        sched.wait_for_inflight_binds()
        gpu = client.get_pod("default", "gpu")
        assert not gpu.spec.node_name
        ok = client.get_pod("default", "ok")
        assert ok.spec.node_name == "n"

    def test_tolerate_everything_admits_cordoned_node(self, cluster):
        server, client, informers, sched = cluster
        node = make_node("c").capacity(cpu="8", memory="16Gi").unschedulable().obj()
        client.create_node(node)
        informers.start()
        informers.wait_for_cache_sync()
        p = make_pod("t").container(cpu="1").obj()
        from kubernetes_tpu.api.types import Toleration
        p.spec.tolerations.append(Toleration(key="", operator="Exists"))
        client.create_pod(p)
        sched.start()
        pods = _wait_all_bound(client, 1)
        assert pods[0].spec.node_name == "c"

    def test_fallback_does_not_jump_high_priority_solver_pod(self, cluster):
        server, client, informers, sched = cluster
        client.create_node(make_node("n").capacity(cpu="1", memory="4Gi").obj())
        informers.start()
        informers.wait_for_cache_sync()
        # high-priority plain pod and low-priority spread pod compete for
        # the single cpu; queue order must win
        high = make_pod("high").container(cpu="1").obj()
        high.spec.priority = 100
        low = (
            make_pod("low").labels(app="low").container(cpu="1")
            .spread_constraint(1, "zone", match_labels={"app": "low"})
            .obj()
        )
        client.create_pod(high)
        client.create_pod(low)
        sched.start()
        _wait_all_bound(client, 1)
        sched.wait_for_inflight_binds()
        assert client.get_pod("default", "high").spec.node_name == "n"
        assert not client.get_pod("default", "low").spec.node_name


class TestExistingAntiAffinityGate:
    def test_existing_required_anti_affinity_respected(self, cluster):
        """A pod with no affinity of its own must still honor required
        anti-affinity declared by pods already on nodes (symmetric check);
        the batch path falls back to the sequential oracle for this."""
        server, client, informers, sched = cluster
        for name in ("a", "b"):
            client.create_node(
                make_node(name).labels(host=name)
                .capacity(cpu="8", memory="16Gi").obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        # guard on node a: anti-affinity against app=web on its host
        guard = (
            make_pod("guard").labels(app="guard")
            .container(cpu="100m")
            .pod_affinity("host", {"app": "web"}, anti=True)
            .obj()
        )
        client.create_pod(guard)
        sched.start()
        _wait_all_bound(client, 1)
        sched.wait_for_inflight_binds()
        guard_node = client.get_pod("default", "guard").spec.node_name
        for i in range(4):
            client.create_pod(
                make_pod(f"web-{i}").labels(app="web").container(cpu="100m").obj()
            )
        pods = _wait_all_bound(client, 5)
        for p in pods:
            if p.name.startswith("web"):
                assert p.spec.node_name != guard_node, p.name


class TestNominatedOverlay:
    def test_batch_does_not_steal_nominated_capacity(self, cluster):
        """Capacity freed by preemption stays reserved for the nominee."""
        server, client, informers, sched = cluster
        client.create_node(make_node("n").capacity(cpu="2", memory="8Gi").obj())
        informers.start()
        informers.wait_for_cache_sync()
        for i in range(2):
            client.create_pod(make_pod(f"low{i}").container(cpu="1").obj())
        sched.start()
        _wait_all_bound(client, 2)
        sched.wait_for_inflight_binds()
        # high-priority pod preempts a victim and gets nominated
        high = make_pod("high").container(cpu="2").obj()
        high.spec.priority = 100
        client.create_pod(high)
        deadline = time.time() + 15
        while time.time() < deadline:
            hp = client.get_pod("default", "high")
            if hp.spec.node_name:
                break
            # meanwhile, opportunistic low-priority pods keep arriving
            time.sleep(0.2)
            client.create_pod(
                make_pod(f"opportunist-{time.monotonic_ns()}")
                .container(cpu="1").obj()
            )
        sched.stop()
        hp = client.get_pod("default", "high")
        assert hp.spec.node_name == "n", "nominee starved by batch pods"


class TestSolverSupported:
    def test_plain_pod(self):
        assert solver_supported(make_pod("p").container(cpu="1").obj())

    def test_required_affinity_supported_on_device(self):
        assert solver_supported(
            make_pod("p").pod_affinity("zone", {"a": "b"}).obj()
        )
        assert solver_supported(
            make_pod("p").pod_affinity("zone", {"a": "b"}, anti=True).obj()
        )

    def test_preferred_affinity_supported_on_device(self):
        # preferred terms ride the ipa_* score family (ops/scoring.py)
        assert solver_supported(
            make_pod("p").preferred_pod_affinity("zone", {"a": "b"}).obj()
        )

    def test_hard_spread_supported_on_device(self):
        assert solver_supported(
            make_pod("p").spread_constraint(1, "zone").obj()
        )

    def test_soft_spread_supported_on_device(self):
        assert solver_supported(
            make_pod("p").spread_constraint(
                1, "zone", when_unsatisfiable="ScheduleAnyway"
            ).obj()
        )

    def test_hard_spread_plus_node_selector_supported(self):
        # per-group eligibility scoping (topology._eligibility_sig)
        # keeps this on device now
        assert solver_supported(
            make_pod("p").spread_constraint(1, "zone")
            .node_selector(pool="x").obj()
        )

    def test_soft_spread_plus_node_selector_not_supported(self):
        assert not solver_supported(
            make_pod("p").spread_constraint(
                1, "zone", when_unsatisfiable="ScheduleAnyway"
            )
            .node_selector(pool="x").obj()
        )

    def test_node_selector_supported(self):
        assert solver_supported(make_pod("p").node_selector(pool="x").obj())


class TestNomineeConstrainedFallback:
    def test_constrained_batch_with_nominee_takes_host_path(self):
        """ADVICE r2 (medium): nominee pods are overlaid as resources
        only, so a constrained batch (affinity) with active nominations
        must route to the host path where _add_nominated_pods runs the
        full filter semantics."""
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.informer import InformerFactory

        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=16)
        for i in range(3):
            client.create_node(
                make_node(f"n{i}").labels(zone=f"z{i}")
                .capacity(cpu="8", memory="16Gi").obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        # a standing nomination makes nominated_by_node non-empty
        nominee = make_pod("nominee").container(cpu="1").priority(50).obj()
        sched.queue.update_nominated_pod_for_node(nominee, "n0")
        client.create_pod(
            make_pod("anti").labels(app="a")
            .container(cpu="100m", memory="128Mi")
            .pod_affinity("zone", {"app": "a"}, anti=True)
            .obj()
        )
        deadline = time.time() + 15
        while time.time() < deadline:
            sched.schedule_batch(timeout=0.2)
            pods, _ = client.list_pods()
            if any(p.spec.node_name for p in pods):
                break
        sched.wait_for_inflight_binds()
        sched.stop()
        informers.stop()
        pods, _ = client.list_pods()
        assert any(p.spec.node_name for p in pods)
        assert sched.nominee_constrained_fallbacks >= 1
        assert sched.pods_fallback >= 1


class TestDeviceStateDifferential:
    """Randomized event-stream differential for the device-resident
    node state (PR 5): after K batches with interleaved node churn,
    bind failures, and schema growth, the device-resident ``req_state``
    carry must equal a fresh full pack of the host snapshot -- and the
    CPU (XLA) tier must have exercised the delta-scatter path."""

    def test_event_stream_device_state_matches_full_pack(self, monkeypatch):
        import random

        import numpy as np

        from kubernetes_tpu.cache.snapshot import Snapshot
        from kubernetes_tpu.tensors import NodeTensorCache

        rng = random.Random(20260803)
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=32)
        for i in range(8):
            client.create_node(
                make_node(f"ds-n{i}")
                .capacity(cpu="64", memory="128Gi", pods=200)
                .obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()

        # bind failures: every 4th bulk transaction rejects its first
        # slot (the pod is forgotten + requeued, so the host diverges
        # from the mirrored expectation -- the scatter-fix case)
        orig_bulk = client.bind_assumed_bulk
        calls = {"n": 0}

        def flaky_bulk(assumed):
            calls["n"] += 1
            if calls["n"] % 4 == 0 and assumed:
                errs = orig_bulk(assumed[1:])
                return [(0, RuntimeError("synthetic bind failure"))] + [
                    (i + 1, e) for i, e in errs
                ]
            return orig_bulk(assumed)

        monkeypatch.setattr(client, "bind_assumed_bulk", flaky_bulk)

        seq = 0
        for k in range(12):
            for _ in range(rng.randint(3, 8)):
                seq += 1
                client.create_pod(
                    make_pod(f"ds-p{seq}")
                    .container(
                        cpu=f"{rng.choice([100, 250, 500])}m",
                        memory="128Mi",
                    )
                    .obj()
                )
            if k % 3 == 2:
                # external churn: a controller deletes a bound pod
                # behind the scheduler's back
                bound = [
                    p for p in client.list_pods()[0] if p.spec.node_name
                ]
                if bound:
                    victim = rng.choice(bound)
                    client.delete_pod(
                        victim.metadata.namespace, victim.metadata.name
                    )
            if k == 5:
                # schema growth: a node advertising a new scalar
                # resource forces a full repack + re-upload
                client.create_node(
                    make_node("ds-gpu")
                    .capacity(
                        cpu="8", memory="16Gi",
                        **{"example_com__gpu": 4},
                    )
                    .obj()
                )
            deadline = time.time() + 5
            while time.time() < deadline:
                if sched.schedule_batch(timeout=0.2):
                    break
        # settle: stop injecting bind failures (a failure during the
        # deterministic tail below would leave the device ahead with no
        # reconciling dispatch left), absorb requeues/deletions, then
        # stop mutating
        monkeypatch.setattr(client, "bind_assumed_bulk", orig_bulk)
        for _ in range(10):
            sched.schedule_batch(timeout=0.1)
        sched.wait_for_inflight_binds(timeout=30)
        for _ in range(5):
            sched.schedule_batch(timeout=0.1)
        sched.wait_for_inflight_binds(timeout=30)

        # one quiet batch reconciles the carry with the settled host
        # state (any leftover external change resolves here) and drains
        # the pending-delta ring
        client.create_pod(
            make_pod("ds-final").container(cpu="100m", memory="64Mi").obj()
        )
        deadline = time.time() + 10
        while time.time() < deadline:
            if sched.schedule_batch(timeout=0.2):
                break
        sched.wait_for_inflight_binds(timeout=30)

        # -- deterministic path coverage (the in-loop churn above races
        # the committer, so which resolution each divergence took is
        # timing-dependent; these two phases are not) ------------------

        # phase A: allocatable growth with nothing in flight. The next
        # dispatch must validate the carry (row CONTENTS unchanged) and
        # ship the one changed alloc row as an (indices, rows) scatter
        # -- NOT a full upload.
        node = client.get_node("ds-n0")
        node.status.capacity["cpu"] += 1000
        node.status.allocatable["cpu"] += 1000
        client.update_node(node)
        deadline = time.time() + 10
        while time.time() < deadline:
            ni = sched.cache._nodes.get("ds-n0")
            if ni is not None and ni.allocatable.milli_cpu == 65000:
                break
            time.sleep(0.02)
        uploads_before = sched.state_uploads
        delta_before = sched.delta_rows_uploaded
        client.create_pod(
            make_pod("ds-final2").container(cpu="100m", memory="64Mi").obj()
        )
        deadline = time.time() + 10
        while time.time() < deadline:
            if sched.schedule_batch(timeout=0.2):
                break
        sched.wait_for_inflight_binds(timeout=30)
        assert sched.delta_rows_uploaded > delta_before, (
            "alloc growth should ride the row scatter"
        )
        assert sched.state_uploads == uploads_before, (
            "alloc growth must not trigger a full [N, R] upload"
        )

        # phase B: external pod delete with nothing in flight -- a
        # changed row our own mirrored placements cannot explain. The
        # next dispatch must COUNT the divergence (scatter-fixed or
        # resolved by a full upload, but never silent).
        bound = [p for p in client.list_pods()[0] if p.spec.node_name]
        victim = bound[0]
        vnode = victim.spec.node_name
        client.delete_pod(victim.metadata.namespace, victim.metadata.name)
        deadline = time.time() + 10
        while time.time() < deadline:
            ni = sched.cache._nodes.get(vnode)
            if ni is not None and all(
                p.metadata.uid != victim.metadata.uid for p in ni.pods
            ):
                break
            time.sleep(0.02)
        div_before = sched.carry_divergences
        client.create_pod(
            make_pod("ds-final3").container(cpu="100m", memory="64Mi").obj()
        )
        deadline = time.time() + 10
        while time.time() < deadline:
            if sched.schedule_batch(timeout=0.2):
                break
        sched.wait_for_inflight_binds(timeout=30)
        assert sched.carry_divergences > div_before, (
            "the external delete must surface as a counted divergence"
        )

        ds = sched.device_state
        assert ds.req_dev is not None, "device carry was dropped"
        dev_req = np.asarray(ds.req_dev)
        dev_nzr = np.asarray(ds.nzr_dev)
        names = sched.tensor_cache._names

        # fresh full pack of the settled host state (shared dims +
        # topology registries => identical columns), via a fresh
        # snapshot so the scheduler's change tracking is untouched
        snap2 = Snapshot()
        sched.cache.update_snapshot(snap2)
        fresh = NodeTensorCache(
            sched.tensor_cache.dims, sched.tensor_cache.topology
        ).update(snap2)
        assert sorted(fresh.names) == sorted(names)
        for name in names:
            i = names.index(name)
            j = fresh.row(name)
            assert np.array_equal(dev_req[i], fresh.requested[j]), (
                f"device req_state row for {name} diverged from the "
                f"full pack: {dev_req[i]} != {fresh.requested[j]}"
            )
            assert np.array_equal(
                dev_nzr[i], fresh.non_zero_requested[j]
            ), f"device nzr_state row for {name} diverged"

        # the event stream actually drove the interesting paths
        assert sched.delta_rows_uploaded > 0
        assert sched.carry_divergences > 0
        assert calls["n"] >= 4
        sched.stop()
        informers.stop()


class TestEagerDownload:
    """The dispatch-time result download (PR 4): on this box the core
    gate may disable it, so these tests force the path on."""

    def test_eager_download_result_roundtrip(self):
        import jax.numpy as jnp
        import numpy as np

        from kubernetes_tpu.scheduler.batch import _EagerDownload

        dev = jnp.arange(16, dtype=jnp.int32)
        dl = _EagerDownload(dev)
        out = dl.result()
        assert isinstance(out, np.ndarray)
        assert out.tolist() == list(range(16))
        # result() is idempotent
        assert dl.result() is out

    def test_eager_download_propagates_errors(self):
        from kubernetes_tpu.scheduler.batch import _EagerDownload

        class Boom:
            def __array__(self, *a, **k):
                raise RuntimeError("device link down")

        dl = _EagerDownload(Boom())
        with pytest.raises(RuntimeError, match="device link down"):
            dl.result()

    def test_pipeline_binds_with_eager_downloads_forced(self, cluster, monkeypatch):
        """Full dispatch->commit flow with the eager path forced on
        (regardless of the host-core gate)."""
        from kubernetes_tpu.scheduler import batch as batch_mod

        monkeypatch.setattr(batch_mod, "_EAGER_DOWNLOAD_OK", True)
        server, client, informers, sched = cluster
        for i in range(6):
            client.create_node(
                make_node(f"ed-n{i}")
                .capacity(cpu="8", memory="16Gi", pods=32)
                .obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        for i in range(40):
            client.create_pod(
                make_pod(f"ed-p{i}")
                .container(cpu="100m", memory="128Mi")
                .obj()
            )
        sched.queue.run()
        deadline = time.time() + 30
        done = 0
        while done < 40 and time.time() < deadline:
            done += sched.schedule_batch(timeout=0.5, pipeline=True)
        sched._drain_pending()
        sched.wait_for_inflight_binds(timeout=30)
        _wait_all_bound(client, 40)
        # the device path actually ran with eager downloads in flight
        assert sched.pods_solved_on_device == 40
        assert sched.pods_fallback == 0
