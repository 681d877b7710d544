"""Multi-tenant fairness plane (ISSUE 15): ResourceQuota admission at
the scheduling gate, typed-QuotaExceeded parking with event-driven
wakes, refund-on-failure ledger integrity (randomized differential
against a full watch-history replay, under the ha-chaos profile), the
DRF dominant-share solve-order bias, the plain-pod native-ingest guard
with tenancy armed, and the two satellites (PodQuarantined honored at
relist; legacy-mesh untyped crash-loop containment)."""

import random
import time

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    ObjectMeta,
    PodCondition,
    ResourceQuota,
    pod_resource_requests,
)
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.controllers.quota import QuotaController
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.scheduler.tenancy import (
    TenantShareTracker,
    arm_tenancy,
    fair_order,
)
from kubernetes_tpu.testing import make_node, make_pod


def _mk_quota(ns, **hard):
    return ResourceQuota(
        metadata=ObjectMeta(name="quota", namespace=ns), hard=dict(hard)
    )


def _pod_in(ns, name, cpu="100m", memory="128Mi", priority=0):
    p = make_pod(name).container(cpu=cpu, memory=memory).obj()
    p.metadata.namespace = ns
    p.spec.priority = priority
    return p


def _wait(pred, timeout=20.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


@pytest.fixture
def cluster():
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=64)
    qc = arm_tenancy(sched, client, informers)
    yield server, client, informers, sched, qc
    qc.stop()
    sched.stop()
    informers.stop()


class TestQuotaLedger:
    def test_charge_deny_refund_roundtrip(self):
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        qc = QuotaController(client, informers)
        client.create_resource_quota(_mk_quota("t1", pods=2, cpu=1000))
        p1, p2, p3 = (_pod_in("t1", n, cpu="400m") for n in "abc")
        for p in (p1, p2, p3):
            client.create_pod(p)
        informers.pump()
        assert qc.try_admit(p1) == ""
        assert qc.try_admit(p2) == ""
        assert "exceeded quota" in qc.try_admit(p3)
        used = client.get("ResourceQuota", "t1", "quota").status.used
        assert used == {"pods": 2, "cpu": 800}
        # idempotent: a charged pod re-admits without double-charging
        assert qc.try_admit(p1) == ""
        assert client.get(
            "ResourceQuota", "t1", "quota"
        ).status.used == {"pods": 2, "cpu": 800}
        # exactly-once refund
        assert qc.refund(p1, reason="requeue") is True
        assert qc.refund(p1, reason="requeue") is False
        used = client.get("ResourceQuota", "t1", "quota").status.used
        assert used == {"pods": 1, "cpu": 400}
        assert qc.try_admit(p3) == ""

    def test_multi_quota_partial_charge_refunded_on_deny(self):
        """Quota A grants, quota B denies: A's units come back (the
        can_disrupt give-back discipline)."""
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        qc = QuotaController(client, informers)
        client.create_resource_quota(_mk_quota("t1", pods=10))
        qb = _mk_quota("t1", cpu=100)
        qb.metadata.name = "cpu-cap"
        client.create_resource_quota(qb)
        p = _pod_in("t1", "big", cpu="400m")
        client.create_pod(p)
        informers.pump()
        assert "exceeded quota" in qc.try_admit(p)
        # neither quota retains spend from the denied attempt
        assert client.get(
            "ResourceQuota", "t1", "quota"
        ).status.used.get("pods", 0) == 0
        assert client.get(
            "ResourceQuota", "t1", "cpu-cap"
        ).status.used.get("cpu", 0) == 0

    def test_no_quota_namespace_is_free(self):
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        qc = QuotaController(client, informers)
        informers.pump()
        assert qc.try_admit(_pod_in("anywhere", "p")) == ""
        assert qc.admissions_granted == 0  # the fast path books nothing

    def test_deleted_pod_never_leaks_a_charge(self):
        """The charge-store vs delete race: a pod deleted between the
        gate pop and the charge registration must not strand spend --
        the post-store liveness re-read refunds it."""
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        qc = QuotaController(client, informers)
        client.create_resource_quota(_mk_quota("t1", pods=5))
        p = _pod_in("t1", "ghost")
        client.create_pod(p)
        informers.pump()
        client.delete_pod("t1", "ghost")
        informers.pump()  # the delete handler ran, found no charge
        assert qc.try_admit(p) == ""  # gate still held the popped obj
        assert client.get(
            "ResourceQuota", "t1", "quota"
        ).status.used.get("pods", 0) == 0
        assert qc.charged_uids() == set()

    def test_quota_created_mid_run_adopts_existing_usage(self):
        """A ResourceQuota created over a namespace with bound pods
        must start from the real usage, not zero -- otherwise the cap
        silently overspends until a restart."""
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        qc = QuotaController(client, informers)
        for i in range(3):
            p = _pod_in("t1", f"b{i}")
            p.spec.node_name = "n0"
            client.create_pod(p)
        informers.pump()
        qc.sync_all()  # adopts the bound pods into the ledger
        client.create_resource_quota(_mk_quota("t1", pods=4))
        informers.pump()
        qc.drain_resync()
        used = client.get("ResourceQuota", "t1", "quota").status.used
        assert used == {"pods": 3}, used
        # only ONE more pod fits under the adopted usage
        p4 = _pod_in("t1", "p4")
        p5 = _pod_in("t1", "p5")
        client.create_pod(p4)
        client.create_pod(p5)
        informers.pump()
        assert qc.try_admit(p4) == ""
        assert "exceeded quota" in qc.try_admit(p5)


class TestQuotaParking:
    def _settle(self, client, informers, sched, n_nodes=4):
        for i in range(n_nodes):
            client.create_node(
                make_node(f"n{i}").capacity(cpu="16", memory="32Gi").obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()

    def test_park_and_wake_on_quota_raise(self, cluster):
        server, client, informers, sched, qc = cluster
        client.create_resource_quota(_mk_quota("t1", pods=1))
        self._settle(client, informers, sched)
        qc.sync_all()
        qc.start()
        client.create_pod(_pod_in("t1", "p1"))
        client.create_pod(_pod_in("t1", "p2"))
        sched.start()
        assert _wait(
            lambda: sched.queue.quota_parked_count() == 1
            and sum(
                1 for p in client.list_pods()[0] if p.spec.node_name
            ) == 1
        )
        # the typed condition is on the apiserver
        parked = [
            p for p in client.list_pods()[0] if not p.spec.node_name
        ][0]
        assert _wait(lambda: any(
            c.reason == "QuotaExceeded"
            for p in client.list_pods()[0] if not p.spec.node_name
            for c in p.status.conditions
        ))
        # a cluster event must NOT wake the parked pod
        client.create_node(
            make_node("late").capacity(cpu="16", memory="32Gi").obj()
        )
        time.sleep(0.5)
        assert sched.queue.quota_parked_count() == 1
        # raising the hard cap is the wake event
        client.update_resource_quota_status(
            "t1", "quota", lambda o: setattr(o, "hard", {"pods": 2})
        )
        assert _wait(lambda: all(
            p.spec.node_name for p in client.list_pods()[0]
        ))
        assert sched.queue.quota_parked_count() == 0
        assert parked.metadata.name in {
            p.metadata.name
            for p in client.list_pods()[0] if p.spec.node_name
        }

    def test_wake_on_usage_drop(self, cluster):
        server, client, informers, sched, qc = cluster
        client.create_resource_quota(_mk_quota("t1", pods=1))
        self._settle(client, informers, sched)
        qc.sync_all()
        qc.start()
        client.create_pod(_pod_in("t1", "p1"))
        sched.start()
        assert _wait(lambda: bool(
            client.get_pod("t1", "p1").spec.node_name
        ))
        client.create_pod(_pod_in("t1", "p2"))
        assert _wait(lambda: sched.queue.quota_parked_count() == 1)
        # deleting the bound pod refunds its charge -> wake
        client.delete_pod("t1", "p1")
        assert _wait(lambda: bool(
            client.get_pod("t1", "p2").spec.node_name
        ))
        used = client.get("ResourceQuota", "t1", "quota").status.used
        assert used == {"pods": 1}

    def test_unschedulable_pod_refunds_charge(self, cluster):
        """A charged pod that solves NO_NODE requeues UNCHARGED (used
        never counts parked-unschedulable pods), so a sibling in the
        same namespace can take the headroom."""
        server, client, informers, sched, qc = cluster
        client.create_resource_quota(_mk_quota("t1", pods=1))
        self._settle(client, informers, sched, n_nodes=1)
        qc.sync_all()
        qc.start()
        # does not fit anywhere, but passes quota (pods=1)
        client.create_pod(_pod_in("t1", "huge", cpu="64", memory="1Ti"))
        sched.start()
        assert _wait(lambda: qc.admissions_granted >= 1, timeout=25)
        assert _wait(lambda: qc.refunds >= 1, timeout=25)
        assert _wait(lambda: client.get(
            "ResourceQuota", "t1", "quota"
        ).status.used.get("pods", 0) == 0, timeout=25)


def _replay_bound_usage(server, quotas_by_ns):
    """Replay the FULL Pod watch history: per-namespace bound usage at
    every event, asserting it never exceeds any quota's hard caps.
    Returns the final per-namespace bound usage."""
    watch = server.watch("Pod", since_rv=0)
    bound: dict = {}  # uid -> (ns, usage)
    usage_by_ns: dict = {}

    def apply(ns, usage, sign):
        tot = usage_by_ns.setdefault(ns, {})
        for name, qty in usage.items():
            tot[name] = tot.get(name, 0) + sign * qty

    for ev in watch.pending():
        pod = ev.object
        uid = pod.metadata.uid
        ns = pod.metadata.namespace
        if ev.type in ("ADDED", "MODIFIED"):
            if pod.spec.node_name and uid not in bound:
                from kubernetes_tpu.controllers.quota import (
                    quota_pod_usage,
                )

                u = quota_pod_usage(pod)
                bound[uid] = (ns, u)
                apply(ns, u, +1)
        elif ev.type == "DELETED":
            entry = bound.pop(uid, None)
            if entry is not None:
                apply(entry[0], entry[1], -1)
        for q in quotas_by_ns.get(ns, []):
            tot = usage_by_ns.get(ns, {})
            for name, hard in q.hard.items():
                assert tot.get(name, 0) <= hard, (
                    f"overspend in {ns}: {name}={tot.get(name, 0)} > "
                    f"hard {hard} at rv {ev.resource_version}"
                )
    watch.stop()
    return usage_by_ns


class TestLedgerDifferential:
    def test_randomized_churn_ledger_matches_replay(self):
        """Seeded multi-namespace churn (bursts, deletes, quota raises)
        under the ha-chaos profile (api_unavailable, watch truncation,
        bind conflicts): at quiescence every quota's used equals the
        apiserver-truth recount of bound pods, and the full
        watch-history replay shows ZERO overspend at every point."""
        from kubernetes_tpu.robustness.faults import (
            FaultInjector, install_injector, load_profile,
        )

        rng = random.Random(1234)
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=64)
        qc = arm_tenancy(sched, client, informers)
        namespaces = [f"t{k}" for k in range(6)]
        quotas_by_ns = {}
        for ns in namespaces:
            q = _mk_quota(ns, pods=rng.randint(3, 8), cpu=4000)
            client.create_resource_quota(q)
            quotas_by_ns[ns] = [q]
        for i in range(6):
            client.create_node(
                make_node(f"n{i}").capacity(cpu="16", memory="32Gi").obj()
            )
        install_injector(
            FaultInjector(load_profile("ha-chaos", seed=77))
        )
        try:
            informers.start()
            informers.wait_for_cache_sync()
            sched.queue.run()
            qc.sync_all()
            qc.start()
            sched.start()
            created = []
            for round_i in range(5):
                for _ in range(rng.randint(5, 15)):
                    ns = rng.choice(namespaces)
                    name = f"p{len(created)}"
                    client.create_pod(
                        _pod_in(ns, name, cpu=f"{rng.randint(1, 4)}00m")
                    )
                    created.append((ns, name))
                time.sleep(0.3)
                # delete a random slice (bound or pending alike)
                for _ in range(rng.randint(0, 5)):
                    if not created:
                        break
                    ns, name = created.pop(
                        rng.randrange(len(created))
                    )
                    try:
                        client.delete_pod(ns, name)
                    except KeyError:
                        pass
                if round_i == 2:
                    # mid-run quota raise: parked pods must wake
                    for ns in namespaces[:2]:
                        client.update_resource_quota_status(
                            ns, "quota",
                            lambda o: setattr(o, "hard", {
                                **o.hard,
                                "pods": o.hard["pods"] + 3,
                            }),
                        )
                        quotas_by_ns[ns] = [
                            client.get("ResourceQuota", ns, "quota")
                        ]
            # quiesce: chaos points are bounded, so the system settles
            install_injector(None)
            time.sleep(2.0)
            sched.wait_for_inflight_binds(timeout=30)
            _wait(
                lambda: not sched._pending_exists()
                and sched.queue.active_count() == 0,
                timeout=20,
            )
            time.sleep(1.0)
            # (a) ledger == apiserver-truth recount, zero in-flight
            for ns in namespaces:
                q = client.get("ResourceQuota", ns, "quota")
                recount: dict = {}
                for p in client.list_pods()[0]:
                    if (
                        p.metadata.namespace == ns and p.spec.node_name
                        and p.metadata.deletion_timestamp is None
                    ):
                        from kubernetes_tpu.controllers.quota import (
                            quota_pod_usage,
                        )

                        for rname, qty in quota_pod_usage(p).items():
                            recount[rname] = recount.get(rname, 0) + qty
                for rname, hard in q.hard.items():
                    assert q.status.used.get(rname, 0) == recount.get(
                        rname, 0
                    ), (
                        f"{ns}.{rname}: ledger "
                        f"{q.status.used.get(rname, 0)} != recount "
                        f"{recount.get(rname, 0)}"
                    )
                    assert q.status.used.get(rname, 0) <= hard
            # (b) zero overspend over the whole history
            final = _replay_bound_usage(server, {
                ns: [client.get("ResourceQuota", ns, "quota")]
                for ns in namespaces
            })
            for ns in namespaces:
                q = client.get("ResourceQuota", ns, "quota")
                for rname in q.hard:
                    assert q.status.used.get(rname, 0) == final.get(
                        ns, {}
                    ).get(rname, 0)
        finally:
            install_injector(None)
            qc.stop()
            sched.stop()
            informers.stop()


class TestFairOrder:
    def _pods(self, spec):
        """spec: list of (ns, cpu_milli, priority)."""
        out = []
        for i, (ns, cpu, prio) in enumerate(spec):
            p = _pod_in(ns, f"f{i}", cpu=f"{cpu}m", priority=prio)
            pod_resource_requests(p)
            out.append(p)
        return out

    def test_under_served_tenant_places_first(self):
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 1 << 30)
        # "heavy" already holds 40% of cluster cpu
        heavy = _pod_in("heavy", "bound", cpu="4000m")
        tt.note_bound([heavy])
        pods = self._pods(
            [("heavy", 100, 0)] * 3 + [("light", 100, 0)] * 3
        )
        order = fair_order(
            np.arange(6, dtype=np.int32), pods,
            np.zeros(6, dtype=np.int32), tt,
        )
        ns_seq = [pods[int(i)].metadata.namespace for i in order]
        assert ns_seq[:3] == ["light"] * 3

    def test_priority_dominates_share(self):
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 1 << 30)
        tt.note_bound([_pod_in("a", "bound", cpu="5000m")])
        # tenant a's pod has HIGHER priority: it must still go first
        pods = self._pods([("a", 100, 50), ("b", 100, 0)])
        order = fair_order(
            np.asarray([0, 1], dtype=np.int32), pods,
            np.asarray([50, 0], dtype=np.int32), tt,
        )
        assert [int(i) for i in order] == [0, 1]

    def test_virtual_share_interleaves_equal_tenants(self):
        """Equal starting shares: the merge round-robins (each placed
        pod advances its tenant's virtual share past the other's)."""
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 1 << 30)
        pods = self._pods(
            [("a", 500, 0)] * 3 + [("b", 500, 0)] * 3
        )
        order = fair_order(
            np.arange(6, dtype=np.int32), pods,
            np.zeros(6, dtype=np.int32), tt,
        )
        ns_seq = [pods[int(i)].metadata.namespace for i in order]
        assert ns_seq == ["a", "b", "a", "b", "a", "b"]

    def test_mixed_resource_tenants_seed_per_axis_usage(self):
        """The virtual progression seeds from each tenant's ACTUAL
        per-axis usage, not the dominant share smeared across both
        axes: A (50% cpu / ~0% mem) still outranks B (40% / 40%) on a
        mem-dominant comparison once B's true mem usage counts."""
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 10_000)
        # A: 50% cpu, ~0% mem (dominant share 0.50, cpu-pinned)
        a_bound = _pod_in("a", "abound", cpu="5000m")
        a_bound.spec.containers[0].resources.requests["memory"] = 0
        pod_resource_requests(a_bound)
        tt.note_bound([a_bound])
        # B: 52% on BOTH axes (dominant share 0.52)
        b_bound = _pod_in("b", "bbound", cpu="5200m")
        b_bound.spec.containers[0].resources.requests["memory"] = (
            5200 * 1024
        )
        pod_resource_requests(b_bound)
        tt.note_bound([b_bound])
        # mem-only contenders: A's dominant share stays cpu-pinned at
        # 0.50 no matter how many it places (its mem axis starts near
        # ZERO), so all four A pods lead. A share-smeared seed would
        # start A's virtual mem at 50% of capacity, cross B's 0.52
        # after two placements, and wrongly hand B the middle slots.
        pods = []
        for i, ns in enumerate(["a", "b", "a", "b", "a", "a"]):
            p = _pod_in(ns, f"m{i}", cpu="0")
            p.spec.containers[0].resources.requests["memory"] = (
                100 * 1024
            )
            pod_resource_requests(p)
            pods.append(p)
        order = fair_order(
            np.arange(6, dtype=np.int32), pods,
            np.zeros(6, dtype=np.int32), tt,
        )
        ns_seq = [pods[int(i)].metadata.namespace for i in order]
        assert ns_seq == ["a", "a", "a", "a", "b", "b"], ns_seq

    def test_single_tenant_fast_path_returns_base(self):
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 1 << 30)
        pods = self._pods([("only", 100, 0)] * 4)
        base = np.asarray([2, 0, 3, 1], dtype=np.int32)
        order = fair_order(
            base, pods, np.zeros(4, dtype=np.int32), tt
        )
        assert order is base

    def test_fifo_within_tenant_preserved(self):
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 1 << 30)
        pods = self._pods(
            [("a", 100, 0), ("b", 100, 0), ("a", 100, 0), ("b", 100, 0)]
        )
        order = [int(i) for i in fair_order(
            np.arange(4, dtype=np.int32), pods,
            np.zeros(4, dtype=np.int32), tt,
        )]
        assert order.index(0) < order.index(2)
        assert order.index(1) < order.index(3)


class TestDRFBiasE2E:
    def test_contended_capacity_splits_fairly(self):
        """Two tenants, one with existing usage, contending for a
        cluster that fits half the burst: the under-served tenant must
        take at least its fair share of the contended binds."""
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=64)
        arm_tenancy(sched, client, informers, quota=False)
        try:
            # 2 nodes x 8 pods capacity = 16 slots
            for i in range(2):
                client.create_node(
                    make_node(f"n{i}")
                    .capacity(cpu="4", memory="16Gi", pods=8)
                    .obj()
                )
            informers.start()
            informers.wait_for_cache_sync()
            sched.queue.run()
            # heavy's previous usage: 6 pre-bound pods
            for i in range(6):
                p = _pod_in("heavy", f"pre{i}", cpu="400m")
                p.spec.node_name = f"n{i % 2}"
                client.create_pod(p)
            # the contended burst: heavy first in FIFO order, then light
            for i in range(10):
                client.create_pod(_pod_in("heavy", f"h{i}", cpu="400m"))
            for i in range(10):
                client.create_pod(_pod_in("light", f"l{i}", cpu="400m"))
            sched.start()
            _wait(
                lambda: sum(
                    1 for p in client.list_pods()[0] if p.spec.node_name
                ) >= 16,
                timeout=30,
            )
            sched.wait_for_inflight_binds()
            bound_light = sum(
                1 for p in client.list_pods()[0]
                if p.spec.node_name and p.metadata.namespace == "light"
                and p.metadata.name.startswith("l")
            )
            # 10 contended slots (16 - 6 pre-bound): FIFO alone would
            # give heavy all 10; DRF must hand light at least half
            assert bound_light >= 5, f"light bound only {bound_light}"
        finally:
            sched.stop()
            informers.stop()


class TestPlainPodIngestGuard:
    def test_native_ingest_stays_fallback_free_with_tenancy_armed(self):
        """Tier-1 guard: arming the fairness plane must not knock plain
        pods off the native ingest fast path -- tenant identity is the
        namespace the decode already materialized, so ingest_stamp runs
        unchanged and books zero fallbacks."""
        from kubernetes_tpu import native as _native
        from kubernetes_tpu.utils import metrics

        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=64)
        arm_tenancy(sched, client, informers)
        try:
            fallbacks0 = sum(
                metrics.ingest_native_fallbacks.value(site=s)
                for s in (
                    "classify-stamp", "informer-apply", "queue-shape",
                    "pack-gather",
                )
            )
            pods = []
            for i in range(64):
                p = _pod_in(f"tenant-{i % 8}", f"plain{i}", cpu="250m")
                pods.append(p)
            sched.classify_pods_bulk(pods)
            fallbacks1 = sum(
                metrics.ingest_native_fallbacks.value(site=s)
                for s in (
                    "classify-stamp", "informer-apply", "queue-shape",
                    "pack-gather",
                )
            )
            assert fallbacks1 == fallbacks0
            plain = sched._plain_admission_record()
            for p in pods:
                assert "_packrow" in p.__dict__
                assert "_req_memo" in p.__dict__
                if _native.ingest_fn("ingest_stamp")[0] is not None:
                    # the shared read-only record serves every plain pod
                    assert p.__dict__["_admission"] is plain
        finally:
            sched.stop()
            informers.stop()


class TestQuarantineRelist:
    def test_persisted_condition_parks_at_relist(self):
        """ROADMAP item 6c: a restarted scheduler relists a pending pod
        still carrying PodQuarantined=True -- it must re-park, never
        re-enter batches, until a REAL spec update releases it."""
        from kubernetes_tpu.robustness.containment import (
            QUARANTINE_CONDITION,
        )

        server = APIServer()
        client = Client(server)
        # the pod was parked by the PREVIOUS incarnation
        poisoned = make_pod("poison").container(cpu="100m").obj()
        poisoned.status.conditions.append(PodCondition(
            type=QUARANTINE_CONDITION, status="True",
            reason="QuarantineBudgetExhausted",
        ))
        client.create_pod(poisoned)
        client.create_pod(make_pod("healthy").container(cpu="100m").obj())
        client.create_node(
            make_node("n0").capacity(cpu="16", memory="32Gi").obj()
        )
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=64)
        try:
            informers.start()
            informers.wait_for_cache_sync()
            sched.queue.run()
            sched.start()
            assert _wait(lambda: bool(
                client.get_pod("default", "healthy").spec.node_name
            ))
            sched.wait_for_inflight_binds()
            assert sched.queue.quarantine_parked_count() == 1
            assert not client.get_pod("default", "poison").spec.node_name
            # cluster events never wake it
            client.create_node(
                make_node("n1").capacity(cpu="16", memory="32Gi").obj()
            )
            time.sleep(0.5)
            assert sched.queue.quarantine_parked_count() == 1
            # a REAL spec update (operator intervention) releases it
            # (guaranteed_update is copy-on-write: nested collections
            # are REPLACED, never mutated in place)
            client.server.guaranteed_update(
                "Pod", "default", "poison",
                lambda p: setattr(
                    p.metadata, "labels",
                    {**p.metadata.labels, "fixed": "yes"},
                ),
            )
            assert _wait(lambda: bool(
                client.get_pod("default", "poison").spec.node_name
            ))
        finally:
            sched.stop()
            informers.stop()


class _HalfCoord:
    """Stub partition coordinator owning an explicit node set (queue-
    side responsibility stays open: these tests only exercise the
    cache/tenancy side of the partition gates)."""

    def __init__(self, owned):
        self.owned = set(owned)

    def wants_pod(self, pod):
        return True

    def owns_node(self, name):
        return name in self.owned

    def owns_node_obj(self, node):
        return node.metadata.name in self.owned


class TestClusterWideShares:
    """Residual 7(a) (ISSUE 18): partitioned-mode DRF dominant shares
    fold sibling stacks' bind echoes (the cache-side echo path sees
    them even though the partitioned cache drops them) and divide by
    CLUSTER capacity, not the stack's N/P-row slice."""

    def _stack(self, server, owned):
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=16)
        sched.partition_coordinator = _HalfCoord(owned)
        arm_tenancy(sched, client, informers, quota=False)
        return client, informers, sched

    def test_foreign_bind_echo_folds_into_shares(self):
        server = APIServer()
        client, informers, sched = self._stack(server, {"node-0"})
        try:
            for i in range(2):
                client.create_node(
                    make_node(f"node-{i}")
                    .capacity(cpu="16", memory="32Gi").obj()
                )
            # our commit on the owned node + a sibling stack's commit on
            # the foreign node, arriving as plain bind echoes
            ours = _pod_in("tenant-a", "p-ours", cpu="1000m")
            ours.spec.node_name = "node-0"
            theirs = _pod_in("tenant-b", "p-theirs", cpu="1000m")
            theirs.spec.node_name = "node-1"
            client.create_pod(ours)
            client.create_pod(theirs)
            informers.pump()
            tt = sched.tenant_shares
            tt.refresh_capacity(None)  # node feed wins; nt unused
            used, cap_cpu, cap_mem = tt.usage_and_caps(
                ["tenant-a", "tenant-b"]
            )
            assert used["tenant-a"][0] == 1000
            assert used["tenant-b"][0] == 1000, (
                "sibling-stack bind echo must fold into the shares"
            )
            # denominator is BOTH nodes, not the owned slice
            assert cap_cpu == 32000
            assert abs(tt.share("tenant-b") - 1000 / 32000) < 1e-9
            # the foreign pod must NOT have entered the partitioned cache
            assert "node-1" not in sched.cache._nodes
        finally:
            sched.stop()
            informers.stop()

    def test_uid_double_echo_dedup_and_unbind_retires(self):
        tt = TenantShareTracker()
        tt.set_capacity(10_000, 1 << 30)
        pod = _pod_in("a", "p", cpu="5000m")
        tt.note_bound([pod])
        tt.note_bound([pod])  # relist re-echo of the same bind
        assert tt.share("a") == 0.5
        tt.note_unbound([pod])
        assert tt.share("a") == 0.0
        # a genuine re-bind after the unbind counts again
        tt.note_bound([pod])
        assert tt.share("a") == 0.5

    def test_two_stacks_converge_to_cluster_truth(self):
        server = APIServer()
        s1 = self._stack(server, {"node-0", "node-1"})
        s2 = self._stack(server, {"node-2", "node-3"})
        try:
            client = s1[0]
            for i in range(4):
                client.create_node(
                    make_node(f"node-{i}")
                    .capacity(cpu="10", memory="16Gi").obj()
                )
            for i in range(4):
                p = _pod_in(
                    "tenant-a" if i % 2 == 0 else "tenant-b",
                    f"b{i}", cpu="2000m",
                )
                p.spec.node_name = f"node-{i}"
                client.create_pod(p)
            for _c, informers, _s in (s1, s2):
                informers.pump()
            views = []
            for _c, _i, sched in (s1, s2):
                tt = sched.tenant_shares
                tt.refresh_capacity(None)
                views.append(
                    tt.usage_and_caps(["tenant-a", "tenant-b"])
                )
            assert views[0] == views[1], (
                "both stacks must see identical cluster-wide usage"
            )
            used, cap_cpu, _ = views[0]
            assert used["tenant-a"] == (4000, used["tenant-a"][1])
            assert used["tenant-b"][0] == 4000
            assert cap_cpu == 40000
            # node retirement shrinks the shared denominator everywhere
            client.delete_node("node-3")
            for _c, informers, _s in (s1, s2):
                informers.pump()
            for _c, _i, sched in (s1, s2):
                tt = sched.tenant_shares
                tt.refresh_capacity(None)
                assert tt.usage_and_caps([])[1] == 30000
        finally:
            for _c, informers, sched in (s1, s2):
                sched.stop()
                informers.stop()
