"""Tier-1 perf guard (fast smoke): the device path must carry a basic
burst AND a CSI-PV burst with ZERO host fallbacks, so a host-path cliff
(the 54 pods/s SchedulingCSIPVs regression shape) fails CI loudly
instead of silently degrading the perf matrix."""

import time

import pytest

from kubernetes_tpu.api.types import (
    CSINode,
    CSINodeDriver,
    ObjectMeta,
    PersistentVolume,
    PersistentVolumeClaim,
)
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod


@pytest.fixture
def stack():
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=32)
    yield server, client, informers, sched
    sched.stop()
    informers.stop()


def _wait_all_bound(client, count, timeout=60.0):
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


def test_basic_workload_zero_fallback(stack):
    server, client, informers, sched = stack
    for i in range(6):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="16", memory="32Gi").obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    for i in range(24):
        client.create_pod(
            make_pod(f"p{i}").container(cpu="250m", memory="256Mi").obj()
        )
    sched.start()
    _wait_all_bound(client, 24)
    sched.wait_for_inflight_binds()
    assert sched.pods_fallback == 0
    assert sched.pods_solved_on_device >= 24


def test_csi_pv_workload_zero_fallback(stack):
    """The acceptance shape of the volume-count columns: every pod
    carries a bound CSI PV, the nodes advertise CSINode attach limits,
    and the whole burst rides the device path end to end."""
    server, client, informers, sched = stack
    for i in range(6):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="16", memory="32Gi").obj()
        )
        server.create(
            CSINode(
                metadata=ObjectMeta(name=f"n{i}", namespace=""),
                drivers=[
                    CSINodeDriver(
                        name="ebs.csi.aws.com", node_id=f"n{i}",
                        allocatable_count=8,
                    )
                ],
            )
        )
    for i in range(24):
        cn, vn = f"pvc-{i}", f"pv-{i}"
        server.create(
            PersistentVolumeClaim(
                metadata=ObjectMeta(name=cn, namespace="default"),
                volume_name=vn,
                requested_bytes=1 << 30,
            )
        )
        server.create(
            PersistentVolume(
                metadata=ObjectMeta(name=vn, namespace=""),
                capacity_bytes=1 << 30,
                claim_ref_namespace="default",
                claim_ref_name=cn,
                csi_driver="ebs.csi.aws.com",
                csi_volume_handle=vn,
            )
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    for i in range(24):
        client.create_pod(
            make_pod(f"p{i}")
            .container(cpu="250m", memory="256Mi")
            .pvc(f"pvc-{i}")
            .obj()
        )
    sched.start()
    _wait_all_bound(client, 24)
    sched.wait_for_inflight_binds()
    assert sched.pods_fallback == 0, (
        "CSI-PV pods fell off the device path"
    )
    assert sched.volume_reject_retries == 0
    assert sched.pods_solved_on_device >= 24
    # attach limits respected AND accounted in the cache
    per_node = {}
    for name, ni in sched.cache._nodes.items():
        used = ni.volume_in_use.get(
            "attachable-volumes-csi-ebs.csi.aws.com", 0
        )
        per_node[name] = used
        assert used <= 8
    assert sum(per_node.values()) == 24
