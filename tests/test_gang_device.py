"""Gang all-or-nothing group masks in the device solver (SURVEY stage 6).

A half-fitting gang must place ZERO pods (no capacity reserved, no
Permit-timeout churn); a fitting gang places fully and releases through
Permit. Reference hook: framework/v1alpha1/interface.go:384 (Permit) +
the out-of-tree coscheduling pattern.
"""

import time

from kubernetes_tpu.api.types import ObjectMeta, POD_GROUP_LABEL, PodGroup
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod


def _cluster(max_batch=32):
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=max_batch)
    return server, client, informers, sched


def _gang_pod(name, group, cpu="1"):
    p = make_pod(name).container(cpu=cpu, memory="128Mi").obj()
    p.metadata.labels[POD_GROUP_LABEL] = group
    return p


def _pg(client, name, min_member):
    client.create_pod_group(
        PodGroup(
            metadata=ObjectMeta(name=name, namespace="default"),
            min_member=min_member,
        )
    )


def test_half_fitting_gang_places_nothing():
    server, client, informers, sched = _cluster()
    # capacity for 4 gang pods; the gang needs 6
    for i in range(2):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="2", memory="8Gi").obj()
        )
    _pg(client, "g6", 6)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    for i in range(6):
        client.create_pod(_gang_pod(f"g{i}", "g6"))
    deadline = time.time() + 10
    while time.time() < deadline:
        sched.schedule_batch(timeout=0.2)
        if sched.queue.num_pending()["unschedulable"] == 6:
            break
    sched.wait_for_inflight_binds()
    pods, _ = client.list_pods()
    bound = [p for p in pods if p.spec.node_name]
    # all-or-nothing: NOTHING placed, nothing parked at Permit
    assert bound == []
    assert sched.queue.num_pending()["unschedulable"] == 6
    for fw in sched.profiles.values():
        assert not fw.waiting_pods.list() if hasattr(
            fw.waiting_pods, "list"
        ) else True
    sched.stop()
    informers.stop()


def test_fitting_gang_places_fully_on_device():
    server, client, informers, sched = _cluster()
    for i in range(3):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="4", memory="8Gi").obj()
        )
    _pg(client, "g6", 6)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    for i in range(6):
        client.create_pod(_gang_pod(f"g{i}", "g6"))
    sched.start()
    deadline = time.time() + 30
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if sum(1 for p in pods if p.spec.node_name) == 6:
            break
        time.sleep(0.05)
    sched.wait_for_inflight_binds()
    sched.stop()
    informers.stop()
    pods, _ = client.list_pods()
    assert sum(1 for p in pods if p.spec.node_name) == 6


def test_gang_failure_releases_capacity_to_other_pods():
    """The re-solve gives the failed gang's capacity to later plain pods
    in the same batch instead of leaving it reserved."""
    server, client, informers, sched = _cluster()
    client.create_node(
        make_node("n0").capacity(cpu="4", memory="8Gi").obj()
    )
    _pg(client, "g8", 8)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    # gang of 8 x 1cpu (needs 8, only 4 fit) + 4 plain 1cpu pods,
    # created gang-first so they sort ahead in the batch
    for i in range(8):
        client.create_pod(_gang_pod(f"g{i}", "g8"))
    for i in range(4):
        client.create_pod(
            make_pod(f"plain{i}").container(cpu="1", memory="128Mi").obj()
        )
    sched.start()
    deadline = time.time() + 30
    while time.time() < deadline:
        pods, _ = client.list_pods()
        plain_bound = sum(
            1
            for p in pods
            if p.spec.node_name and p.metadata.name.startswith("plain")
        )
        if plain_bound == 4:
            break
        time.sleep(0.05)
    sched.wait_for_inflight_binds()
    sched.stop()
    informers.stop()
    pods, _ = client.list_pods()
    gang_bound = [
        p for p in pods
        if p.spec.node_name and p.metadata.name.startswith("g")
    ]
    plain_bound = [
        p for p in pods
        if p.spec.node_name and p.metadata.name.startswith("plain")
    ]
    assert gang_bound == []
    assert len(plain_bound) == 4


def test_split_arrival_gang_assembles_via_permit():
    """A gang split across two batches still assembles: the first half
    waits at Permit (members known), the second half completes it."""
    server, client, informers, sched = _cluster()
    for i in range(4):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="2", memory="8Gi").obj()
        )
    _pg(client, "g6", 6)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    # all 6 members exist up front (known to the informer), but the
    # queue is drained in two waves
    pods = [_gang_pod(f"g{i}", "g6") for i in range(6)]
    for p in pods[:4]:
        client.create_pod(p)
    sched.start()
    time.sleep(1.0)
    for p in pods[4:]:
        client.create_pod(p)
    deadline = time.time() + 30
    while time.time() < deadline:
        got, _ = client.list_pods()
        if sum(1 for p in got if p.spec.node_name) == 6:
            break
        time.sleep(0.05)
    sched.wait_for_inflight_binds()
    sched.stop()
    informers.stop()
    got, _ = client.list_pods()
    assert sum(1 for p in got if p.spec.node_name) == 6


# -- at the benchmark's batch size: work-conserving masks ---------------------
#
# Found by running the deployment gang-train-5000 small (ISSUE 34): with one
# batch for a whole wave, a gang that can never fit took the free slots in the
# first solve, every gang after it failed beside it, all of them were masked,
# and nothing woke them.


def _full_cluster(max_batch, nodes=60, residents=320):
    """Nodes of 32 CPU / 64Gi that hold eight 4000m / 8Gi workers each,
    ``residents`` of them placed by the scheduler, on the operator's path."""
    from kubernetes_tpu.config.loader import load_config_from_dict
    from kubernetes_tpu.scheduler.scheduler import new_scheduler_from_config

    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler_from_config(
        client, informers,
        load_config_from_dict({"tpuSolver": {"maxBatch": max_batch}}),
    )
    for i in range(nodes):
        client.create_node(
            make_node(f"node-{i}")
            .capacity(cpu="32", memory="64Gi", pods=110).obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.start()
    client.create_pods_bulk([_worker(f"res-{i}") for i in range(residents)])
    assert _until(lambda: len(_bound(client)) == residents, 60)
    return server, client, informers, sched


def _worker(name, group=None):
    p = make_pod(name).container(cpu="4000m", memory="8192Mi").obj()
    if group:
        p.metadata.labels[POD_GROUP_LABEL] = group
    return p


def _bound(client):
    return {
        p.metadata.name for p in client.list_pods()[0] if p.spec.node_name
    }


def _until(fn, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(0.05)
    return False


def _offer(client, sizes):
    """One wave: PodGroups first, then every gang's members together, as
    a job controller sends them. Returns gang -> member names."""
    gangs = {}
    pods = []
    for g, size in enumerate(sizes):
        _pg(client, f"job{g}", size)
        gangs[f"job{g}"] = [f"job{g}-{i}" for i in range(size)]
        pods += [_worker(name, f"job{g}") for name in gangs[f"job{g}"]]
    for i in range(0, len(pods), 256):
        client.create_pods_bulk(pods[i:i + 256])
    return gangs


def _settled(client, gangs, free):
    """(whole, part, fits): gangs bound whole, gangs bound in part, and
    unbound gangs that the slots left would hold."""
    bound = _bound(client)
    whole = [g for g, m in gangs.items() if all(n in bound for n in m)]
    part = [
        g for g, m in gangs.items()
        if g not in whole and any(n in bound for n in m)
    ]
    left = free - sum(len(gangs[g]) for g in whole)
    fits = [
        g for g, m in gangs.items()
        if g not in whole and g not in part and len(m) <= left
    ]
    return whole, part, fits


import pytest  # noqa: E402


@pytest.mark.parametrize("max_batch", [256, 4096])
def test_a_gang_that_cannot_fit_starves_no_gang_that_can(max_batch):
    server, client, informers, sched = _full_cluster(max_batch)
    try:
        # the gang that cannot fit comes first, and takes the free slots in
        # a first solve
        gangs = _offer(client, [256] + [8] * 10 + [32] * 2)
        fitting = [g for g, m in gangs.items() if len(m) < 256]

        def done():
            whole, part, _ = _settled(client, gangs, 160)
            return not part and set(whole) == set(fitting)

        assert _until(done, 30), _settled(client, gangs, 160)
        sched.wait_for_inflight_binds()
        assert len(_bound(client)) == 320 + 144
        # the gang that cannot fit holds nothing, at Permit or in the cache
        for fw in sched.profiles.values():
            assert len(fw.waiting_pods) == 0
        assert sched.cache.pod_count() == 320 + 144
    finally:
        sched.stop()
        informers.stop()


@pytest.mark.parametrize("max_batch", [256, 4096])
def test_a_half_fitting_wave_ends_maximal(max_batch):
    server, client, informers, sched = _full_cluster(max_batch)
    try:
        # 304 workers offered onto 160 slots, the sizes mixed
        gangs = _offer(
            client, [64, 8, 32, 8, 8, 64, 8, 32, 8, 8, 32, 8, 8, 8, 8]
        )

        def done():
            whole, part, fits = _settled(client, gangs, 160)
            return whole and not part and not fits

        assert _until(done, 30), _settled(client, gangs, 160)
        sched.wait_for_inflight_binds()
        whole, part, fits = _settled(client, gangs, 160)
        assert not part and not fits
        assert sched.cache.pod_count() == 320 + sum(
            len(gangs[g]) for g in whole
        )
    finally:
        sched.stop()
        informers.stop()
