"""The program's gang path against the plain reference
(``chipbench/gang_reference.py``), on the CPU: seeded random small
clusters with gangs of mixed sizes that offer about twice the free
slots, through the operator's path (``load_config_from_dict`` ->
``new_scheduler_from_config`` -> apiserver -> informers ->
``BatchScheduler``); and the reference's lemma by brute force: with pods
of one size the outcomes of ``admit`` over all orders are exactly the
maximal all-or-nothing packings, which is what lets a run be held to
``admissible`` whatever its order, batching and tie-break."""

import itertools
import time

import numpy as np
import pytest

from chipbench import gang_reference, reference
from kubernetes_tpu.api.types import ObjectMeta, POD_GROUP_LABEL, PodGroup
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.config.loader import load_config_from_dict
from kubernetes_tpu.scheduler.scheduler import new_scheduler_from_config
from kubernetes_tpu.testing import make_node, make_pod

MIB = 1 << 20
WORKER = reference.PodClass(cpu=1000, mem=1024 * MIB)
NODES = 12
PER_NODE = 4  # workers a node holds


def nodes_with(used) -> reference.Nodes:
    used = np.asarray(used, dtype=np.int64)
    n = used.shape[0]
    return reference.Nodes(
        cap_cpu=np.full(n, PER_NODE * WORKER.cpu, dtype=np.int64),
        cap_mem=np.full(n, PER_NODE * WORKER.mem, dtype=np.int64),
        cap_pods=np.full(n, 110, dtype=np.int64),
        used_cpu=used * WORKER.cpu, used_mem=used * WORKER.mem,
        used_pods=used.copy(), zone=np.zeros(n, dtype=np.int64),
    )


# -- the lemma, by brute force -------------------------------------------------


def instances():
    rng = np.random.default_rng(34)
    for _ in range(12):
        used = rng.integers(0, PER_NODE + 1, size=6)
        count = int(rng.integers(5, 7))
        sizes = rng.choice([1, 2, 3, 5, 8], size=count)
        yield nodes_with(used), {f"g{k}": int(s) for k, s in enumerate(sizes)}


def outcomes_over_all_orders(nodes, gangs) -> set:
    return {
        frozenset(gang_reference.admit(nodes, WORKER, gangs, order)[0])
        for order in itertools.permutations(gangs)
    }


def maximal_packings(nodes, gangs) -> set:
    """By slot counts alone: sets of gangs that fit together and leave
    fewer slots than any gang outside them needs."""
    free = gang_reference.slots(nodes, WORKER)
    found = set()
    for r in range(len(gangs) + 1):
        for chosen in itertools.combinations(gangs, r):
            left = free - sum(gangs[g] for g in chosen)
            if left >= 0 and all(
                gangs[g] > left for g in gangs if g not in chosen
            ):
                found.add(frozenset(chosen))
    return found


@pytest.mark.parametrize("k", range(12))
def test_the_outcomes_over_all_orders_are_the_maximal_packings(k):
    nodes, gangs = list(instances())[k]
    outcomes = outcomes_over_all_orders(nodes, gangs)
    packings = maximal_packings(nodes, gangs)
    # every outcome is a maximal packing, and every maximal packing is
    # the outcome of some order: the lemma's two directions
    assert outcomes <= packings
    assert packings <= outcomes


@pytest.mark.parametrize("k", range(12))
def test_admissible_is_membership_in_the_outcomes_of_all_orders(k):
    nodes, gangs = list(instances())[k]
    outcomes = outcomes_over_all_orders(nodes, gangs)
    for r in range(len(gangs) + 1):
        for chosen in itertools.combinations(gangs, r):
            assert gang_reference.admissible(
                nodes, WORKER, gangs, chosen
            ) == (frozenset(chosen) in outcomes), chosen


def test_a_gang_that_finds_no_node_holds_nothing():
    nodes = nodes_with([3, 3, 4])  # two slots
    got, per_node = gang_reference.admit(
        nodes, WORKER, {"big": 3, "small": 2}, ["big", "small"]
    )
    assert got == ["small"] and int(per_node.sum()) == 2


def test_the_control_reads_no_pod_groups():
    nodes = nodes_with([3, 3, 4])
    bound = gang_reference.ignoring_groups(
        nodes, WORKER, {"big": 3, "small": 2}, ["big", "small"]
    )
    assert bound == {"big": 2, "small": 0}  # a gang bound in part


# -- the program, held to it ---------------------------------------------------


@pytest.fixture(scope="module")
def stack():
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler_from_config(
        client, informers,
        load_config_from_dict({"tpuSolver": {"maxBatch": 64}}),
    )
    for i in range(NODES):
        client.create_node(
            make_node(f"node-{i}").capacity(
                cpu=str(PER_NODE), memory=f"{PER_NODE}Gi", pods=110
            ).obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.start()
    yield server, client, sched
    sched.stop()
    informers.stop()


def worker(name, group=None):
    p = make_pod(name).container(cpu="1000m", memory="1024Mi").obj()
    if group:
        p.metadata.labels[POD_GROUP_LABEL] = group
    return p


def until(fn, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(0.02)
    return False


def where(client) -> dict:
    return {
        p.metadata.name: p.spec.node_name
        for p in client.list_pods()[0] if p.spec.node_name
    }


@pytest.mark.parametrize("seed", range(32))
def test_the_programs_settled_outcome_is_one_the_reference_gives(stack, seed):
    server, client, sched = stack
    rng = np.random.default_rng(3400 + seed)
    tag = f"s{seed}"
    residents = int(rng.integers(NODES * PER_NODE // 3, NODES * PER_NODE - 6))
    client.create_pods_bulk(
        [worker(f"{tag}-res-{i}") for i in range(residents)]
    )
    assert until(lambda: len(where(client)) == residents, 30)
    before = where(client)
    used = np.zeros(NODES, dtype=np.int64)
    for node in before.values():
        used[int(node.rsplit("-", 1)[1])] += 1
    nodes = nodes_with(used)
    free = gang_reference.slots(nodes, WORKER)
    assert free == NODES * PER_NODE - residents

    gangs, offered = {}, 0
    while offered < 2 * free:
        size = int(rng.choice([1, 2, 3, 4, 6, 9]))
        gangs[f"{tag}-job{len(gangs)}"] = size
        offered += size
    order = list(gangs)
    rng.shuffle(order)
    pods = []
    for group in order:
        client.create_pod_group(PodGroup(
            metadata=ObjectMeta(name=group, namespace="default"),
            min_member=gangs[group], schedule_timeout_seconds=10,
        ))
        pods += [worker(f"{group}-{i}", group) for i in range(gangs[group])]
    for i in range(0, len(pods), 16):  # gangs arrive split over creates
        client.create_pods_bulk(pods[i:i + 16])

    def state():
        bound = where(client)
        count = {
            g: sum(1 for i in range(n) if f"{g}-{i}" in bound)
            for g, n in gangs.items()
        }
        whole = [g for g, n in gangs.items() if count[g] == n]
        part = [g for g, n in gangs.items() if 0 < count[g] < n]
        left = free - sum(count.values())
        fits = [g for g, n in gangs.items() if count[g] == 0 and n <= left]
        return whole, part, fits

    try:
        assert until(lambda: not state()[1] and not state()[2], 30), state()
        sched.wait_for_inflight_binds()
        whole, part, fits = state()
        assert not part and not fits
        assert gang_reference.admissible(nodes, WORKER, gangs, whole)
        # the order the benchmark's comparison builds: the gangs the
        # program bound, then the rest as created
        rest = [g for g in order if g not in set(whole)]
        got, per_node = gang_reference.admit(
            nodes, WORKER, gangs, whole + rest
        )
        assert set(got) == set(whole)
        # and every bound member sits where a node had the room
        now = where(client)
        landed = np.zeros(NODES, dtype=np.int64)
        for name, node in now.items():
            if name not in before:
                landed[int(node.rsplit("-", 1)[1])] += 1
        assert (used + landed <= PER_NODE).all()
        assert int(landed.sum()) == int(per_node.sum())
    finally:
        names = [p.metadata.name for p in client.list_pods()[0]]
        client.delete_pods_bulk([("default", n) for n in names])
        server.delete_bulk("PodGroup", [("default", g) for g in gangs])
        assert until(lambda: sched.cache.pod_count() == 0, 30)
