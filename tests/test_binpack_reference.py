"""The plain reference for bin-packing (``chipbench/binpack_reference.py``):
its lemma by brute force (under ``NodeResourcesMostAllocated`` the
multiset of pods that each group of alike nodes receives is the same
under every order of arrival and every tie-break, where ``exact_for``
says so, and not where it does not), and the program held to it on
seeded small clusters through the operator's path
(``load_config_from_dict`` -> ``new_scheduler_from_config`` -> apiserver
-> informers -> ``BatchScheduler``) under the bin-packing profile."""

import itertools
import time

import numpy as np
import pytest

from chipbench import binpack_reference as ref
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.config.loader import load_config_from_dict
from kubernetes_tpu.scheduler.scheduler import new_scheduler_from_config
from kubernetes_tpu.testing import make_node, make_pod

MIB = 1 << 20
GPU = "nvidia.com/gpu"
ZONE = "topology.kubernetes.io/zone"
#: one GPU's worth of the cell's pod, in the reference's columns
UNIT = np.array([3500, 7000 * MIB, 0, 1], dtype=np.int64)
CAP = np.array([32000, 64 << 30, 110, 8], dtype=np.int64)


def nodes_holding(held) -> ref.Nodes:
    """Eight-GPU nodes that hold ``held[i]`` one-GPU pods each."""
    held = np.asarray(held, dtype=np.int64)
    used = held[:, None] * UNIT[None, :]
    used[:, ref.PODS] = held
    return ref.Nodes(np.tile(CAP, (held.shape[0], 1)), used)


def pod_of(gpus: int) -> np.ndarray:
    pod = UNIT * gpus
    pod[ref.PODS] = 1
    return pod


# -- the lemma, by brute force -------------------------------------------------


def multisets(nodes, eligible, per_node) -> tuple:
    """A placement as the comparison sees it: for each group of alike
    nodes of the pool, the sorted counts its nodes received."""
    label = ref.groups(nodes, eligible)
    return tuple(
        (int(g), tuple(sorted(per_node[label == g].tolist())))
        for g in np.unique(label[label >= 0])
    )


def every_outcome(nodes, arrivals, pools) -> set:
    """Every placement the sequential rule can end in for ``arrivals``
    (a sequence of pool names, in their order of arrival) over every
    tie-break: at each step any feasible node of the pool that scores
    highest may be taken. Returns the set of (pool -> group multisets)."""
    n = nodes.cap.shape[0]
    found = set()

    def step(k, used, per_pool):
        if k == len(arrivals):
            found.add(tuple(
                (name, multisets(nodes, pools[name][1], per_pool[name]))
                for name in sorted(pools)
            ))
            return
        name = arrivals[k]
        pod, eligible = pools[name]
        now = ref.Nodes(nodes.cap, used)
        feasible = ref.fits(now, used, pod) & eligible
        if not feasible.any():
            step(k + 1, used, per_pool)
            return
        score = np.where(
            feasible, ref.scores(now, used, pod, "most", "exact"), -1
        )
        for i in np.flatnonzero(score == score.max()):
            more = used.copy()
            more[i] += pod
            got = dict(per_pool)
            got[name] = per_pool[name].copy()
            got[name][i] += 1
            step(k + 1, more, got)

    step(0, nodes.used.copy(), {p: np.zeros(n, np.int64) for p in pools})
    return found


def lemma_instances():
    rng = np.random.default_rng(43)
    for _ in range(16):
        n = 6
        nodes = nodes_holding(rng.integers(0, 9, size=n))
        half = np.arange(n) % 2 == 0
        pools = {
            "a": (pod_of(int(rng.choice([1, 2, 4]))), half),
            "b": (pod_of(int(rng.choice([1, 2, 4, 8]))), ~half),
        }
        counts = {"a": int(rng.integers(2, 5)), "b": int(rng.integers(1, 4))}
        yield nodes, pools, counts


@pytest.mark.parametrize("k", range(16))
def test_the_group_multisets_are_the_same_under_every_order_and_tie_break(k):
    nodes, pools, counts = list(lemma_instances())[k]
    pods = [name for name, c in counts.items() for _ in range(c)]
    for name, (pod, eligible) in pools.items():
        assert ref.exact_for(nodes, pod, eligible)
    outcomes = set()
    for arrivals in set(itertools.permutations(pods)):
        outcomes |= every_outcome(nodes, arrivals, pools)
    assert len(outcomes) == 1, outcomes
    (outcome,) = outcomes
    for name, got in outcome:
        pod, eligible = pools[name]
        want, _ = ref.schedule(nodes, pod, counts[name], eligible)
        assert got == multisets(nodes, eligible, want)


def test_the_comparison_counts_the_pods_it_cannot_explain():
    nodes = nodes_holding([7, 7, 3, 3, 0, 0])
    pod = pod_of(1)
    want, unplaced = ref.schedule(nodes, pod, 5)
    assert want.tolist() == [1, 1, 3, 0, 0, 0] and unplaced == 0
    # the twin of a node is as good as the node: which of two alike
    # nodes is filled is the tie-break's
    assert ref.unexplained(nodes, pod, 5, [1, 1, 0, 3, 0, 0]) == 0
    # one pod on an empty node that the rule leaves empty
    assert ref.unexplained(nodes, pod, 5, [1, 1, 2, 0, 1, 0]) == 1
    # spread as the default rule spreads
    assert ref.unexplained(nodes, pod, 5, [0, 0, 1, 1, 2, 1]) == 4
    # a pod never bound counts whole, and one outside the pool too
    assert ref.unexplained(nodes, pod, 5, [1, 1, 2, 0, 0, 0]) == 1
    pool = np.array([1, 1, 1, 1, 1, 0], dtype=bool)
    assert ref.unexplained(nodes, pod, 5, [1, 1, 2, 0, 0, 1], pool) == 2


def test_where_groups_tie_the_comparison_says_it_is_not_exact():
    """Pods so small that two loads floor to one score: which group is
    opened first is the tie-break's, the multisets differ, and
    ``exact_for`` says so."""
    tiny = np.array([100, 100 * MIB, 1, 0], dtype=np.int64)
    nodes = nodes_holding([0, 0])
    nodes.used[1, :2] = 100, 100 * MIB  # another load, the same score
    nodes.cap[:, ref.PODS] = 3
    everyone = np.ones(2, dtype=bool)
    assert not ref.exact_for(nodes, tiny, everyone)
    outcomes = every_outcome(nodes, ["p"] * 2, {"p": (tiny, everyone)})
    assert len(outcomes) > 1


def test_the_default_rule_is_told_from_the_profiles():
    """The control: the reference reading the default provider's score
    spreads where the profile packs."""
    nodes = nodes_holding([8] * 4 + [5] * 4 + [0] * 8)
    pod = pod_of(1)
    spread, _ = ref.schedule(nodes, pod, 12, rule="default")
    assert (spread[8:] > 0).all() and spread[4:8].sum() == 0
    assert ref.unexplained(nodes, pod, 12, spread) == 12
    packed, _ = ref.schedule(nodes, pod, 12)
    assert packed[4:8].tolist() == [3, 3, 3, 3] and packed[8:].sum() == 0


# -- the program, held to it ---------------------------------------------------

NODES = 16
ZONES = 4
SIZES = {0: 1, 1: 2, 2: 4, 3: 8}  # the GPUs a pod of each zone's pool asks
PACKING = {
    "tpuSolver": {"maxBatch": 64},
    "profiles": [{"schedulerName": "default-scheduler", "plugins": {"score": {
        "disabled": [{"name": "NodeResourcesLeastAllocated"},
                     {"name": "NodeResourcesBalancedAllocation"}],
        "enabled": [{"name": "NodeResourcesMostAllocated", "weight": 1}],
    }}}],
}


@pytest.fixture(scope="module")
def stack():
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler_from_config(
        client, informers, load_config_from_dict(PACKING)
    )
    for i in range(NODES):
        node = (make_node(f"node-{i}")
                .capacity(cpu="32", memory="64Gi", pods=110)
                .label(ZONE, f"zone-{i % ZONES}").obj())
        node.status.capacity[GPU] = node.status.allocatable[GPU] = 8
        client.create_node(node)
    informers.start()
    informers.wait_for_cache_sync()
    sched.start()
    yield server, client, sched
    sched.stop()
    informers.stop()


def gpu_pod(name, gpus, zone=None, node=None):
    w = make_pod(name).container(
        cpu=f"{3500 * gpus}m", memory=f"{7000 * gpus}Mi")
    if zone is not None:
        w = w.node_selector(**{ZONE: f"zone-{zone}"})
    if node is not None:
        w = w.node(node)
    pod = w.obj()
    pod.spec.containers[0].resources.requests[GPU] = gpus
    return pod


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
def test_the_programs_placements_are_the_references_group_multisets(
    stack, seed,
):
    server, client, sched = stack
    rng = np.random.default_rng(4300 + seed)
    tag = f"s{seed}"
    held = rng.integers(0, 9, size=NODES)
    client.create_pods_bulk([
        gpu_pod(f"{tag}-res-{i}-{j}", 1, node=f"node-{i}")
        for i in range(NODES) for j in range(int(held[i]))
    ])
    assert until(lambda: sched.cache.pod_count() == int(held.sum()), 30)
    nodes = nodes_holding(held)
    zone = np.arange(NODES) % ZONES
    wave = []
    asked = {}
    for z, gpus in SIZES.items():
        free = int(((8 - held[zone == z]) // gpus).sum())
        asked[z] = int(rng.integers(0, free + 1))
        wave += [gpu_pod(f"{tag}-z{z}-{i}", gpus, zone=z)
                 for i in range(asked[z])]
    wave = [wave[int(k)] for k in rng.permutation(len(wave))]
    for i in range(0, len(wave), 16):  # a wave arrives split over creates
        client.create_pods_bulk(wave[i:i + 16])
    try:
        total = int(held.sum()) + len(wave)
        assert until(lambda: len(where(client)) == total, 30), (
            len(where(client)), total)
        sched.wait_for_inflight_binds()
        now = where(client)
        assert sched.pods_fallback == 0
        for z, gpus in SIZES.items():
            got = np.zeros(NODES, dtype=np.int64)
            for name, node in now.items():
                if name.startswith(f"{tag}-z{z}-"):
                    got[int(node.rsplit("-", 1)[1])] += 1
            assert got[zone != z].sum() == 0  # inside its pool
            assert (held + got * gpus <= 8).all()  # eight GPUs a node
            assert ref.exact_for(nodes, pod_of(gpus), zone == z)
            assert ref.unexplained(
                nodes, pod_of(gpus), asked[z], got, zone == z) == 0, (z, got)
    finally:
        names = [p.metadata.name for p in client.list_pods()[0]]
        client.delete_pods_bulk([("default", n) for n in names])
        assert until(lambda: sched.cache.pod_count() == 0, 30)
