"""The profile decides the device's resource score: on the operator's
path (``load_config_from_dict`` -> ``new_scheduler_from_config``) each
profile's enabled ``NodeResourcesLeastAllocated``,
``NodeResourcesBalancedAllocation`` and ``NodeResourcesMostAllocated``
and their weights are the batch solver's, so the batch path places as
the host oracle (the framework's own plugins, KeepFirst tie RNG) does,
on clusters with and without ``nvidia.com/gpu``. Until PR 43 the device
scored the default rule under every profile."""

import random
import time

import pytest

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.config.loader import load_config_from_dict
from kubernetes_tpu.ops.assignment import GreedyConfig
from kubernetes_tpu.scheduler.scheduler import new_scheduler_from_config
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics

GPU = "nvidia.com/gpu"
LEAST = "NodeResourcesLeastAllocated"
BALANCED = "NodeResourcesBalancedAllocation"
MOST = "NodeResourcesMostAllocated"
#: ``plugins.score`` of the one profile, and the rule the device must have
PROFILES = {
    "default": ({}, GreedyConfig(1, 1, 0)),
    "most": ({"disabled": [{"name": LEAST}, {"name": BALANCED}],
              "enabled": [{"name": MOST, "weight": 1}]},
             GreedyConfig(0, 0, 1)),
    "least2": ({"disabled": [{"name": LEAST}],
                "enabled": [{"name": LEAST, "weight": 2}]},
               GreedyConfig(2, 1, 0)),
}
PODS = 22
RTCR = {"disabled": [{"name": LEAST}, {"name": BALANCED}],
        "enabled": [{"name": "RequestedToCapacityRatio", "weight": 1}]}


class _KeepFirstRng:
    def randrange(self, n):
        return 1 if n > 1 else 0

    def randint(self, a, b):
        return b


def wire(score: dict, batch: bool) -> dict:
    return {
        "percentageOfNodesToScore": 100,
        "tpuSolver": {"enabled": batch, "maxBatch": 64},
        "profiles": [{"schedulerName": "default-scheduler",
                      "plugins": {"score": score}}],
    }


def with_gpus(obj, kind: str, count: int):
    if kind == "node":
        obj.status.capacity[GPU] = count
        obj.status.allocatable[GPU] = count
    else:
        obj.spec.containers[0].resources.requests[GPU] = count
    return obj


def run(score: dict, batch: bool, gpu: bool, seed: int):
    rng = random.Random(seed)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler_from_config(
        client, informers, load_config_from_dict(wire(score, batch)),
        rng=_KeepFirstRng(),
    )
    for i in range(10):  # distinct capacities: few ties between nodes
        node = make_node(f"n{i}").capacity(
            cpu=str(16 + 2 * i), memory=f"{32 + 5 * i}Gi", pods=110
        ).obj()
        client.create_node(with_gpus(node, "node", 8) if gpu else node)
    for j in range(6):  # residents, bound before the scheduler starts
        pod = (make_pod(f"ex{j}").node(f"n{rng.randrange(10)}")
               .container(cpu="1500m", memory="3Gi").obj())
        client.create_pod(with_gpus(pod, "pod", 1) if gpu else pod)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    for i in range(PODS):
        size = rng.choice([1, 1, 1, 2, 4])
        pod = (make_pod(f"m{i}").creation_timestamp(float(i))
               .container(cpu=f"{size * 1700}m", memory=f"{size * 3500}Mi")
               .obj())
        client.create_pod(with_gpus(pod, "pod", size) if gpu else pod)
    sched.start()
    deadline = time.time() + 90
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if all(p.spec.node_name or p.status.conditions for p in pods):
            break
        time.sleep(0.05)
    sched.wait_for_inflight_binds()
    placed = {p.metadata.name: p.spec.node_name
              for p in client.list_pods()[0]
              if p.metadata.name.startswith("m")}
    sched.stop()
    informers.stop()
    return placed, sched


@pytest.mark.parametrize("gpu", [False, True], ids=["plain", "gpu"])
@pytest.mark.parametrize("profile", sorted(PROFILES))
@pytest.mark.parametrize("seed", [43, 4343])
def test_the_batch_path_places_as_the_host_oracle_under_the_profiles_rule(
    profile, gpu, seed,
):
    score, rule = PROFILES[profile]
    label = metrics.solves_by_resource_score
    before = label.value(score=rule.label())
    device, sched = run(score, True, gpu, seed)
    assert sched.solver_configs() == [rule] and sched.solver_config == rule
    assert sched.pods_fallback == 0 and sched.batches_solved >= 1
    assert label.value(score=rule.label()) - before == sched.batches_solved
    host, _ = run(score, False, gpu, seed)
    assert all(device.values()) and device == host
    if profile == "most":
        # and not as the default rule would have placed them, which is
        # what the device scored under every profile until PR 43 (a
        # second weight on LeastAllocated moves some seeds' pods only)
        assert device != run({}, False, gpu, seed)[0]


def test_a_profile_with_an_unmodelled_resource_scorer_takes_the_host_path():
    """RequestedToCapacityRatio is scored by the framework's own plugin
    on the host path, never as something else on the device: every pod
    is counted in ``pods_fallback`` and no batch is solved."""
    device, sched = run(RTCR, True, True, 43)
    assert sched.solver_configs() == [GreedyConfig()]  # nothing to warm
    assert sched._host_scored_profiles == {"default-scheduler"}
    assert sched.pods_fallback == PODS and sched.batches_solved == 0
    host, _ = run(RTCR, False, True, 43)
    assert all(device.values()) and device == host


def test_a_drivers_override_holds_for_every_profile():
    """``new_scheduler(solver_config=...)`` and a later assignment both
    override what the profiles say (``benchmarks/runner.py``'s
    ``solver:`` rows; the benchmark's broken twins)."""
    from kubernetes_tpu.scheduler.scheduler import new_scheduler

    server = APIServer()
    packed = GreedyConfig(0, 0, 1)
    sched = new_scheduler(
        Client(server), InformerFactory(server), batch=True,
        solver_config=packed,
    )
    assert sched.solver_config == packed
    assert sched.solver_configs() == [packed]
    sched.solver_config = None  # back to the profile's own
    assert sched.solver_config == GreedyConfig()
    sched.solver_config = packed
    assert sched._profile_rules == {"default-scheduler": packed}


def test_the_rules_label():
    assert GreedyConfig().label() == "least+balanced"
    assert GreedyConfig(0, 0, 1).label() == "most"
    assert GreedyConfig(2, 1, 0).label() == "leastx2+balanced"
    assert GreedyConfig(0, 0, 0).label() == "none"
    assert GreedyConfig.from_score_weights(
        {"RequestedToCapacityRatio": 1, LEAST: 1}) is None
    assert GreedyConfig.from_score_weights({MOST: 3}) == GreedyConfig(0, 0, 3)
