"""``waves``' closed waves on a cluster of Services whose pods carry a
chart's soft anti-affinity: the same create, drain, record, delete,
collect, with the three things that generator cannot be told.

**The Services, their ReplicaSets and the residents.** Before warm-up
the configuration's ``services`` are created through the API
(``run.client.create``): a Service ``svc-<k>`` and a ReplicaSet of the
same name, both selecting ``app=svc-<k>``; then the residents, created
bound as the ballast is and never deleted: ``residents`` pods in the
Zipf shares ``services_reference.zipf_shares`` gives, each service's on
distinct nodes, every node holding the same number to within one
(``services_reference.resident_nodes``). The harness builds its cluster
without any of them.

**Each pod's service.** A pod is the harness's own (``run.make_pods`` of
the mix's class) and is then given what the chart gives a replica: the
label ``app=svc-<k>``, a controller owner reference to its ReplicaSet,
and one preferred pod anti-affinity term of the configuration's weight
on its topology key to its own label. A wave is ``pods`` pods in the
same Zipf shares, put in another order by the run's seed: every seed
offers the same multiset.

**The precondition.** The first warm-up wave begins with one create that
holds a pod of every service: a batch of as many selector groups and as
many preferred terms as the cluster has services. A scheduler that
answers any of them on the host path (``pods_fallback`` moved) cannot
run this deployment, whose guarantee is that every batch is solved on
the device; the run ends there, exit code 2, and says so, before it
would spend a window finding out.

What it leaves on the ``Run`` for the cell's comparisons:
``run.service_of``, pod name -> service, and ``run.services``, the
deployment as this module made it.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import services_reference
from chipbench.harness import CREATE_CHUNK, BenchError


class Services:
    """The configuration's ``services`` block, and what was made of it."""

    def __init__(self, spec: dict) -> None:
        self.count = int(spec["count"])
        self.namespace = spec.get("namespace", "default")
        self.weight = int(spec["term_weight"])
        self.topology_key = spec["topology_key"]
        self.exponent = float(spec["zipf_exponent"])
        self.app_seed = int(spec["app_seed"])
        self.residents = int(spec["residents"])
        self.resident_class = spec["resident_class"]
        self.uids: dict = {}  # service -> its ReplicaSet's uid

    def shares(self, pods: int):
        return services_reference.zipf_shares(
            pods, self.count, self.exponent, self.app_seed
        )

    def create_objects(self, run, first: int = 0, count: int = None) -> None:
        """Service and ReplicaSet ``svc-<k>`` for ``count`` services
        from ``first`` on, through the API."""
        from kubernetes_tpu.api.types import (
            LabelSelector, ObjectMeta, ReplicaSet, Service,
        )

        count = self.count if count is None else count
        for k in range(first, first + count):
            name = f"svc-{k}"
            run.client.create(Service(
                metadata=ObjectMeta(name=name, namespace=self.namespace),
                selector={"app": name},
            ))
            made = run.client.create(ReplicaSet(
                metadata=ObjectMeta(name=name, namespace=self.namespace),
                selector=LabelSelector(match_labels={"app": name}),
            ))
            self.uids[k] = made.metadata.uid

    def decorate(self, run, pod, k: int) -> None:
        """What the chart gives a replica of service ``k``."""
        from kubernetes_tpu.api.types import (
            Affinity, LabelSelector, OwnerReference, PodAffinityTerm,
            PodAntiAffinity, WeightedPodAffinityTerm,
        )

        name = f"svc-{k}"
        pod.metadata.labels["app"] = name
        pod.metadata.owner_references = [OwnerReference(
            kind="ReplicaSet", name=name, uid=self.uids[k], controller=True,
        )]
        pod.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(
            preferred_during_scheduling=[WeightedPodAffinityTerm(
                weight=self.weight,
                pod_affinity_term=PodAffinityTerm(
                    label_selector=LabelSelector(match_labels={"app": name}),
                    topology_key=self.topology_key,
                ),
            )],
        ))
        run.service_of[pod.metadata.name] = k


def make_pods(run, cls: str, services, stem: str, selector: dict = None):
    """One pod of class ``cls`` for each entry of ``services``, named by
    the harness, then decorated as its service's replica."""
    pods = run.make_pods(cls, len(services), stem, selector=selector)
    for pod, k in zip(pods, services):
        run.services.decorate(run, pod, int(k))
    return pods


def wave_services(run, pods: int):
    """The services of a wave's pods: the Zipf shares, in the order the
    run's seed gives."""
    shares = run.services.shares(pods)
    services = np.repeat(np.arange(len(shares)), shares)
    return services[run.rng.permutation(len(services))]


def settle_residents(run, timeout_s: float) -> None:
    """Wait until the scheduler's own cache holds the residents: they
    reach it as the ballast does, by the informer."""
    held = run.sched.cache.pod_count
    deadline = time.perf_counter() + timeout_s
    target = len(run.created)  # every pod so far is bound and stays
    while held() < target:
        if time.perf_counter() > deadline:
            raise BenchError(
                f"the scheduler's cache holds {held()} of {target} pods "
                f"{timeout_s}s after the residents were created"
            )
        time.sleep(0.01)


def create_residents(run) -> None:
    svc = run.services
    start = run.now()
    with run.phase("residents"):
        svc.create_objects(run)
        shares = svc.shares(svc.residents)
        seats = services_reference.resident_nodes(
            shares, len(run.node_rows), svc.app_seed
        )
        node_of_row = {row: name for name, row in run.node_rows.items()}
        pods = []
        for k, rows in enumerate(seats):
            made = make_pods(
                run, svc.resident_class, [k] * len(rows), f"res{k}"
            )
            for pod, row in zip(made, rows.tolist()):
                pod.spec.node_name = node_of_row[row]
            pods += made
        run.prebound.update(p.metadata.name for p in pods)
        for i in range(0, len(pods), CREATE_CHUNK):
            run.server.create_bulk(pods[i:i + CREATE_CHUNK])
        settle_residents(run, run.config["setup_timeout_s"])
    print(f"rollout waves: {svc.count} Services and ReplicaSets, "
          f"{len(pods)} residents created bound (largest service "
          f"{int(shares.max())}, smallest {int(shares.min())}) in "
          f"{run.now() - start:.2f}s", flush=True)


def every_service_at_once(run, params: dict) -> None:
    """One create, a pod of every service, waited for and deleted; ends
    the run where the scheduler sent any of them to the host path."""
    count = run.services.count
    before = int(run.sched.pods_fallback)
    pods = make_pods(run, params["class"], range(count), "warmall")
    names = [p.metadata.name for p in pods]
    run.create(pods)
    # the first batch of the cluster's shape: its programs compile here
    run.wait_bound(names, run.config["setup_timeout_s"])
    run.sched.wait_for_inflight_binds(timeout=30)
    moved = int(run.sched.pods_fallback) - before
    if moved:
        raise BenchError(
            f"a batch of {count} pods of {count} services, on a cluster "
            f"whose residents carry their {count} preferred terms: "
            f"pods_fallback moved by {moved}. This scheduler cannot solve "
            "a batch of that many selector groups and preferred-affinity "
            "rows on the device, and the deployment's guarantee is that "
            "every batch is (exit code 2 is the precondition's, "
            "generators/rollout_waves.py)"
        )
    run.delete(names, params["delete_timeout_s"])


def one_wave(run, params: dict) -> None:
    """``waves.one_wave`` with this module's pods."""
    with run.phase("wave_build"):
        pods = make_pods(
            run, params["class"], wave_services(run, params["pods"]), "wave"
        )
        names = [p.metadata.name for p in pods]
    with run.phase("wave_create"):
        start = run.now()
        run.create(
            pods, due=start, threads=params["creators"],
            chunk=params["chunk"],
        )
    with run.phase("wave_drain"):
        left = params["deadline_s"] - (run.now() - start)
        run.wait_bound(names, left)
    wave = run.record_wave(start, names)
    with run.phase("gap_delete"):
        wave["snapshot"] = run.snapshot()
        run.delete(names, params["delete_timeout_s"])
        gc.collect()  # the harness's own garbage, as ``waves`` does


def warmup(run, params: dict) -> None:
    run.service_of = {}
    run.services = Services(run.config["services"])
    create_residents(run)
    every_service_at_once(run, params)
    for _ in range(params["warmup_waves"]):
        one_wave(run, params)


def prepare(run, params: dict, seconds: float):
    return None


def window(run, params: dict, prepared, seconds: float) -> None:
    """Waves until the window closes; the wave in flight at the end is
    finished and counted."""
    start = run.now()
    while run.now() - start < seconds:
        one_wave(run, params)
