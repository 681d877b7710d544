"""Speculative-chain tier-1 guard (ISSUE 18).

The pipelined dispatcher launches batch N+1's solve against the
post-N EXPECTED carry (the committer's shadow) while batch N is still
committing. This suite pins the whole contract:

- a steady 1k-pod burst with in-flight speculation places every pod
  IDENTICALLY to the sequential oracle (batch=False scheduler) with
  ``carry_divergences == 0`` -- the expectation was never wrong;
- under a one-bind-conflict chaos profile, all pods still bind, the
  rewind ledger (``speculative_rewinds``) stays bounded, and the
  uid-keyed watch-history replay proves exactly-once binds per
  incarnation (zero double-binds);
- a cluster of pods small enough to fit int16 still rides the int32
  resident carry and matches the oracle.
"""

import random
import time

import numpy as np

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.robustness.faults import (
    FaultInjector,
    FaultPoint,
    FaultProfile,
    PointConfig,
    install_injector,
)
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics

import pytest


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    install_injector(None)


class _KeepFirstRng:
    def randrange(self, n):
        return 1 if n > 1 else 0

    def randint(self, a, b):
        return b


def _pods(num, seed, cpu_choices=(100, 200, 250), mem_choices=(128, 256)):
    rng = random.Random(seed)
    out = []
    for i in range(num):
        out.append(
            make_pod(f"s{i}")
            .creation_timestamp(float(i))
            .container(
                cpu=f"{rng.choice(cpu_choices)}m",
                memory=f"{rng.choice(mem_choices)}Mi",
            )
            .obj()
        )
    return out


def _run(
    pods,
    *,
    batch,
    nodes=16,
    node_cpu="64",
    node_mem="256Gi",
    max_batch=128,
    chunk=128,
    timeout=120.0,
    slow_commit=0.0,
):
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=batch, max_batch=max_batch,
        rng=_KeepFirstRng(),
    )
    if batch and slow_commit:
        # hold each commit on the committer thread long enough that the
        # dispatcher provably gets ahead: the next solves launch against
        # the shadow expectation while batches are still committing.
        # Purely a scheduling-pressure knob -- the commit itself is
        # untouched, so correctness must hold with REAL speculation.
        orig_complete = sched._complete_solve

        def _held(p, _orig=orig_complete):
            time.sleep(slow_commit)
            _orig(p)

        sched._complete_solve = _held
    for i in range(nodes):
        client.create_node(
            make_node(f"g{i}")
            .capacity(cpu=node_cpu, memory=node_mem, pods=200)
            .obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    sched.start()
    # chunked creates so several batches are in flight concurrently
    # (one bulk create of everything would drain as one giant batch)
    for lo in range(0, len(pods), chunk):
        client.create_pods_bulk(pods[lo:lo + chunk])
    deadline = time.time() + timeout
    while time.time() < deadline:
        ps, _ = client.list_pods()
        if sum(1 for p in ps if p.spec.node_name) >= len(pods):
            break
        time.sleep(0.05)
    sched.wait_for_inflight_binds()
    placements = {
        p.metadata.name: p.spec.node_name
        for p in client.list_pods()[0]
    }
    sched.stop()
    informers.stop()
    return placements, sched, server


def test_speculative_burst_matches_sequential_oracle():
    """1k pods, max_batch small enough that the burst spans many
    batches with in-flight speculation: every pod places exactly where
    the sequential oracle puts it, and the speculative expectation was
    never wrong (zero carry divergences, zero drains)."""
    want, _o, _ = _run(_pods(1000, seed=42), batch=False)
    assert all(want.values()), "oracle failed to place a fitting pod"

    got, sched, _ = _run(
        _pods(1000, seed=42), batch=True, max_batch=128,
        slow_commit=0.03,
    )
    assert got == want
    assert sched.pods_fallback == 0
    assert sched.pods_solved_on_device == 1000
    assert sched.carry_divergences == 0, (
        "speculative shadow expectation diverged on a conflict-free run"
    )
    # the pipeline actually pipelined: overlapping launches were counted
    assert sched.speculative_launches > 0, (
        "no solve ever launched with a batch still committing -- the "
        "burst ran serially"
    )
    assert sched.speculative_rewinds == 0


def test_one_bind_conflict_bounded_rewinds_exactly_once_binds():
    """One injected bind conflict mid-burst: every pod still binds, the
    rewind ledger stays bounded (the divergence re-solves ONE batch, it
    does not cascade), and the uid-keyed watch-history replay shows
    exactly-once binds per incarnation -- no double-bind ever reaches
    the apiserver."""
    install_injector(FaultInjector(FaultProfile(
        "spec-one-conflict", seed=0,
        points={
            FaultPoint.BIND_CONFLICT: PointConfig(rate=1.0, max_fires=1),
        },
    )))
    fired_before = metrics.faults_injected.value(
        point=FaultPoint.BIND_CONFLICT
    )
    pods = _pods(600, seed=7)
    placements, sched, server = _run(
        pods, batch=True, max_batch=64, slow_commit=0.03,
    )

    assert all(placements.values()), (
        f"unbound after conflict: "
        f"{[k for k, v in placements.items() if not v][:5]}"
    )
    assert metrics.faults_injected.value(
        point=FaultPoint.BIND_CONFLICT
    ) > fired_before, "the conflict never fired"
    # bounded: a single conflict rewinds at most the in-flight window,
    # not the whole burst
    assert sched.speculative_rewinds <= sched.max_inflight + 2, (
        f"rewind cascade: {sched.speculative_rewinds} rewinds from one "
        f"injected conflict"
    )

    # uid-keyed watch-history replay: per incarnation, the node_name is
    # written exactly once and never rewritten to a different node
    bind_count = {}
    for ev in server._history["Pod"]:
        uid = ev.object.metadata.uid
        node = ev.object.spec.node_name
        if not node:
            continue
        prev = bind_count.get(uid)
        if prev is None:
            bind_count[uid] = (node, 1)
        elif prev[0] != node:
            raise AssertionError(
                f"uid {uid} double-bound: {prev[0]} -> {node}"
            )
    assert len(bind_count) == len(pods)


def test_small_pod_carry_is_int32_and_matches_oracle():
    """Pods small enough that a whole batch sums under 32,767 KiB (24 Mi
    nodes, 512 Ki - 1 Mi pods, batches of 16) still ride the int32
    resident carry, and land where the sequential oracle puts them."""
    rng = random.Random(11)
    specs = [
        (rng.choice([50, 100, 150]), rng.choice([512, 1024]))
        for _ in range(48)
    ]

    def mk():
        return [
            make_pod(f"c{i}")
            .creation_timestamp(float(i))
            .container(cpu=f"{cpu}m", memory=f"{mem}Ki")
            .obj()
            for i, (cpu, mem) in enumerate(specs)
        ]

    cluster = dict(nodes=40, node_cpu="4", node_mem="24Mi")
    want, _o, _ = _run(mk(), batch=False, **cluster)
    assert all(want.values())
    got, sched, _ = _run(
        mk(), batch=True, max_batch=16, chunk=16, slow_commit=0.01,
        **cluster,
    )
    assert got == want
    assert sched.carry_divergences == 0
    assert sched.pods_fallback == 0
    ds = sched.device_state
    assert ds.req_dev.dtype == np.int32
    assert ds.nzr_dev.dtype == np.int32
