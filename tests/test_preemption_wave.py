"""Batched device preemption waves (PR 11): the wave solver ladder
(pallas tier -> jnp twin -> host-oracle floor), the shared
DisruptionController PDB gate with refund-on-deny, nominatedNodeName
end-to-end semantics, drain-via-preemption, and the preemption-chaos
profile.

Covers the ISSUE-11 satellites:
- randomized differential: the device wave (one kernel round trip with
  the in-scan nomination carry) vs the sequential HOST oracle folding
  nominations through the queue (_add_nominated_pods) -- placements and
  victim sets equal per seed, with and without PDB budgets, with
  pre-existing nominated pods;
- tier-1 guard: a saturated 1k-pod burst with a high-priority tail --
  every high-band pod binds, zero PDB overspend (the budget is never
  driven negative in the full watch history), and the device carry
  stays warm across the wave (state_uploads <= 1 after victims commit);
- preemption-chaos e2e: wave-solve faults + a bind-conflict burst +
  slow-dying victims; the storm still binds 100% of the high band with
  exactly-once binds per pod incarnation;
- drain-via-preemption: strictly fewer evictions than the whole-node
  baseline, paced by the same budget;
- metrics book what actually happened (an aborted wave books nothing).
"""

import random
import threading
import time

import pytest

from kubernetes_tpu.api.types import LabelSelector, PodDisruptionBudget
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.controllers import DisruptionController, NodeDrainer
from kubernetes_tpu.framework.interface import CycleState, FitError
from kubernetes_tpu.framework.runtime import Framework
from kubernetes_tpu.plugins import new_in_tree_registry
from kubernetes_tpu.queue.scheduling_queue import PriorityQueue
from kubernetes_tpu.robustness.faults import (
    FaultInjector,
    FaultPoint,
    FaultProfile,
    PointConfig,
    builtin_profiles,
    install_injector,
    load_profile,
)
from kubernetes_tpu.robustness.lifecycle import PodRespawner
from kubernetes_tpu.scheduler.generic import GenericScheduler
from kubernetes_tpu.scheduler.preemption import Preemptor
from kubernetes_tpu.scheduler.provider import default_plugins
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    install_injector(None)


# -- harness ---------------------------------------------------------------


def _env(pods, nodes):
    cache = SchedulerCache()
    for n in nodes:
        cache.add_node(n)
    for p in pods:
        cache.add_pod(p)
    snapshot = Snapshot()
    cache.update_snapshot(snapshot)
    algorithm = GenericScheduler(cache, snapshot)
    fw = Framework(
        new_in_tree_registry(),
        default_plugins(),
        snapshot_provider=lambda: snapshot,
    )
    return algorithm, fw


def _fail(algorithm, fw, pod):
    state = CycleState()
    with pytest.raises(FitError) as exc:
        algorithm.schedule(fw, state, pod)
    return exc.value


def _queue(fw):
    return PriorityQueue(
        fw.queue_sort_less_func(), sort_key_func=fw.queue_sort_key_func()
    )


def _random_cluster(rng, with_pdbs):
    nodes = []
    for i in range(12):
        w = make_node(f"n{i}").capacity(
            cpu=str(rng.choice([2, 4, 8])), memory="16Gi", pods=32
        )
        if rng.random() < 0.2:
            w.label("disk", "ssd")
        if rng.random() < 0.15:
            w.taint("dedicated", "infra")
        nodes.append(w.obj())
    pods = []
    t0 = time.time() - 10_000
    # near-fill every node so the wave always needs victims
    for i, n in enumerate(nodes):
        cap_milli = n.status.allocatable["cpu"]
        p = (
            make_pod(f"fill{i}")
            .node(n.metadata.name)
            # leave <1000m free so every wave pod (>=1000m) must preempt
            .container(cpu=f"{cap_milli - 500}m", memory="8Gi")
            .labels(app=rng.choice(["a", "b", "c"]))
            .priority(rng.choice([0, 5]))
            .obj()
        )
        p.status.start_time = t0 + rng.randrange(10_000)
        pods.append(p)
    for j in range(30):
        node = f"n{rng.randrange(12)}"
        p = (
            make_pod(f"p{j}")
            .node(node)
            .container(
                cpu=f"{rng.choice([250, 500, 1000, 2000])}m",
                memory=f"{rng.choice([128, 512, 1024])}Mi",
            )
            .labels(app=rng.choice(["a", "b", "c"]))
            .priority(rng.choice([0, 0, 5, 10, 50]))
            .obj()
        )
        p.status.start_time = t0 + rng.randrange(10_000)
        pods.append(p)
    pdbs = []
    if with_pdbs:
        for app, budget in (("a", 1), ("b", 0)):
            pdbs.append(
                PodDisruptionBudget(
                    selector=LabelSelector(match_labels={"app": app}),
                )
            )
            pdbs[-1].status.disruptions_allowed = budget
            pdbs[-1].metadata.name = f"pdb-{app}"
            pdbs[-1].metadata.namespace = "default"
    return nodes, pods, pdbs


def _bind_transitions_by_uid(server):
    """unbound->bound transitions per pod INCARNATION (uid), replayed
    from the full watch history (the PR-6/PR-8 exactly-once harness)."""
    w = server.watch("Pod", since_rv=0)
    node = {}
    transitions = {}
    for ev in w.pending():
        pod = ev.object
        uid = pod.metadata.uid
        if ev.type == "DELETED":
            node.pop(uid, None)
            continue
        prev = node.get(uid, "")
        cur = pod.spec.node_name or ""
        if not prev and cur:
            transitions[uid] = transitions.get(uid, 0) + 1
        node[uid] = cur
    w.stop()
    return transitions


def _pdb_never_negative(server):
    """Replay the FULL PodDisruptionBudget watch history: the
    zero-overspend pin. Every status write the shared can_disrupt gate
    (and the reconcile loop) ever made must leave disruptionsAllowed
    >= 0 -- a negative value is a budget spent past zero."""
    w = server.watch("PodDisruptionBudget", since_rv=0)
    floor = 0
    for ev in w.pending():
        if ev.type == "DELETED":
            continue
        floor = min(floor, ev.object.status.disruptions_allowed)
    w.stop()
    return floor >= 0


# -- profile + config registration ----------------------------------------


def test_preemption_chaos_profile_registered():
    profiles = builtin_profiles()
    assert "preemption-chaos" in profiles
    p = profiles["preemption-chaos"]
    assert FaultPoint.PREEMPT_SOLVE in p.points
    assert FaultPoint.BIND_CONFLICT in p.points
    assert FaultPoint.VICTIM_SLOW_DEATH in p.points
    # slow death needs a grace: the delayed delete must actually land
    assert p.points[FaultPoint.VICTIM_SLOW_DEATH].hang_seconds > 0
    # every point heals: bounded fires so a chaos run converges
    assert all(c.max_fires is not None for c in p.points.values())
    assert load_profile("preemption-chaos", seed=7).seed == 7


def test_preemption_chaos_profile_validates_in_config():
    from kubernetes_tpu.config.loader import load_config_from_dict
    from kubernetes_tpu.config.validation import validate_config

    cfg = load_config_from_dict(
        {
            "faultInjection": {
                "enabled": True,
                "profile": "preemption-chaos",
                "seed": 3,
            }
        }
    )
    assert validate_config(cfg) == []
    bad = load_config_from_dict(
        {
            "faultInjection": {
                "enabled": True,
                "profile": "preemption-chaos-typo",
            }
        }
    )
    errs = validate_config(bad)
    assert any("preemption-chaos-typo" in e for e in errs)


# -- randomized differential: wave kernel vs host oracle -------------------


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("with_pdbs", [False, True])
def test_wave_matches_host_oracle(seed, with_pdbs):
    """The whole WAVE -- priority-desc failed-pod group, in-scan
    nomination carry, pre-existing nominated pods -- against the
    sequential host oracle folding every nomination through the queue.
    Placement and victim sets must be equal per pod."""
    rng = random.Random(seed)
    nodes, pods, pdbs = _random_cluster(rng, with_pdbs)
    algorithm, fw = _env(pods, nodes)

    # pre-existing nominations: two pending pods virtually occupying
    # capacity (one big enough to matter, one tiny)
    nominated = []
    for i, (cpu, prio) in enumerate((("1", 90), ("250m", 60))):
        np_ = (
            make_pod(f"nom{i}")
            .container(cpu=cpu, memory="256Mi")
            .priority(prio)
            .obj()
        )
        nominated.append((np_, f"n{rng.randrange(12)}"))

    # the wave: priority-desc failed pods of mixed shapes
    wave = []
    for j in range(6):
        wave.append(
            make_pod(f"wave{j}")
            .container(
                cpu=f"{rng.choice([1000, 1500, 2000])}m",
                memory=f"{rng.choice([512, 1024])}Mi",
            )
            .priority(rng.choice([100, 80, 80, 40]))
            .obj()
        )
    wave.sort(key=lambda p: -p.spec.priority)
    items = [(p, _fail(algorithm, fw, p)) for p in wave]

    # -- device wave ------------------------------------------------------
    queue_dev = _queue(fw)
    for np_, node in nominated:
        queue_dev.update_nominated_pod_for_node(np_, node)
    dev = Preemptor(algorithm, queue_dev, None)
    pot_cache = {}
    pot_list = []
    for p, fe in items:
        key = id(fe.filtered_nodes_statuses)
        if key not in pot_cache:
            pot_cache[key] = dev.nodes_where_preemption_might_help(fe)
        pot_list.append(pot_cache[key])
    answers, tier = dev._device_answers(
        [p for p, _ in items], pot_list, pdbs
    )
    assert tier in ("pallas", "xla")

    # -- host oracle with the queue nomination fold -----------------------
    queue_host = _queue(fw)
    for np_, node in nominated:
        queue_host.update_nominated_pod_for_node(np_, node)
    algorithm.nominated_pods_lister = queue_host
    try:
        host = Preemptor(algorithm, queue_host, None)
        expected = host._host_wave_answers(fw, items, pdbs)
    finally:
        algorithm.nominated_pods_lister = None

    for k, ((dn, dv, _), (hn, hv, _)) in enumerate(zip(answers, expected)):
        assert dn == hn, f"pod {k}: device {dn!r} != host {hn!r}"
        assert {p.metadata.name for p in dv} == {
            p.metadata.name for p in hv
        }, f"pod {k}: victim sets differ on {dn}"


def test_wave_breaker_falls_back_to_jnp_twin():
    """A faulted wave solve charges the tier's breaker and the SAME
    dispatch completes on the next tier; with every device tier down the
    host-oracle floor still answers (and books the host tier)."""
    rng = random.Random(5)
    nodes, pods, pdbs = _random_cluster(rng, False)
    algorithm, fw = _env(pods, nodes)
    queue = _queue(fw)
    algorithm.nominated_pods_lister = queue
    try:
        pre = Preemptor(algorithm, queue, None)
        wave = [
            make_pod(f"w{j}").container(cpu="1500m", memory="512Mi")
            .priority(100).obj()
            for j in range(3)
        ]
        items = [(p, _fail(algorithm, fw, p)) for p in wave]
        pots = [pre.nodes_where_preemption_might_help(items[0][1])] * 3

        # fault EVERY device attempt: on CPU only the jnp twin is
        # offered, so the ladder exhausts and the floor answers
        install_injector(FaultInjector(FaultProfile(
            name="wave-down", seed=0,
            points={FaultPoint.PREEMPT_SOLVE: PointConfig(rate=1.0)},
        )))
        from kubernetes_tpu.robustness.ladder import LadderExhausted

        with pytest.raises(LadderExhausted):
            pre._device_answers([p for p, _ in items], pots, pdbs)
        # the wave driver's floor: host answers with the queue fold
        answers = pre._host_wave_answers(fw, items, pdbs)
        assert any(node for node, _, _ in answers)

        # faults healed: the twin answers again (one ladder failure is
        # below the default breaker threshold of 3, so the tier stayed
        # closed) and agrees with the host floor
        install_injector(None)
        answers2, tier2 = pre._device_answers(
            [p for p, _ in items], pots, pdbs
        )
        assert tier2 in ("pallas", "xla")
        assert [a[0] for a in answers] == [a[0] for a in answers2]
    finally:
        algorithm.nominated_pods_lister = None


# -- metrics book what actually happened -----------------------------------


class _StubProf:
    def get_waiting_pod(self, uid):
        return None

    recorder = None


def test_aborted_wave_books_no_victims(monkeypatch):
    """An eviction transaction that fails books NOTHING: no victim
    counters, budget refunded, None sentinel so callers requeue with
    backoff (the PR-5 count-what-actually-happened rule)."""
    rng = random.Random(11)
    nodes, pods, _ = _random_cluster(rng, False)

    server = APIServer()
    client = Client(server)
    for n in nodes:
        client.create_node(n)
    for p in pods:
        client.create_pod(p)
    informers = InformerFactory(server)
    algorithm, fw = _env(pods, nodes)
    queue = _queue(fw)
    dc = DisruptionController(client, informers)
    pdb = PodDisruptionBudget(
        selector=LabelSelector(match_labels={"app": "a"}),
        max_unavailable=50,
    )
    pdb.metadata.name = "budget"
    pdb.metadata.namespace = "default"
    client.create_pdb(pdb)
    informers.start()
    informers.wait_for_cache_sync()
    dc.sync_all()
    budget0 = client.list_pdbs()[0][0].status.disruptions_allowed
    assert budget0 > 0

    pre = Preemptor(algorithm, queue, client, disruption=dc)
    wave = [
        make_pod(f"w{j}").container(cpu="1500m", memory="512Mi")
        .priority(100).obj()
        for j in range(2)
    ]
    for p in wave:
        client.create_pod(p)
    items = [(p, _fail(algorithm, fw, p)) for p in wave]

    def boom(keys, missing_out=None):
        raise RuntimeError("api down")

    monkeypatch.setattr(client, "delete_pods_bulk", boom)
    v0 = dict(pre.victims_by_tier)
    selected0 = metrics.victims_selected.value(tier="xla")
    results, uids = pre.preempt_batch(_StubProf(), items)
    assert uids is None  # transaction failed: backoff sentinel
    assert pre.victims_by_tier == v0  # nothing booked
    assert metrics.victims_selected.value(tier="xla") == selected0
    # every grant refunded: the budget is exactly where it started
    assert (
        client.list_pdbs()[0][0].status.disruptions_allowed == budget0
    )
    informers.stop()


def test_budget_deny_refunds_and_skips_nomination():
    """A zero-budget PDB over every victim: the wave selects victims but
    the shared gate denies the spend -- no nomination, no eviction, the
    denial counted, sibling-PDB grants refunded, and the budget never
    negative."""
    server = APIServer()
    client = Client(server)
    nodes = [
        make_node(f"n{i}").capacity(cpu="2", memory="8Gi", pods=10).obj()
        for i in range(3)
    ]
    pods = []
    for i, n in enumerate(nodes):
        p = (
            make_pod(f"fill{i}").node(n.metadata.name)
            .container(cpu="2", memory="1Gi")
            .labels(app="guarded").priority(0).obj()
        )
        p.status.start_time = time.time() - 100
        pods.append(p)
    for n in nodes:
        client.create_node(n)
    for p in pods:
        client.create_pod(p)
    informers = InformerFactory(server)
    algorithm, fw = _env(pods, nodes)
    queue = _queue(fw)
    dc = DisruptionController(client, informers)
    pdb = PodDisruptionBudget(
        selector=LabelSelector(match_labels={"app": "guarded"}),
        min_available=3,  # every pod protected: zero budget
    )
    pdb.metadata.name = "frozen"
    pdb.metadata.namespace = "default"
    client.create_pdb(pdb)
    informers.start()
    informers.wait_for_cache_sync()
    dc.sync_all()
    assert client.list_pdbs()[0][0].status.disruptions_allowed == 0

    pre = Preemptor(algorithm, queue, client, disruption=dc)
    high = make_pod("high").container(cpu="1").priority(100).obj()
    client.create_pod(high)
    fe = _fail(algorithm, fw, high)
    denials0 = pre.budget_denials
    # the kernel models the zero budget (victims go violating-first,
    # reference last-resort semantics) and still proposes a node; the
    # shared gate is the last line of defense that actually refuses to
    # spend past zero -- nomination and eviction must both be dropped
    results, uids = pre.preempt_batch(_StubProf(), [(high, fe)])
    assert results == [""]  # no nomination survived the deny
    assert uids == []
    assert pre.budget_denials == denials0 + 1
    assert queue.nominated_pods_for_node("n0") == []
    # nothing evicted, budget intact and never negative
    assert len(client.list_pods()[0]) == 4
    assert client.list_pdbs()[0][0].status.disruptions_allowed == 0
    assert _pdb_never_negative(server)
    informers.stop()


# -- nominatedNodeName end-to-end ------------------------------------------


def test_nominations_cleared_on_node_delete():
    """Deleting the nominated node clears the nomination (the queue map
    stops reserving phantom capacity) and re-arms the nominee."""
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=16)
    for i in range(2):
        client.create_node(
            make_node(f"n{i}").capacity(cpu="2", memory="8Gi").obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    pend = make_pod("pend").container(cpu="1").priority(50).obj()
    client.create_pod(pend)
    # park it with a nomination (as a wave would)
    deadline = time.time() + 10
    while sched.queue.active_count() == 0 and time.time() < deadline:
        time.sleep(0.01)
    cleared0 = metrics.nominations_cleared.value()
    sched.queue.update_nominated_pod_for_node(pend, "n1")
    # the API-side status write a wave's record_scheduling_failure makes
    def set_nom(p):
        p.status.nominated_node_name = "n1"

    client.update_pod_status("default", "pend", set_nom)
    assert [p.metadata.name for p in sched.queue.nominated_pods_for_node("n1")]
    client.delete_node("n1")
    deadline = time.time() + 10
    while (
        sched.queue.nominated_pods_for_node("n1")
        and time.time() < deadline
    ):
        time.sleep(0.01)
    assert sched.queue.nominated_pods_for_node("n1") == []
    assert metrics.nominations_cleared.value() >= cleared0 + 1
    # the API status cleared too -- otherwise the queue map re-installs
    # the phantom reservation from status on the next update echo
    deadline = time.time() + 10
    while (
        client.get_pod("default", "pend").status.nominated_node_name
        and time.time() < deadline
    ):
        time.sleep(0.01)
    assert client.get_pod("default", "pend").status.nominated_node_name == ""
    # poke an update through the informer: the re-add must NOT resurrect
    client.update_pod_status("default", "pend", lambda p: None)
    deadline = time.time() + 2
    while time.time() < deadline:
        if sched.queue.nominated_pods_for_node("n1"):
            break
        time.sleep(0.01)
    assert sched.queue.nominated_pods_for_node("n1") == []
    sched.stop()
    informers.stop()


# -- tier-1 guard: saturated burst + high-priority tail --------------------


def _e2e(num_nodes, node_cpu, pods_cap=32, max_batch=256):
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=max_batch)
    for i in range(num_nodes):
        client.create_node(
            make_node(f"n{i}")
            .capacity(cpu=node_cpu, memory="64Gi", pods=pods_cap)
            .obj()
        )
    return server, client, informers, sched


def _wait_named_bound(client, names, deadline_s):
    deadline = time.time() + deadline_s
    names = set(names)
    while time.time() < deadline:
        pods, _ = client.list_pods()
        bound = {
            p.metadata.name
            for p in pods
            if p.metadata.name in names and p.spec.node_name
        }
        if bound == names:
            return True
        time.sleep(0.05)
    return False


def test_high_priority_tail_guard():
    """Tier-1 guard: 1k low-priority pods saturate the cluster; a
    40-pod high-priority tail must ALL bind via the batched wave, with
    zero PDB overspend (full watch-history pin), no budget denials
    (ample budget), and the device carry warm across the wave
    (state_uploads <= 1 after the victims commit)."""
    server, client, informers, sched = _e2e(50, "20", pods_cap=40)
    dc = DisruptionController(client, informers)
    sched.preemptor.disruption = dc
    pdb = PodDisruptionBudget(
        selector=LabelSelector(match_labels={"app": "low"}),
        max_unavailable=80,
    )
    pdb.metadata.name = "tail-budget"
    pdb.metadata.namespace = "default"
    client.create_pdb(pdb)
    informers.start()
    informers.wait_for_cache_sync()
    dc.start()
    sched.queue.run()
    try:
        low_names = [f"low-{i}" for i in range(1000)]
        for nm in low_names:
            client.create_pod(
                make_pod(nm).container(cpu="1", memory="128Mi")
                .labels(app="low").priority(0).obj()
            )
        sched.start()
        assert _wait_named_bound(client, low_names, 120), (
            "saturating burst never fully bound"
        )
        sched.wait_for_inflight_binds(timeout=60)

        uploads0 = sched.state_uploads
        denials0 = sched.preemptor.budget_denials
        blocked0 = metrics.evictions_blocked_by_pdb.value()

        high_names = [f"high-{i}" for i in range(40)]
        for nm in high_names:
            client.create_pod(
                make_pod(nm).container(cpu="1", memory="128Mi")
                .priority(100).obj()
            )
        assert _wait_named_bound(client, high_names, 120), (
            "high-priority tail did not fully bind"
        )
        sched.wait_for_inflight_binds(timeout=60)

        # the wave ran on device and booked its victims by tier
        assert sched.preemptor.waves >= 1
        assert sum(sched.preemptor.victims_by_tier.values()) >= 40
        # budget consistency: ample budget => zero denials, zero blocks,
        # and the full watch history never shows a negative budget
        assert sched.preemptor.budget_denials == denials0
        assert metrics.evictions_blocked_by_pdb.value() == blocked0
        assert _pdb_never_negative(server)
        # warm carry: victims ride the delta scatter, never a repack
        assert sched.state_uploads - uploads0 <= 1, (
            f"preemption wave forced {sched.state_uploads - uploads0} "
            "state uploads"
        )
        # exactly-once binds per incarnation over the whole run
        transitions = _bind_transitions_by_uid(server)
        doubles = {u: c for u, c in transitions.items() if c > 1}
        assert not doubles, f"double-bound incarnations: {doubles}"
    finally:
        sched.stop()
        dc.stop()
        informers.stop()


# -- a wave hands its preemptors back as one retry batch ---------------------


@pytest.mark.parametrize("echoes", ["before", "during", "after"])
def test_wave_hands_its_preemptors_back_as_one_retry_batch(echoes):
    """A full cluster and N preemptors in one burst: one failing batch,
    ONE program wave that searches each preemptor once and evicts N
    residents, ONE retry batch that binds every preemptor exactly once.
    The watch's echoes of the wave's status writes reach the queue
    ``before`` the dispatcher pops the retry batch (the pods are in the
    activeQ), ``after`` it (the scheduler holds them: the queue must add
    no second record) or as the threads happen to run (``during``)."""
    n = 16
    server, client, informers, sched = _e2e(8, "4", max_batch=256)
    pods_informer = informers.pods()
    echo_gate = threading.Event()   # set: status echoes may be ingested
    pop_gate = threading.Event()    # set: the dispatcher may pop
    first_pop = threading.Event()   # the burst's failing batch was popped
    retry_pop = threading.Event()   # the retry batch was popped
    pop_gate.set()
    if echoes != "after":
        echo_gate.set()

    apply_batch = pods_informer._apply_batch

    def gated_apply(evs):
        if any(
            ev.type == "MODIFIED"
            and ev.object.metadata.name.startswith("high-")
            and not ev.object.spec.node_name
            for ev in evs
        ):
            assert echo_gate.wait(60), "the echoes were never released"
        apply_batch(evs)

    pods_informer._apply_batch = gated_apply
    pop_batch = sched.queue.pop_batch

    def gated_pop(*args, **kwargs):
        assert pop_gate.wait(60), "the dispatcher was never released"
        batch = pop_batch(*args, **kwargs)
        if any(pi.pod.metadata.name.startswith("high-") for pi in batch):
            if not first_pop.is_set():
                first_pop.set()
                if echoes == "before":
                    pop_gate.clear()  # hold the pop of the retry batch
            else:
                retry_pop.set()
        return batch

    sched.queue.pop_batch = gated_pop
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    try:
        low_names = [f"low-{i}" for i in range(32)]
        client.create_pods_bulk([
            make_pod(nm).container(cpu="1", memory="128Mi")
            .priority(0).obj()
            for nm in low_names
        ])
        sched.start()
        assert _wait_named_bound(client, low_names, 120), (
            "the residents never filled the cluster"
        )
        sched.wait_for_inflight_binds(timeout=60)
        batches0 = sched.batches_solved
        assert sched.preemptor.waves == 0

        high_names = [f"high-{i}" for i in range(n)]
        client.create_pods_bulk([
            make_pod(nm).container(cpu="1", memory="128Mi")
            .priority(100).obj()
            for nm in high_names
        ])
        if echoes == "before":
            # the echoes land while the retry batch waits in the activeQ
            deadline = time.time() + 60
            while not all(
                (p := pods_informer.get("default", nm)) is not None
                and p.status.conditions
                for nm in high_names
            ):
                assert time.time() < deadline, "echoes never ingested"
                time.sleep(0.01)
            assert not retry_pop.is_set()
            pop_gate.set()
        elif echoes == "after":
            # ... or only once the scheduler holds every preemptor
            assert retry_pop.wait(60), "the retry batch was never popped"
            echo_gate.set()
        assert _wait_named_bound(client, high_names, 120), (
            "the preemptors did not all bind"
        )
        sched.wait_for_inflight_binds(timeout=60)
        # let a second record, if the queue made one, fail and be parked
        time.sleep(0.5)

        pre = sched.preemptor
        assert pre.waves == 1
        assert pre.device_preemptions == n and pre.host_preemptions == 0
        assert pre.searched_again == {}
        assert sum(pre.ladder.solves_by_tier.values()) == 1
        assert sum(pre.victims_by_tier.values()) == n
        # the failing batch and ONE retry batch
        assert sched.batches_solved - batches0 == 2
        pods, _ = client.list_pods()
        left = {p.metadata.name for p in pods}
        assert len(set(low_names) - left) == n, "N victims and no more"
        assert set(high_names) <= left
        transitions = _bind_transitions_by_uid(server)
        assert all(
            transitions.get(p.metadata.uid) == 1
            for p in pods if p.metadata.name in high_names
        )
        assert sched.queue.num_pending() == {
            "active": 0, "backoff": 0, "unschedulable": 0,
        }
        for p in pods:
            if p.metadata.name in high_names:
                # the record is still written for every preemptor
                (cond,) = [
                    c for c in p.status.conditions
                    if c.type == "PodScheduled"
                ]
                assert (cond.status, cond.reason) == (
                    "False", "Unschedulable"
                )
                assert p.status.nominated_node_name == p.spec.node_name
        ignored = sched.queue.echoes_ignored
        if echoes == "before":
            assert ignored == 0
        elif echoes == "after":
            assert ignored == n
        else:
            assert 0 <= ignored <= n
    finally:
        pop_gate.set()
        echo_gate.set()
        sched.stop()
        informers.stop()


# -- preemption-chaos e2e --------------------------------------------------


def test_preemption_chaos_storm_e2e():
    """The acceptance e2e: a priority-inversion storm under
    preemption-chaos (wave-solve faults + a bind-conflict burst +
    slow-dying victims) binds 100% of the high band, with zero PDB
    overspend and exactly-once binds per pod incarnation."""
    # seed 10: the PREEMPT_SOLVE stream fires on its very first draw
    # (the first wave pays an in-place retry / twin fallback) and the
    # VICTIM_SLOW_DEATH stream fires within the storm's victim count
    injector = FaultInjector(load_profile("preemption-chaos", seed=10))
    install_injector(injector)
    server, client, informers, sched = _e2e(16, "4", pods_cap=12)
    dc = DisruptionController(client, informers)
    sched.preemptor.disruption = dc
    pdb = PodDisruptionBudget(
        selector=LabelSelector(match_labels={"app": "low"}),
        max_unavailable=60,
    )
    pdb.metadata.name = "storm-budget"
    pdb.metadata.namespace = "default"
    client.create_pdb(pdb)
    informers.start()
    informers.wait_for_cache_sync()
    dc.start()
    sched.queue.run()
    try:
        low_names = [f"low-{i}" for i in range(64)]
        for nm in low_names:
            client.create_pod(
                make_pod(nm).container(cpu="1", memory="128Mi")
                .labels(app="low").priority(0).obj()
            )
        sched.start()
        assert _wait_named_bound(client, low_names, 60)
        sched.wait_for_inflight_binds(timeout=60)

        # the inversion storm: a low-priority flood arrives WITH the
        # high band (the flood can never place -- the cluster is full
        # and it cannot preempt equals), so the high band must cut
        # through it via the wave
        high_names = [f"high-{i}" for i in range(24)]
        for i in range(24):
            client.create_pod(
                make_pod(f"noise-{i}").container(cpu="1", memory="128Mi")
                .labels(app="low").priority(0).obj()
            )
            client.create_pod(
                make_pod(high_names[i]).container(cpu="1", memory="128Mi")
                .priority(100).obj()
            )
        assert _wait_named_bound(client, high_names, 120), (
            "high band did not fully bind under preemption-chaos"
        )
        sched.wait_for_inflight_binds(timeout=60)

        # the chaos actually happened
        assert injector.fired_count(FaultPoint.PREEMPT_SOLVE) >= 1
        assert injector.fired_count(FaultPoint.VICTIM_SLOW_DEATH) >= 1
        assert sched.preemptor.waves >= 1
        assert sched.preemptor.victims_slow_death >= 1
        # zero PDB overspend across the full history
        assert _pdb_never_negative(server)
        # exactly-once binds per pod incarnation
        transitions = _bind_transitions_by_uid(server)
        doubles = {u: c for u, c in transitions.items() if c > 1}
        assert not doubles, f"double-bound incarnations: {doubles}"
    finally:
        sched.stop()
        dc.stop()
        informers.stop()


# -- drain-via-preemption --------------------------------------------------


def test_drain_via_preemption_evicts_strictly_fewer():
    """Drain a node whose residents only PARTIALLY fit elsewhere: the
    kernel-planned drain evicts exactly the placeable pods (strictly
    fewer than the whole-node baseline), leaves the rest RUNNING on the
    cordoned node, and paces every eviction through the shared PDB
    budget as replacements land."""
    server, client, informers, sched = _e2e(1, "8", pods_cap=20)
    # receivers: 3 cpu of spare capacity in total (plus the 100m the
    # snapshot-freshening warm pod pins onto r1)
    client.create_node(
        make_node("r1").capacity(cpu="2100m", memory="16Gi", pods=10)
        .label("kubernetes.io/hostname", "r1").obj()
    )
    client.create_node(
        make_node("r2").capacity(cpu="1", memory="16Gi", pods=10).obj()
    )
    dc = DisruptionController(client, informers)
    sched.preemptor.disruption = dc
    pdb = PodDisruptionBudget(
        selector=LabelSelector(match_labels={"app": "drainable"}),
        max_unavailable=1,  # one eviction in flight at a time
    )
    pdb.metadata.name = "drain-budget"
    pdb.metadata.namespace = "default"
    client.create_pdb(pdb)
    # 6 residents bound on the drained node
    for i in range(6):
        p = (
            make_pod(f"res-{i}").node("n0")
            .container(cpu="1", memory="128Mi")
            .labels(app="drainable").priority(0).obj()
        )
        p.status.start_time = time.time() - 100
        client.create_pod(p)
    informers.start()
    informers.wait_for_cache_sync()
    dc.start()
    sched.queue.run()
    respawner = PodRespawner(
        client, should_respawn=lambda p: p.metadata.name.startswith("res-")
    )
    respawner.start()
    try:
        sched.start()
        # freshen the snapshot (an idle scheduler never dispatches);
        # pinned to r1 so the drain ledger below stays deterministic
        client.create_pod(
            make_pod("warm").container(cpu="100m", memory="64Mi")
            .node_selector(**{"kubernetes.io/hostname": "r1"}).obj()
        )
        assert _wait_named_bound(client, ["warm"], 30)
        sched.wait_for_inflight_binds(timeout=30)

        drainer = NodeDrainer(
            client, disruption=dc, preemptor=sched.preemptor
        )
        emptied = drainer.drain_via_preemption("n0", timeout=60)
        baseline = 6  # the whole-node drain would evict every resident
        assert not emptied  # stragglers have no destination
        assert 0 < drainer.evictions < baseline, (
            f"evicted {drainer.evictions} of baseline {baseline}"
        )
        assert drainer.preempt_left_running >= 1
        assert drainer.preempt_planned == drainer.evictions
        # the stragglers still RUN on the cordoned node
        on_node = [
            p for p in client.list_pods()[0]
            if p.spec.node_name == "n0"
            and p.metadata.deletion_timestamp is None
        ]
        assert len(on_node) == baseline - drainer.evictions
        # budget pacing engaged at least once and never overspent
        assert _pdb_never_negative(server)
        # the replacements actually re-placed (the capacity argument)
        deadline = time.time() + 30
        while time.time() < deadline:
            replaced = [
                p for p in client.list_pods()[0]
                if p.metadata.name.startswith("res-")
                and p.spec.node_name in ("r1", "r2")
            ]
            if len(replaced) == drainer.evictions:
                break
            time.sleep(0.05)
        assert len(replaced) == drainer.evictions
    finally:
        respawner.stop()
        sched.stop()
        dc.stop()
        informers.stop()
