"""A cluster's worth of Services and of soft anti-affinity (ISSUE 51).

``pack_score_batch``'s three dynamic sections (selector spread, soft
topology spread, preferred inter-pod affinity) walked every resident of
every node once for each group or row, and stopped at 8 selector groups
and 16 preferred-affinity rows: a ninth Service in a batch, or a
seventeenth distinct term among the RESIDENTS, sent the whole batch to
the host path. They now read the dispatcher's ``FamilyFacts`` (the pod
census, the node-value rows, the term owners; one ``default_selector`` a
pod template) and carry a wide shape of 64 + 64 rows where the small one
does not hold batch and cluster; a batch past the wide shape is cut
where the row past it is asked for.

Held here: over binds, deletes, a terminating pod, a node-spec epoch
move, a node added and a node removed, every array of the ``ScoreBatch``
that keeps its facts is, bit for bit, what a call that keeps nothing
builds, and only the nodes the change log names are counted again; 48
groups and 48 rows stay on the device's shapes and the constrained
kernel agrees with the XLA scan at the wide caps; the envelopes cut or
refuse, each counted by its reason.
"""

import dataclasses
import math
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    LabelSelector,
    ObjectMeta,
    OwnerReference,
    ReplicaSet,
    Service,
)
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.snapshot import Snapshot, new_snapshot
from kubernetes_tpu.ops.affinity import noop_affinity_tensors
from kubernetes_tpu.ops.assignment import (
    GreedyConfig,
    caps_for_families,
    greedy_assign_constrained,
)
from kubernetes_tpu.ops.family_facts import FamilyFacts, attach
from kubernetes_tpu.ops.host_masks import static_mask_compact
from kubernetes_tpu.ops.pallas_constrained import (
    VMEM_BUDGET,
    constrained_vmem_bytes,
    live_caps,
    pallas_constrained_solve,
)
from kubernetes_tpu.ops.scoring import (
    MAX_IPA_ROWS,
    MAX_SCORE_SIGS,
    MAX_SEL_GROUPS,
    SIG_BUCKET,
    WIDE_IPA_ROWS,
    WIDE_SEL_GROUPS,
    ScoreBatch,
    ScoreEnvelopeCut,
    ScoreEnvelopeExceeded,
    pack_score_batch,
    pad_score_tensors,
)
from kubernetes_tpu.ops.topology import noop_spread_tensors
from kubernetes_tpu.tensors import NodeTensorCache, pack_pod_batch
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
NODES = 20
WEIGHTS = {"DefaultPodTopologySpread": 1, "InterPodAffinity": 1,
           "PodTopologySpread": 2}


class _Lister:
    def __init__(self, items):
        self.items = items

    def list(self):
        return self.items


class _Informers:
    """What ``default_selector`` reads: Services and ReplicaSets."""

    def __init__(self, services):
        self._services = [
            Service(metadata=ObjectMeta(name=f"svc-{k}"),
                    selector={"app": f"svc-{k}"})
            for k in range(services)
        ]
        self._replica_sets = [
            ReplicaSet(metadata=ObjectMeta(name=f"svc-{k}"),
                       selector=LabelSelector(
                           match_labels={"app": f"svc-{k}"}))
            for k in range(services)
        ]

    def services(self):
        return _Lister(self._services)

    def replica_sets(self):
        return _Lister(self._replica_sets)

    def replication_controllers(self):
        return _Lister([])

    def stateful_sets(self):
        return _Lister([])


def _node(i, zone=None):
    return (
        make_node(f"n{i}")
        .capacity(cpu=str(8 + i % 5), memory=f"{16 + i % 7}Gi", pods=110)
        .label(ZONE, zone or f"z{i % 4}").label(HOST, f"n{i}").obj()
    )


def _svc_pod(name, k, node=None, weight=100, key=HOST, anti=True,
             term=True):
    """A pod of service ``k`` as the chart makes it: the app's label, a
    controller owner reference and a soft anti-affinity term to its own
    label."""
    w = make_pod(name).labels(app=f"svc-{k}").container(
        cpu="100m", memory="64Mi")
    if term:
        w.preferred_pod_affinity(key, {"app": f"svc-{k}"}, weight=weight,
                                 anti=anti)
    if node is not None:
        w.node(node)
    pod = w.obj()
    pod.metadata.owner_references = [OwnerReference(
        kind="ReplicaSet", name=f"svc-{k}", uid=f"rs-{k}", controller=True)]
    return pod


def _assert_same(got: ScoreBatch, want: ScoreBatch) -> None:
    assert got is not None and want is not None
    for field in dataclasses.fields(ScoreBatch):
        a, b = getattr(got, field.name), getattr(want, field.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, field.name
            assert a.tobytes() == b.tobytes(), field.name
        else:
            assert a == b, field.name


class _Cluster:
    """A cache, the snapshot it feeds, one tensor cache, the Services
    and the dispatcher's ``FamilyFacts``."""

    def __init__(self, services, count=NODES):
        self.cache = SchedulerCache()
        self.nodes = {}
        for i in range(count):
            self.add(_node(i))
        self.snap = Snapshot()
        self.tc = NodeTensorCache()
        self.facts = FamilyFacts()
        self.informers = _Informers(services)

    def add(self, node):
        old = self.nodes.get(node.metadata.name)
        self.nodes[node.metadata.name] = node
        if old is None:
            self.cache.add_node(node)
        else:
            self.cache.update_node(old, node)

    def remove(self, name):
        self.cache.remove_node(self.nodes.pop(name))

    def pack(self, pods, hard=1) -> ScoreBatch:
        """The pack that keeps, held to one of the same snapshot and
        tensor that keeps nothing: every array, bit for bit."""
        self.cache.update_snapshot(self.snap)
        nt = self.tc.update(self.snap)
        got = pack_score_batch(
            pods, self.snap, nt, self.informers, WEIGHTS,
            hard_pod_affinity_weight=hard, facts=self.facts,
        )
        want = pack_score_batch(
            pods, self.snap, nt, self.informers, WEIGHTS,
            hard_pod_affinity_weight=hard,
        )
        _assert_same(got, want)
        return got


def _residents(services, each, rng):
    return [
        _svc_pod(f"res-{k}-{e}", k, node=f"n{rng.randrange(NODES)}",
                 weight=100 if k % 3 else 7 + k)
        for k in range(services) for e in range(each)
    ]


def _batch(services, stem, each=1):
    return [_svc_pod(f"{stem}-{k}-{e}", k)
            for k in range(services) for e in range(each)]


# -- (a) the kept facts against the whole build -------------------------------


def _counts_of(got: ScoreBatch, c: _Cluster):
    """The selector-spread and preferred-affinity counts by hand, from
    the snapshot's own pods: ``{app: {node row: live pods}}`` and
    ``{app: {node row: all pods}}``."""
    nt = c.tc.update(c.snap)
    rows = nt.rows_for(c.snap.list_node_infos()).tolist()
    live, every = {}, {}
    for j, ni in zip(rows, c.snap.list_node_infos()):
        for p in ni.pods:
            app = p.metadata.labels.get("app")
            every.setdefault(app, {}).setdefault(j, 0)
            every[app][j] += 1
            if p.metadata.deletion_timestamp is None:
                live.setdefault(app, {}).setdefault(j, 0)
                live[app][j] += 1
    return live, every


def test_kept_facts_equal_the_whole_build_over_every_event():
    rng = random.Random(51)
    c = _Cluster(services=12)
    held = _residents(12, 3, rng)
    for p in held:
        c.cache.add_pod(p)
    got = c.pack(_batch(12, "a"))
    assert got.dynamic
    # 12 groups and 12 rows are past the small shape: the wide one
    assert got.sel_counts.shape[0] == WIDE_SEL_GROUPS
    assert got.ipa_counts.shape[0] == WIDE_IPA_ROWS
    assert c.facts.score_dynamic_rows == 12 + 12
    # the first batch counted every node, the next counts none
    assert c.facts.score_census_recounted == c.facts.score_census_nodes == NODES
    c.pack(_batch(12, "b"))
    assert c.facts.score_census_recounted == NODES
    assert c.facts.score_census_nodes == 2 * NODES

    def terminate():
        gone = _replaced(held[7], deletion_timestamp=1.0)
        c.cache.update_pod(held[7], gone)
        held[7] = gone

    fresh = [_svc_pod(f"new-{i}", i % 12, node=f"n{i}") for i in range(6)]
    plain = make_pod("plain").node("n3").container(cpu="100m").obj()
    events = [
        ("binds", lambda: [c.cache.add_pod(p) for p in fresh[:4]], 4),
        ("an assume", lambda: c.cache.assume_pod(fresh[4]), 1),
        ("a delete", lambda: c.cache.remove_pod(held[0]), 1),
        ("a terminating pod", terminate, 1),
        ("a pod with no term", lambda: c.cache.add_pod(plain), 1),
        ("nothing", lambda: None, 0),
        ("a forget", lambda: c.cache.forget_pod(fresh[4]), 1),
        ("a zone label", lambda: c.add(_node(5, zone="z9")), None),
        ("a node added", lambda: c.add(_node(NODES + 1)), None),
        ("a node removed", lambda: c.remove("n2"), None),
    ]
    for n, (what, event, nodes_named) in enumerate(events):
        event()
        recounted = c.facts.score_census_recounted
        got = c.pack(_batch(12, f"e{n}", each=2))  # or pack() fails
        moved = c.facts.score_census_recounted - recounted
        if nodes_named is not None:
            # the nodes the change log names, and no other
            assert moved <= nodes_named, what
        live, every = _counts_of(got, c)
        # the rows against a count by hand: group g is service g's here
        for g in range(12):
            group = int(got.pod_sel_group[2 * g])
            want = np.zeros(got.sel_counts.shape[1], dtype=np.int32)
            for j, count in live.get(f"svc-{g}", {}).items():
                want[j] = count
            assert np.array_equal(got.sel_counts[group], want), what
    # the terminating pod is in no selector count and in its term's
    assert held[7].metadata.deletion_timestamp is not None


def test_the_owners_mass_is_the_residents_signed_weights():
    c = _Cluster(services=3)
    pods = [
        _svc_pod("a0", 0, node="n1", weight=100),
        _svc_pod("a1", 0, node="n1", weight=100),
        _svc_pod("a2", 0, node="n4", weight=100),
        _svc_pod("b0", 1, node="n4", weight=9, anti=False, key=ZONE),
    ]
    hard = make_pod("h0").labels(app="svc-2").node("n6").container(
        cpu="100m").pod_affinity(ZONE, {"app": "svc-0"}).obj()
    for p in pods + [hard]:
        c.cache.add_pod(p)
    got = c.pack(_batch(3, "x"), hard=5)
    nt = c.tc.update(c.snap)
    row = {name: j for j, name in enumerate(nt.names) if name}
    by_weight = {}
    for r in range(got.ipa_wcounts.shape[0]):
        mass = got.ipa_wcounts[r]
        if mass.any():
            by_weight[float(mass[mass != 0][0])] = (r, mass)
    # two owners of -100 on n1, one on n4, by hostname
    r, mass = by_weight[-200.0]
    assert mass[got.ipa_node_value[r, row["n1"]]] == -200.0
    assert mass[got.ipa_node_value[r, row["n4"]]] == -100.0
    # +9 at n4's zone; the required term at 5 times one owner, n6's zone
    r, mass = by_weight[9.0]
    assert mass[got.ipa_node_value[r, row["n4"]]] == 9.0
    r, mass = by_weight[5.0]
    assert mass[got.ipa_node_value[r, row["n6"]]] == 5.0
    # hardPodAffinityWeight 0: the required term makes no row
    none = c.pack(_batch(3, "y"), hard=0)
    assert not (none.ipa_wcounts == 5.0).any()


def test_the_term_owners_are_counted_from_the_first_batch_that_asks():
    rng = random.Random(3)
    c = _Cluster(services=6)
    for p in _residents(6, 2, rng):
        c.cache.add_pod(p)

    def census_of(name):
        """The census read as a hard family reads it: no term owner."""
        c.cache.update_snapshot(c.snap)
        kept = attach(c.facts, c.snap, c.tc.update(c.snap))
        return kept.matching_in(
            "default", ("test", name), lambda labels: "app" in labels)

    # a dispatcher whose batches carry no scoring term counts the pods
    # and no owner, at the first batch and after a bind
    assert census_of("first")
    c.cache.add_pod(_svc_pod("late", 2, node="n3"))
    assert census_of("second")
    assert c.facts.nodes_recounted == NODES + 1
    assert not c.facts._owners and not c.facts._owners_kept
    # the first batch that scores by them counts every owner, and the
    # change log keeps them after that (pack() holds both to a whole build)
    got = c.pack(_batch(6, "a"))
    assert len(c.facts._owners) == 6
    assert got.ipa_wcounts.sum() == sum(
        -(100.0 if k % 3 else 7.0 + k) * (3 if k == 2 else 2)
        for k in range(6))
    c.cache.add_pod(_svc_pod("later", 4, node="n5", weight=100))
    after = c.pack(_batch(6, "b"))
    assert after.ipa_wcounts.sum() == got.ipa_wcounts.sum() - 100.0


def test_a_snapshot_no_cache_feeds_keeps_nothing():
    rng = random.Random(7)
    nodes = [_node(i) for i in range(NODES)]
    snap = new_snapshot(_residents(5, 2, rng), nodes)
    assert snap.node_spec_epoch == 0
    nt = NodeTensorCache().update(snap)
    facts = FamilyFacts()
    informers = _Informers(5)
    for stem in "ab":
        got = pack_score_batch(
            _batch(5, stem), snap, nt, informers, WEIGHTS, facts=facts)
        _assert_same(got, pack_score_batch(
            _batch(5, stem), snap, nt, informers, WEIGHTS))
    # every node counted at every call
    assert facts.score_census_recounted == facts.score_census_nodes == 2 * NODES


# -- (a') the census by difference (ISSUE 52) ---------------------------------
# The census keeps the pods it counted on every row and moves the counts
# by the pods that came and went; what it says has to stay, bit for bit,
# what counting every node from nothing says.


def _replaced(pod, **meta):
    """``pod`` as an update hands it over: another object, same uid."""
    new = dataclasses.replace(pod)
    new.metadata = dataclasses.replace(pod.metadata, **meta)
    return new


class _Walk:
    """A random walk over everything that moves a node's pods or the
    node rows, ``pack`` after every step holding the kept census to one
    that keeps nothing."""

    SERVICES = 12
    STEPS = (
        "bind", "bind", "bind", "assume", "forget", "confirm", "delete",
        "delete", "terminate", "relabel", "twins", "lose_a_twin",
        "empty_a_class", "cancelling_owners", "hard_owner", "zone_label",
        "node_added", "node_removed", "node_back", "nothing",
    )

    def __init__(self, seed):
        self.rng = random.Random(seed)
        self.c = _Cluster(self.SERVICES)
        self.count = NODES
        self.gone_nodes = []
        self.held = {}  # uid -> the object the cache holds
        self.assumed = []
        self.twins = []
        self.refill = None
        self.seq = 0
        self.done = set()
        for p in _residents(self.SERVICES, 2, self.rng):
            self.add(p)
        self.c.pack(_batch(self.SERVICES, "w"))

    def name(self):
        self.seq += 1
        return f"walk-{self.seq}"

    def node(self):
        return self.rng.choice(sorted(self.c.nodes))

    def pod(self, k=None, node=None, **kw):
        k = self.rng.randrange(self.SERVICES) if k is None else k
        return _svc_pod(self.name(), k, node=node or self.node(),
                        weight=100 if k % 3 else 7 + k, **kw)

    def add(self, pod):
        self.c.cache.add_pod(pod)
        self.held[pod.metadata.uid] = pod

    def some(self):
        return self.held[self.rng.choice(sorted(self.held))]

    def update(self, old, new):
        self.c.cache.update_pod(old, new)
        self.held[new.metadata.uid] = new

    def step(self):
        rng, c = self.rng, self.c
        kind = rng.choice(self.STEPS)
        if self.refill is not None:  # the class just emptied comes back
            kind, k = "refill", self.refill
            self.refill = None
            self.add(self.pod(k))
        elif kind == "bind":
            self.add(self.pod())
        elif kind == "assume":
            pod = self.pod()
            c.cache.assume_pod(pod)
            self.assumed.append(pod)
        elif kind == "forget" and self.assumed:
            c.cache.forget_pod(
                self.assumed.pop(rng.randrange(len(self.assumed))))
        elif kind == "confirm" and self.assumed:
            self.add(self.assumed.pop(rng.randrange(len(self.assumed))))
        elif kind == "delete" and self.held:
            c.cache.remove_pod(self.held.pop(self.some().metadata.uid))
        elif kind == "terminate" and self.held:
            old = self.some()
            self.update(old, _replaced(old, deletion_timestamp=1.0))
        elif kind == "relabel" and self.held:
            # into another service: another class, the pod's term stays
            old = self.some()
            app = f"svc-{rng.randrange(self.SERVICES)}"
            self.update(old, _replaced(
                old, labels={**old.metadata.labels, "app": app}))
        elif kind == "twins":
            # two pods of one class on one node
            k, node = rng.randrange(self.SERVICES), self.node()
            first, second = self.pod(k, node), self.pod(k, node)
            self.add(first)
            self.add(second)
            self.twins.append(second)
        elif kind == "lose_a_twin" and self.twins:
            pod = self.twins.pop()
            if pod.metadata.uid not in self.held:
                return self.step()
            c.cache.remove_pod(self.held.pop(pod.metadata.uid))
        elif kind == "empty_a_class" and self.held:
            app = self.some().metadata.labels["app"]
            for uid in [u for u, p in self.held.items()
                        if p.metadata.labels["app"] == app]:
                c.cache.remove_pod(self.held.pop(uid))
            self.refill = int(app.split("-")[1])
        elif kind == "cancelling_owners":
            # +w beside -w on one row: the sum 0.0, the row an owners' row
            k, node = rng.randrange(self.SERVICES), self.node()
            self.add(self.pod(k, node, anti=True))
            self.add(self.pod(k, node, anti=False))
        elif kind == "hard_owner":
            pod = make_pod(self.name()).labels(app="svc-1").node(
                self.node()).container(cpu="100m").pod_affinity(
                ZONE, {"app": "svc-0"}).obj()
            self.add(pod)
        elif kind == "zone_label":
            i = int(self.node()[1:])
            c.add(_node(i, zone=f"z{rng.randrange(6)}"))
        elif kind == "node_added":
            c.add(_node(self.count))
            self.count += 1
        elif kind == "node_removed" and len(c.nodes) > 14:
            name = self.node()
            c.remove(name)  # its pods stay in the cache, on no row
            self.gone_nodes.append(name)
        elif kind == "node_back" and self.gone_nodes:
            c.add(_node(int(self.gone_nodes.pop()[1:])))
        elif kind != "nothing":
            return self.step()
        self.done.add(kind)
        return kind


@pytest.mark.parametrize("seed", [52, 53, 54, 55])
def test_the_census_by_difference_equals_the_whole_count_over_a_walk(seed):
    w = _Walk(seed)
    for n in range(250):
        what = w.step()
        try:
            w.c.pack(_batch(w.SERVICES, f"s{n}"), hard=1 + n % 2)
        except AssertionError as err:
            raise AssertionError(f"step {n} ({what}): {err}") from err
    assert w.done >= set(_Walk.STEPS) | {"refill"}
    facts = w.c.facts
    # it did advance by difference: fewer pods handled than resident on
    # the rows it visited, and never more
    assert facts.census_pods_moved < facts.census_pods_held


def _census(c, facts=None):
    """The census of ``c``'s snapshot as ``facts`` (the kept one, or one
    that keeps nothing) has it."""
    c.cache.update_snapshot(c.snap)
    kept = attach(facts, c.snap, c.tc.update(c.snap))
    kept.matching_in("default", ("test", "all"), lambda labels: True)
    return kept


def _class_ids(facts):
    return {id(cls) for by in facts._classes.values() for cls in by.values()}


def _census_read(kept):
    classes = {
        (ns, key): (dict(cls.pods), dict(cls.terminating))
        for ns, by_labels in kept._classes.items()
        for key, cls in by_labels.items()
    }
    owners = {
        o.sig: (dict(o.preferred), dict(o.preferred_owners), dict(o.required))
        for o in kept.term_owners()
    }
    return classes, owners


def test_a_bind_moves_one_pod_of_the_rows_it_visits():
    c = _Cluster(services=3)
    five = [_svc_pod(f"r{e}", e % 3, node="n4") for e in range(5)]
    for p in five + [_svc_pod("elsewhere", 0, node="n9")]:
        c.cache.add_pod(p)
    c.pack(_batch(3, "a"))
    facts = c.facts
    # the first count: every pod of every node came
    assert (facts.census_pods_moved, facts.census_pods_held) == (6, 6)
    assert facts.nodes_recounted == NODES
    c.pack(_batch(3, "b"))  # nothing moved: no row visited, no pod handled
    assert (facts.census_pods_moved, facts.census_pods_held) == (6, 6)
    c.cache.add_pod(_svc_pod("sixth", 1, node="n4"))
    c.pack(_batch(3, "c"))
    assert facts.nodes_recounted == NODES + 1
    assert (facts.census_pods_moved, facts.census_pods_held) == (7, 12)
    c.cache.remove_pod(five[2])
    c.pack(_batch(3, "d"))
    # a pod that went was resident on the row too: the six it held
    assert (facts.census_pods_moved, facts.census_pods_held) == (8, 18)
    # a node's own write names the row and moves no pod
    c.add(_node(4, zone="z7"))
    c.pack(_batch(3, "e"))
    assert (facts.census_pods_moved, facts.census_pods_held) == (8, 23)


def _whole_cluster(c):
    rng = random.Random(5)
    pods = _residents(6, 4, rng) + [
        _svc_pod(f"every-{i}", i % 6, node=f"n{i}") for i in range(NODES)]
    for p in pods:
        c.cache.add_pod(p)
    return pods


def test_a_log_that_names_every_node_is_advanced_by_difference():
    c = _Cluster(services=6)
    _whole_cluster(c)
    c.pack(_batch(6, "a"))
    facts = c.facts
    classes = _class_ids(facts)
    moved, held = facts.census_pods_moved, facts.census_pods_held
    assert moved == held == 6 * 4 + NODES
    # a pod bound on every node: the log names as many nodes as there are
    for i in range(NODES):
        c.cache.add_pod(_svc_pod(f"wave-{i}", i % 6, node=f"n{i}"))
    c.pack(_batch(6, "b"))
    assert facts.nodes_recounted == 2 * NODES
    assert facts.census_pods_moved - moved == NODES  # what moved, no more
    assert facts.census_pods_held - held == held + NODES
    assert classes == _class_ids(facts)


def _membership_move(c):
    c.add(_node(NODES + 3))


def _truncated_log(c):
    # the cap drops the older half, the census's cursor with it
    c.snap.note_changed_many(["n0"] * 5000)


def _new_slot_list(c):
    c.tc = NodeTensorCache()


@pytest.mark.parametrize(
    "event", [_membership_move, _truncated_log, _new_slot_list],
    ids=lambda f: f.__name__.strip("_"))
def test_what_the_log_cannot_vouch_for_is_counted_from_nothing(event):
    c = _Cluster(services=6)
    pods = _whole_cluster(c)
    c.pack(_batch(6, "a"))
    facts = c.facts
    classes = _class_ids(facts)
    moved = facts.census_pods_moved
    c.cache.remove_pod(pods[0])
    event(c)
    c.pack(_batch(6, "b"))
    nodes = len(c.snap.list_node_infos())
    assert facts.nodes_recounted == NODES + nodes
    assert facts.census_pods_moved - moved == len(pods) - 1  # every pod came
    assert not classes & _class_ids(facts)


def test_a_kept_census_and_one_that_keeps_nothing_share_the_pods():
    """Both read the same pod objects off one snapshot, in the
    dispatcher within one call: nothing of either is on a pod."""
    c = _Cluster(services=4)
    rng = random.Random(11)
    pods = _residents(4, 5, rng)
    for p in pods:
        c.cache.add_pod(p)
    before = {id(p): dict(p.__dict__) for p in pods}
    kept0 = _census_read(_census(c, c.facts))
    fresh = _census(c)
    assert fresh is not c.facts and not fresh.keeps
    assert _census_read(fresh) == kept0
    for step in range(12):
        gone = pods.pop(rng.randrange(len(pods)))
        c.cache.remove_pod(gone)
        new = _svc_pod(f"in-{step}", step % 4, node=f"n{rng.randrange(NODES)}")
        c.cache.add_pod(new)
        pods.append(new)
        first, second = (
            (_census(c), _census(c, c.facts)) if step % 2
            else (_census(c, c.facts), _census(c)))
        assert _census_read(first) == _census_read(second)
        third = _census(c)  # another fresh one changes nothing for the kept
        assert _census_read(third) == _census_read(_census(c, c.facts))
    # the classes are each census's own objects
    assert not _class_ids(c.facts) & _class_ids(third)
    # what the census left on a pod is a function of the pod alone: the
    # memo of its scoring terms, which both read
    for p in pods:
        added = set(p.__dict__) - set(before.get(id(p), p.__dict__))
        assert added <= {"_scoring_terms_memo"}
    assert c.facts.census_pods_moved < c.facts.census_pods_held


def test_a_row_leaves_a_term_with_its_last_owner_not_when_weights_cancel():
    c = _Cluster(services=2)
    minus = _svc_pod("minus", 0, node="n1", weight=50, anti=True)
    plus = _svc_pod("plus", 0, node="n1", weight=50, anti=False)
    other = _svc_pod("other", 0, node="n6", weight=50, anti=True)
    hard = make_pod("hard").labels(app="svc-1").node("n1").container(
        cpu="100m").pod_affinity(HOST, {"app": "svc-0"}).obj()
    for p in (minus, plus, other, hard):
        c.cache.add_pod(p)
    c.pack(_batch(2, "a"))
    (owners,) = c.facts.term_owners()  # one signature: weight is not in it
    nt = c.tc.update(c.snap)
    row = {name: j for j, name in enumerate(nt.names) if name}
    n1, n6 = row["n1"], row["n6"]
    # -50 and +50 cancel: the row stays, at 0.0, as a whole build has it
    assert owners.preferred == {n1: 0.0, n6: -50.0}
    assert owners.preferred_owners == {n1: 2, n6: 1}
    assert owners.required == {n1: 1}
    c.cache.remove_pod(plus)
    c.pack(_batch(2, "b"))
    assert owners.preferred == {n1: -50.0, n6: -50.0}
    c.cache.remove_pod(minus)
    c.pack(_batch(2, "c"))
    # the last preferred owner of n1 left: the row leaves that kind, and
    # the required owner keeps its own
    assert owners.preferred == {n6: -50.0}
    assert owners.preferred_owners == {n6: 1}
    assert owners.required == {n1: 1}
    c.cache.remove_pod(hard)
    c.cache.remove_pod(other)
    c.pack(_batch(2, "d"))
    assert c.facts.term_owners() == []  # the term left with its last row
    assert _census_read(c.facts)[0] == {}  # and each class with its last pod
    c.cache.add_pod(_svc_pod("again", 0, node="n6", weight=50, anti=True))
    c.pack(_batch(2, "e"))
    (again,) = c.facts.term_owners()
    assert again is not owners and again.preferred == {n6: -50.0}


# -- (b) the two shapes -------------------------------------------------------


@pytest.mark.parametrize("services,wide", [(8, False), (9, True), (48, True)])
def test_the_shape_follows_the_batch_and_the_cluster(services, wide):
    rng = random.Random(services)
    c = _Cluster(services)
    for p in _residents(services, 2, rng):
        c.cache.add_pod(p)
    got = c.pack(_batch(services, "a"))
    assert got.sel_counts.shape[0] == (
        WIDE_SEL_GROUPS if wide else MAX_SEL_GROUPS)
    assert got.ipa_counts.shape[0] == (
        WIDE_IPA_ROWS if wide else MAX_IPA_ROWS)
    assert got.pod_sel_match.shape[1] == got.sel_counts.shape[0]
    assert got.pod_ipa_weight.shape[1] == got.ipa_counts.shape[0]
    # no static family is live: the placeholders' rows, not 64
    assert got.direct_rows.shape[0] == SIG_BUCKET
    assert sorted(set(got.pod_sel_group.tolist())) == list(range(services))


def test_the_residents_terms_alone_take_the_wide_shape():
    """17 distinct terms among the residents made every batch of such a
    cluster a host batch, whatever it held."""
    rng = random.Random(3)
    c = _Cluster(services=0)
    for p in _residents(17, 1, rng):
        c.cache.add_pod(p)
    plain = [make_pod(f"p{i}").labels(app="svc-3").container(
        cpu="100m").obj() for i in range(4)]
    got = c.pack(plain)
    assert got.ipa_counts.shape[0] == WIDE_IPA_ROWS
    assert got.pod_ipa_match.sum() == 4  # each matches svc-3's term


def test_the_wide_caps_pass_the_vmem_gate_beside_the_placeholder_rows():
    """5,632 node slots, 4 resource columns, 8 mask rows: 64 + 64 rows
    fit beside 4 static rows and not beside 64 (ISSUE 51's reckoning)."""
    caps = live_caps(False, False, True, sc_used=(0, 48, 48), sc_wide=True)
    assert (caps.rp, caps.g_sel, caps.gt) == (64, 64, 8)
    assert constrained_vmem_bytes(
        5632, 4, 8, SIG_BUCKET, 64, 128, caps) <= VMEM_BUDGET
    assert constrained_vmem_bytes(
        5632, 4, 8, MAX_SCORE_SIGS, 64, 128, caps) > VMEM_BUDGET
    small = live_caps(False, False, True, sc_used=(0, 8, 8))
    assert (small.rp, small.g_sel) == (8, 8)


# -- (c) the envelopes --------------------------------------------------------


def _counted(reason):
    return metrics.score_envelope_exceeded.value(reason=reason)


def test_a_batch_past_the_wide_shape_is_cut_where_it_asks():
    c = _Cluster(services=WIDE_SEL_GROUPS + 6)
    pods = _batch(WIDE_SEL_GROUPS + 6, "a", each=2)
    c.cache.update_snapshot(c.snap)
    nt = c.tc.update(c.snap)
    before = _counted("selector_groups")
    with pytest.raises(ScoreEnvelopeCut) as cut:
        pack_score_batch(pods, c.snap, nt, c.informers,
                         {"DefaultPodTopologySpread": 1}, facts=c.facts)
    assert cut.value.reason == "selector_groups"
    assert cut.value.fit == 2 * WIDE_SEL_GROUPS
    assert _counted("selector_groups") == before + 1
    head = pack_score_batch(
        pods[:cut.value.fit], c.snap, nt, c.informers,
        {"DefaultPodTopologySpread": 1}, facts=c.facts)
    assert head.sel_counts.shape[0] == WIDE_SEL_GROUPS
    # the preferred-affinity rows are met first where both are live
    before = _counted("preferred_affinity_rows")
    with pytest.raises(ScoreEnvelopeCut) as cut:
        pack_score_batch(pods, c.snap, nt, c.informers, WEIGHTS)
    assert cut.value.reason in ("selector_groups", "preferred_affinity_rows")
    assert cut.value.fit == 2 * WIDE_IPA_ROWS


def test_residents_past_the_wide_shape_send_the_batch_to_the_host_path():
    rng = random.Random(9)
    c = _Cluster(services=0)
    for p in _residents(WIDE_IPA_ROWS + 1, 1, rng):
        c.cache.add_pod(p)
    c.cache.update_snapshot(c.snap)
    nt = c.tc.update(c.snap)
    before = _counted("preferred_affinity_rows")
    with pytest.raises(ScoreEnvelopeExceeded) as refused:
        pack_score_batch(_batch(2, "a"), c.snap, nt, None, WEIGHTS)
    assert not isinstance(refused.value, ScoreEnvelopeCut)
    assert refused.value.reason == "preferred_affinity_rows"
    assert _counted("preferred_affinity_rows") == before + 1


@pytest.mark.parametrize("reason", [
    "zones", "soft_constraints", "soft_groups", "score_signatures",
])
def test_every_envelope_is_counted_by_its_reason(reason):
    c = _Cluster(services=0, count=0)
    weights = dict(WEIGHTS, NodeAffinity=1)
    if reason == "zones":
        for i in range(70):
            c.add(_node(i, zone=f"z{i}"))
        pods = _batch(2, "a")
    else:
        for i in range(6):
            c.add(_node(i))
        pods = []
        for i in range(70):
            w = make_pod(f"p{i}").labels(app="a").container(cpu="100m")
            if reason == "soft_constraints":
                for k in range(5):
                    w.spread_constraint(
                        1, ZONE, when_unsatisfiable="ScheduleAnyway",
                        match_labels={"app": f"a{k}"})
            elif reason == "soft_groups":
                w.spread_constraint(
                    1, ZONE, when_unsatisfiable="ScheduleAnyway",
                    match_labels={"app": f"a{i}"})
            else:
                w.preferred_node_affinity_in(ZONE, [f"z{i}"], weight=1 + i)
            pods.append(w.obj())
    c.cache.update_snapshot(c.snap)
    nt = c.tc.update(c.snap)
    before = _counted(reason)
    with pytest.raises(ScoreEnvelopeExceeded) as refused:
        pack_score_batch(pods, c.snap, nt, None, weights)
    assert refused.value.reason == reason
    assert _counted(reason) == before + 1


# -- (d) the kernel at the wide caps ------------------------------------------


def _packed(seed, services=48, b=64):
    rng = random.Random(seed)
    c = _Cluster(services)
    for p in _residents(services, 2, rng):
        c.cache.add_pod(p)
    # a few residents of other shapes: a zone-keyed attraction, a pod
    # with no term, so that the rows do not all look alike
    for i in range(4):
        c.cache.add_pod(_svc_pod(
            f"zr{i}", i, node=f"n{rng.randrange(NODES)}", key=ZONE,
            anti=False, weight=3 + i))
    pods = []
    for i in range(b):
        k = rng.randrange(services)
        pods.append(_svc_pod(f"m{i}", k, term=rng.random() < 0.8,
                             weight=rng.choice([100, 100, 40])))
    c.cache.update_snapshot(c.snap)
    nt = c.tc.update(c.snap)
    batch = pack_pod_batch(pods, nt.dims)
    mask_rows, mask_index = static_mask_compact(pods, c.snap, nt)
    padded = 64 * math.ceil(batch.size / 64)
    order = batch.order
    req = np.zeros((padded, nt.dims.num_dims), dtype=np.int32)
    nzr = np.zeros((padded, 2), dtype=np.int32)
    midx = np.zeros(padded, dtype=np.int32)
    active = np.zeros(padded, dtype=bool)
    req[:batch.size] = batch.requests[order]
    nzr[:batch.size] = batch.non_zero_requests[order]
    midx[:batch.size] = mask_index[order]
    active[:batch.size] = True
    rows = np.zeros((8, nt.capacity), dtype=bool)
    rows[:mask_rows.shape[0]] = mask_rows
    ordered = [pods[int(i)] for i in order]
    sc = pack_score_batch(ordered, c.snap, nt, c.informers, WEIGHTS,
                          facts=c.facts)
    common = (nt.allocatable, nt.requested, nt.non_zero_requested, nt.valid,
              req, nzr, rows, midx, active)
    return (common, tuple(noop_spread_tensors(padded, nt.capacity)),
            tuple(noop_affinity_tensors(padded, nt.capacity)),
            tuple(pad_score_tensors(sc, padded)))


@pytest.mark.parametrize("seed", [1, 8, 51])
def test_the_kernel_at_the_wide_caps_matches_the_xla_scan(seed):
    common, sp_t, af_t, sc_t = _packed(seed)
    caps = caps_for_families(sp_t, af_t, sc_t, False, False, True)
    assert (caps.g_sp, caps.ra, caps.rp, caps.g_sel) == (0, 0, 64, 64)
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig())
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(), interpret=True,
        caps=caps)
    assert (np.asarray(a1)[:64] >= 0).all()
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    # and with no caps given: every row as it came packed
    a3, _, _ = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(), interpret=True)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a3))


def test_the_cells_kernel_shape_is_the_kernels_own_operand_plan():
    """``chipbench/configs/services-5000.json`` counts the constrained
    call's node-length rows and per-pod words beyond the basic kernel's
    (``kernel_shape``: the bytes behind ``solve_kernel_roofline``): held
    here to the plan the kernel itself lays out at the cell's caps."""
    import json
    from pathlib import Path

    from kubernetes_tpu.ops.pallas_constrained import _pp_layout, _spec_plan

    root = Path(__file__).resolve().parents[1]
    shape = json.loads(
        (root / "chipbench/configs/services-5000.json").read_text()
    )["kernel_shape"]
    n, r, u, b = shape["n_cap"], shape["r"], shape["u"], shape["b"]
    caps = live_caps(False, False, True, sc_used=(0, 48, 48), sc_wide=True)
    chunk = 1024
    in_specs, out_shapes, _, iidx, oidx, _ = _spec_plan(
        caps, {"r": r, "n": n, "u": u, "s": SIG_BUCKET, "z": 64,
               "v_sp": 128, "grid": b // chunk}, chunk)
    rows_in = sum(spec.block_shape[0] for spec in in_specs
                  if len(spec.block_shape) == 2 and spec.block_shape[1] == n)
    rows_out = sum(s.shape[0] for s in out_shapes
                   if len(s.shape) == 2 and s.shape[1] == n)
    basic = (r + r + 2 + 1 + u) + (r + 2)  # chipbench/kernel_bytes.py
    assert rows_in + rows_out - basic == shape["family_rows"] == 549
    # a pod's words: the SMEM vectors and its column of the parameter block
    per_pod = 1 + r + 2 + 1 + 1 + 1 + _pp_layout(caps)[1] + 1
    assert per_pod - 9 == shape["families"] == 274
