"""Pack's node-side inputs are kept incrementally (ISSUE 25): the snapshot
refresh walks the cache's generation order, static mask rows are handed
out again while no node object and no row slot changed, the score
packer's node-side facts are taken once a node-spec epoch, and changed
tensor rows are written at once. Each is held here to the code it
replaced, kept below as the test's twin: the same arrays must reach
``solve_packed``."""

import random
import sys
import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    CSINode,
    CSINodeDriver,
    NodeCondition,
    ObjectMeta,
)
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.node_info import CSI_ATTACH_PREFIX, pod_host_ports
from kubernetes_tpu.cache.snapshot import Snapshot, new_snapshot
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.ops import host_masks
from kubernetes_tpu.ops.assignment import ConstPiece
from kubernetes_tpu.ops.host_masks import (
    MaskRowCache,
    _UNSCHEDULABLE_TAINT,
    _constraint_signature,
    _tolerates_node_taints,
    static_mask_compact,
)
from kubernetes_tpu.ops.scoring import pack_score_batch
from kubernetes_tpu.plugins.nodeaffinity import (
    pod_matches_node_selector_and_affinity,
)
from kubernetes_tpu.plugins.nodepreferavoidpods import (
    ANNOTATION_KEY as AVOID_ANNOTATION,
)
from kubernetes_tpu.scheduler import batch as batch_mod
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.tensors import NodeTensorCache
from kubernetes_tpu.tensors.node_tensor import (
    CPU,
    EPH,
    MEM,
    PODS,
    _kib_ceil,
    _kib_floor,
)
from kubernetes_tpu.testing import make_node, make_pod

# -- the twins: what each site did before this change -------------------------


def full_walk_update(cache: SchedulerCache, snapshot: Snapshot) -> Snapshot:
    """``SchedulerCache.update_snapshot`` as it was: every node's
    generation compared, two name sets built, the lists rebuilt."""
    with cache._lock:
        max_gen = snapshot.generation
        changed = False
        for name, ni in cache._nodes.items():
            if ni.generation > snapshot.generation:
                prev = snapshot.node_info_map.get(name)
                if prev is None or (prev.node is None) != (ni.node is None):
                    snapshot.note_membership_change()
                snapshot.node_info_map[name] = ni.clone()
                snapshot.note_changed(name)
                changed = True
                if ni.generation > max_gen:
                    max_gen = ni.generation
        stale = set(snapshot.node_info_map) - set(cache._nodes)
        for name in stale:
            del snapshot.node_info_map[name]
            snapshot.note_membership_change()
            changed = True
        if changed:
            snapshot.refresh_lists()
        snapshot.generation = max_gen
        return snapshot


def encode_resource_twin(dims, r, *, ceil_bytes: bool) -> np.ndarray:
    """``ResourceDims.encode_resource`` as it was, the one-row encoder
    the package no longer has."""
    kib = _kib_ceil if ceil_bytes else _kib_floor
    row = np.zeros(dims.num_dims, dtype=np.int32)
    row[CPU] = r.milli_cpu
    row[MEM] = kib(r.memory)
    row[EPH] = kib(r.ephemeral_storage)
    row[PODS] = r.allowed_pod_number
    for name, qty in r.scalar.items():
        row[dims.column(name)] = qty
    return row


def pack_row_twin(tc: NodeTensorCache, i: int, ni) -> None:
    """``NodeTensorCache._pack_row`` as it was: two rows allocated and
    five slices written for one node."""
    tc._alloc[i] = encode_resource_twin(
        tc.dims, ni.allocatable, ceil_bytes=False)
    req = encode_resource_twin(tc.dims, ni.requested, ceil_bytes=True)
    req[PODS] = len(ni.pods)
    vol_cols = tc.dims.volume_columns()
    if vol_cols:
        viu = ni.volume_in_use
        alloc_row = tc._alloc[i]
        for name, col in vol_cols.items():
            alloc_row[col] = ni.volume_limit(name)
            req[col] = viu.get(name, 0)
    tc._req[i] = req
    tc._nzr[i, 0] = ni.non_zero_requested.milli_cpu
    tc._nzr[i, 1] = _kib_ceil(ni.non_zero_requested.memory)
    if tc.topology.keys:
        tc._topo[i] = tc.topology.encode_node_labels(
            ni.node.metadata.labels if ni.node else {}
        )
    tc._generations[i] = ni.generation
    tc._occupied[i] = True
    tc._row_epoch[i] = tc._epoch


class RowByRowTensorCache(NodeTensorCache):
    def _pack_rows(self, rows, infos):
        for i, ni in zip(rows, infos):
            pack_row_twin(self, i, ni)


def static_mask_twin(pods, snapshot, nt, row_cache=None):
    """``static_mask_compact`` as it was: a walk over every node for
    each distinct signature of the batch, at every batch."""
    infos = snapshot.list_node_infos()
    node_rows = nt.rows_for(infos).tolist()
    index = np.zeros(len(pods), dtype=np.int32)
    cache = {}
    rows = []
    for b, pod in enumerate(pods):
        sig = _constraint_signature(pod)
        u = cache.get(sig)
        if u is None:
            row = np.zeros(nt.capacity, dtype=bool)
            for j, ni in zip(node_rows, infos):
                node = ni.node
                if node is None:
                    continue
                if node.spec.unschedulable and not any(
                    t.tolerates(_UNSCHEDULABLE_TAINT)
                    for t in pod.spec.tolerations
                ):
                    continue
                if pod.spec.node_name and (
                    pod.spec.node_name != node.metadata.name
                ):
                    continue
                if not pod_matches_node_selector_and_affinity(pod, ni):
                    continue
                if not _tolerates_node_taints(pod, node):
                    continue
                ports = pod_host_ports(pod)
                if ports and any(
                    ni.used_ports.conflicts(ip, proto, port)
                    for ip, proto, port in ports
                ):
                    continue
                row[j] = True
            u = len(rows)
            rows.append(row)
            cache[sig] = u
        index[b] = u
    return np.stack(rows), index


def score_pack_twin(pods, snapshot, nt, informers, weights, **kwargs):
    """``pack_score_batch`` as it was: the node-side facts swept from
    every node at every batch, the per-pod needs walked from the specs."""
    kwargs.pop("admissions", None)
    snapshot.score_facts = None
    return pack_score_batch(pods, snapshot, nt, informers, weights, **kwargs)


# -- helpers ------------------------------------------------------------------

ZONES = ("z0", "z1", "z2")


def _node(name, zone, **kw):
    w = make_node(name).labels(zone=zone, **{"kubernetes.io/hostname": name})
    return w.capacity(cpu="32", memory="64Gi", pods=110, **kw)


def info_state(ni):
    """Everything a consumer of a snapshot's NodeInfo reads."""
    return (
        ni.generation, id(ni.node),
        [p.metadata.uid for p in ni.pods],
        [p.metadata.uid for p in ni.pods_with_affinity],
        sorted(ni.used_ports.ports),
        (ni.requested.milli_cpu, ni.requested.memory,
         ni.requested.ephemeral_storage, dict(ni.requested.scalar)),
        (ni.non_zero_requested.milli_cpu, ni.non_zero_requested.memory),
        (ni.allocatable.milli_cpu, ni.allocatable.memory,
         ni.allocatable.allowed_pod_number, dict(ni.allocatable.scalar)),
        dict(ni.image_states), dict(ni.csi_volume_limits),
        dict(ni.volume_in_use),
    )


def assert_same_snapshot(new: Snapshot, twin: Snapshot) -> None:
    assert list(new.node_info_map) == list(twin.node_info_map)
    for name, ni in new.node_info_map.items():
        assert info_state(ni) == info_state(twin.node_info_map[name]), name
    assert [ni.node_name for ni in new.node_info_list] == \
        [ni.node_name for ni in twin.node_info_list]
    for ni in new.node_info_list:  # the lists hold the map's own clones
        assert ni is new.node_info_map[ni.node_name]
    assert [ni.node_name for ni in new.have_pods_with_affinity_list] == \
        [ni.node_name for ni in twin.have_pods_with_affinity_list]
    for ni in new.have_pods_with_affinity_list:
        assert ni is new.node_info_map[ni.node_name]
    assert new.generation == twin.generation


def assert_generation_order(cache: SchedulerCache) -> None:
    assert set(cache._gen_order) == set(cache._nodes)
    gens = [cache._nodes[name].generation for name in cache._gen_order]
    assert gens == sorted(gens)


class Churn:
    """Seeded random churn on one cache: pods added, removed, assumed and
    forgotten; nodes added, relabelled, tainted, cordoned and removed; a
    CSINode changed."""

    KINDS = (
        "pod_add", "pod_add", "pod_add", "pod_remove", "assume", "forget",
        "confirm", "node_add", "node_labels", "node_taint",
        "node_unschedulable", "node_remove", "csi", "pod_before_node",
    )

    def __init__(self, seed: int, nodes: int = 12) -> None:
        self.rng = random.Random(seed)
        self.cache = SchedulerCache()
        self.nodes = {}
        self.added = {}
        self.assumed = {}
        self.seq = 0
        for _ in range(nodes):
            self.node_add()

    def _name(self, prefix):
        self.seq += 1
        return f"{prefix}-{self.seq}"

    def _pod(self, node_name):
        name = self._name("p")
        w = make_pod(name).uid(name).node(node_name).labels(app="a")
        kind = self.rng.randrange(4)
        if kind == 0:  # required anti-affinity: pods_with_affinity moves
            w = w.pod_affinity(
                "kubernetes.io/hostname", {"app": "a"}, anti=True
            )
        if kind == 1:
            return w.container(
                cpu="100m", memory="64Mi",
                host_port=8000 + self.rng.randrange(50),
            ).obj()
        if kind == 2:
            return w.container(cpu="250m", memory="1000", foo=1).obj()
        return w.container(cpu="250m", memory="512Mi").obj()

    def node_add(self):
        name = self._name("n")
        node = _node(name, self.rng.choice(ZONES), foo=4).obj()
        self.nodes[name] = node
        self.cache.add_node(node)

    def _replace(self, change):
        if not self.nodes:
            return
        name = self.rng.choice(sorted(self.nodes))
        old = self.nodes[name]
        w = _node(name, old.metadata.labels["zone"], foo=4)
        w.node_obj.spec.taints = list(old.spec.taints)
        w.node_obj.spec.unschedulable = old.spec.unschedulable
        change(w)
        new = w.obj()
        self.nodes[name] = new
        self.cache.update_node(old, new)

    def node_labels(self):
        self._replace(lambda w: w.labels(zone=self.rng.choice(ZONES)))

    def node_taint(self):
        self._replace(lambda w: w.taint("dedicated", "x"))

    def node_unschedulable(self):
        self._replace(
            lambda w: w.unschedulable(not w.node_obj.spec.unschedulable)
        )

    def node_remove(self):
        if len(self.nodes) > 4:
            name = self.rng.choice(sorted(self.nodes))
            self.cache.remove_node(self.nodes.pop(name))

    def csi(self):
        if self.nodes:
            name = self.rng.choice(sorted(self.nodes))
            self.cache.add_csi_node(CSINode(
                metadata=ObjectMeta(name=name),
                drivers=[CSINodeDriver(
                    name="ebs", allocatable_count=self.rng.randrange(1, 9)
                )],
            ))

    def pod_add(self):
        if self.nodes:
            pod = self._pod(self.rng.choice(sorted(self.nodes)))
            self.added[pod.metadata.uid] = pod
            self.cache.add_pod(pod)

    def pod_before_node(self):
        pod = self._pod(self._name("ghost"))
        self.added[pod.metadata.uid] = pod
        self.cache.add_pod(pod)

    def pod_remove(self):
        if self.added:
            uid = self.rng.choice(sorted(self.added))
            self.cache.remove_pod(self.added.pop(uid))

    def assume(self):
        if self.nodes:
            names = sorted(self.nodes)
            pods = sorted(
                (self._pod(self.rng.choice(names)) for _ in range(6)),
                key=lambda p: p.spec.node_name,
            )
            assert not any(self.cache.assume_pods(pods))
            self.assumed.update((p.metadata.uid, p) for p in pods)

    def forget(self):
        if self.assumed:
            uid = self.rng.choice(sorted(self.assumed))
            self.cache.forget_pod(self.assumed.pop(uid))

    def confirm(self):
        if self.assumed:
            uid = self.rng.choice(sorted(self.assumed))
            pod = self.assumed.pop(uid)
            self.added[uid] = pod
            self.cache.add_pod(pod)

    def step(self):
        getattr(self, self.rng.choice(self.KINDS))()


# -- 1. the snapshot refresh --------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_incremental_refresh_equals_the_full_walk(seed):
    """Two snapshots refreshed at different times through the new path
    stay equal, order included, to two refreshed at the same times by
    the full walk; a tensor cache that follows the change log packs what
    a fresh one packs from the twin."""
    churn = Churn(seed)
    rng = random.Random(seed * 7919)
    new_often, new_seldom = Snapshot(), Snapshot()
    twin_often, twin_seldom = Snapshot(), Snapshot()
    follower = NodeTensorCache()
    walks = []
    real_walk = churn.cache._update_snapshot_full
    churn.cache._update_snapshot_full = lambda snapshot: (
        walks.append(snapshot), real_walk(snapshot)
    )
    for step in range(150):
        for _ in range(rng.randrange(1, 4)):
            churn.step()
        assert_generation_order(churn.cache)
        churn.cache.update_snapshot(new_often)
        full_walk_update(churn.cache, twin_often)
        assert_same_snapshot(new_often, twin_often)
        if rng.random() < 0.15:
            churn.cache.update_snapshot(new_seldom)
            full_walk_update(churn.cache, twin_seldom)
            assert_same_snapshot(new_seldom, twin_seldom)
        nt = follower.update(new_often)
        fresh = NodeTensorCache(follower.dims, follower.topology).update(
            twin_often
        )
        assert sorted(n for n in nt.names if n) == sorted(fresh.names)
        for name in fresh.names:
            i, j = nt.row(name), fresh.row(name)
            assert np.array_equal(nt.allocatable[i], fresh.allocatable[j])
            assert np.array_equal(nt.requested[i], fresh.requested[j])
            assert np.array_equal(
                nt.non_zero_requested[i], fresh.non_zero_requested[j]
            )
    # the full walk is for changes of membership, not for every refresh
    assert 0 < len(walks) < 150


def test_a_quiet_refresh_visits_no_node_and_a_busy_one_only_the_changed():
    churn = Churn(11, nodes=40)
    snap = Snapshot()
    churn.cache.update_snapshot(snap)
    assert snap.last_refreshed == 40
    visited = []
    real_clone = type(next(iter(churn.cache._nodes.values()))).clone

    def counting_clone(ni):
        visited.append(ni.node_name)
        return real_clone(ni)

    churn.cache.update_snapshot(snap)
    assert snap.last_refreshed == 0
    names = sorted(churn.nodes)[:3]
    for name in names:
        pod = make_pod(f"q-{name}").uid(f"q-{name}").node(name).container(
            cpu="1"
        ).obj()
        churn.cache.add_pod(pod)
    before = list(snap.node_info_list)
    try:
        type(before[0]).clone = counting_clone
        churn.cache.update_snapshot(snap)
    finally:
        type(before[0]).clone = real_clone
    assert sorted(visited) == names and snap.last_refreshed == 3
    # the list a reader held is not written under it
    assert [ni.requested.milli_cpu for ni in before] == [0] * 40
    assert sum(ni.requested.milli_cpu for ni in snap.node_info_list) == 3000


def test_refreshes_beside_writers_and_readers_lose_nothing():
    """Writers churn pods on every node while one thread refreshes and
    readers walk the list they were handed: more threads than cores, a
    short switch interval. No reader sees a torn list, the generation
    order holds, and the last refresh equals the full walk."""
    cache = SchedulerCache()
    names = [f"s{i}" for i in range(64)]
    for name in names:
        cache.add_node(_node(name, "z0").obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    stop = threading.Event()
    errors = []

    def guarded(body):
        def run():
            try:
                body()
            except Exception as exc:  # noqa: BLE001 - reported below
                errors.append(repr(exc))
                stop.set()
        return run

    def writer(k):
        rng = random.Random(k)
        mine = []
        seq = 0
        while not stop.is_set():
            if mine and rng.random() < 0.45:
                cache.remove_pod(mine.pop(rng.randrange(len(mine))))
            else:
                seq += 1
                pod = make_pod(f"w{k}-{seq}").uid(f"w{k}-{seq}").node(
                    rng.choice(names)).container(cpu="10m").obj()
                mine.append(pod)
                cache.add_pod(pod)

    def refresher():
        while not stop.is_set():
            cache.update_snapshot(snap)

    def reader():
        while not stop.is_set():
            infos = snap.list_node_infos()
            seen = [ni.node_name for ni in infos]
            if seen != names:
                raise AssertionError(f"a torn list: {len(seen)} names")
            for ni in infos:  # each clone is one refresh's, whole
                if ni.requested.milli_cpu != 10 * len(ni.pods):
                    raise AssertionError("a clone written under a reader")

    bodies = [lambda k=k: writer(k) for k in range(8)] + [refresher] \
        + [reader] * 4
    threads = [threading.Thread(target=guarded(b), daemon=True)
               for b in bodies]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=20)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert_generation_order(cache)
    cache.update_snapshot(snap)
    twin = Snapshot()
    full_walk_update(cache, twin)
    assert_same_snapshot(snap, twin)
    assert sum(len(ni.pods) for ni in snap.node_info_list) > 0


def test_a_snapshot_fed_by_another_cache_takes_the_full_walk():
    a, b = Churn(21, nodes=5), Churn(22, nodes=7)
    snap, twin = Snapshot(), Snapshot()
    for cache in (a.cache, b.cache, a.cache):
        cache.update_snapshot(snap)
        full_walk_update(cache, twin)
        assert_same_snapshot(snap, twin)


def test_the_change_log_never_sends_a_steady_reader_to_the_full_walk():
    """One refresh notes each node at most once and the log keeps twice
    the node count: a consumer that reads at every refresh stays on the
    tracked path however many nodes one batch changes (a cap of 4,096
    was what one full batch of a 5,000-node burst hit)."""
    cache = SchedulerCache()
    for i in range(5000):
        cache.add_node(make_node(f"n{i}").capacity(cpu="8", pods=110).obj())
    snap = Snapshot()
    cache.update_snapshot(snap)
    tc = NodeTensorCache()
    tc.update(snap)
    for wave in range(4):
        cache.assume_pods([
            make_pod(f"w{wave}-{i}").uid(f"w{wave}-{i}").node(f"n{i}")
            .container(cpu="10m").obj()
            for i in range(4500)
        ])
        cache.update_snapshot(snap)
        assert snap.last_refreshed == 4500
        cursor = tc._change_cursor
        names, moved, _ = snap.changes_since(cursor)
        assert names is not None and len(names) == 4500 and not moved
        nt = tc.update(snap)
        assert nt.delta.changed_rows.size == 4500
    assert tc.full_repacks == 1
    # a reader that fell a whole log behind is told to walk
    assert snap.changes_since(0)[0] is None


# -- 2. static mask rows -------------------------------------------------------


def _mask_pods():
    return [
        make_pod("plain").container(cpu="100m").obj(),
        make_pod("plain2").container(cpu="200m").obj(),
        make_pod("sel").node_selector(zone="z1").container(cpu="100m").obj(),
        make_pod("tol").toleration("dedicated", "x", effect="NoSchedule")
        .container(cpu="100m").obj(),
        make_pod("aff").node_affinity_in("zone", ["z0", "z2"])
        .container(cpu="100m").obj(),
        make_pod("port").container(cpu="100m", host_port=8080).obj(),
    ]


def _mask_cluster():
    cache = SchedulerCache()
    nodes = {}
    for i in range(9):
        nodes[f"m{i}"] = _node(f"m{i}", ZONES[i % 3]).obj()
        cache.add_node(nodes[f"m{i}"])
    return cache, nodes


def _relabel(cache, nodes, name, change):
    old = nodes[name]
    w = _node(name, old.metadata.labels["zone"])
    change(w)
    nodes[name] = w.obj()
    cache.update_node(old, nodes[name])


NODE_CHANGES = {
    "node_add": lambda c, n: c.add_node(_node("m-new", "z1").obj()),
    "node_labels": lambda c, n: _relabel(
        c, n, "m1", lambda w: w.labels(zone="z2")),
    "node_taint": lambda c, n: _relabel(
        c, n, "m4", lambda w: w.taint("dedicated", "x")),
    "node_unschedulable": lambda c, n: _relabel(
        c, n, "m4", lambda w: w.unschedulable()),
    "node_remove": lambda c, n: c.remove_node(n["m7"]),
    "node_remove_then_add": lambda c, n: (
        c.remove_node(n["m7"]), c.add_node(_node("m7", "z0").obj())),
    "schema_growth": lambda c, n: c.add_node(
        _node("m-gpu", "z1", example__com__gpu=2).obj()),
}


@pytest.mark.parametrize("kind", sorted(NODE_CHANGES))
def test_reused_mask_rows_equal_fresh_ones_after(kind):
    cache, nodes = _mask_cluster()
    snap = Snapshot()
    tc = NodeTensorCache()
    kept = MaskRowCache()
    pods = _mask_pods()

    def both():
        cache.update_snapshot(snap)
        nt = tc.update(snap)
        got = static_mask_compact(pods, snap, nt, kept)
        want = static_mask_twin(pods, snap, nt)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        assert got[0].flags.writeable  # kept rows are copied out

    both()
    assert (kept.rows_built, kept.rows_reused) == (5, 0)
    # pods come and go: node objects stand, four rows are handed out
    # again and the host-port row, which reads used_ports, is rebuilt
    cache.add_pod(
        make_pod("squatter").uid("sq").node("m2")
        .container(cpu="1", host_port=8080).obj()
    )
    both()
    assert (kept.rows_built, kept.rows_reused) == (6, 4)
    assert len(kept) == 4
    NODE_CHANGES[kind](cache, nodes)
    both()
    assert (kept.rows_built, kept.rows_reused) == (11, 4)
    both()
    assert (kept.rows_built, kept.rows_reused) == (12, 8)


def _heartbeat(node):
    ready = [c for c in node.status.conditions if c.type == "Ready"]
    if ready:
        ready[0].status = "Unknown" if ready[0].status == "True" else "True"
    else:
        node.status.conditions.append(NodeCondition("Ready", "True"))


def _halve_cpu(node):
    node.status.allocatable["cpu"] //= 2


@pytest.mark.parametrize("write", [_heartbeat, _halve_cpu])
def test_a_status_write_keeps_the_rows_and_the_facts(write):
    """A kubelet's status write replaces every Node object and touches
    nothing a mask row or the score packer's facts read: the rows and
    the facts stand, the tensor's rows follow the allocatable."""
    cache, nodes = _mask_cluster()
    snap = Snapshot()
    tc = NodeTensorCache()
    kept = MaskRowCache()
    pods = [p for p in _mask_pods() if not pod_host_ports(p)]

    def both():
        cache.update_snapshot(snap)
        nt = tc.update(snap)
        got = static_mask_compact(pods, snap, nt, kept)
        want = static_mask_twin(pods, snap, nt)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        assert pack_score_batch(pods, snap, nt, None, WEIGHTS) is None
        fresh = NodeTensorCache().update(snap)
        assert np.array_equal(
            nt.allocatable[: len(nodes)], fresh.allocatable[: len(nodes)])
        return nt.allocatable[: len(nodes)].copy()

    before = both()
    epoch, facts = snap.node_spec_epoch, snap.score_facts
    assert (kept.rows_built, kept.rows_reused) == (4, 0)
    for round_ in range(3):
        for name in list(nodes):
            old = nodes[name]
            nodes[name] = old.deepcopy()
            write(nodes[name])
            cache.update_node(old, nodes[name])
        after = both()
        assert snap.last_refreshed == len(nodes)
        assert snap.node_spec_epoch == epoch and snap.score_facts is facts
        assert (kept.rows_built, kept.rows_reused) == (4, 4 * (round_ + 1))
    assert (write is _halve_cpu) == (not np.array_equal(before, after))
    # the same object handed in again may have been edited where it
    # stands: that cannot be told from a status write, and keeps nothing
    nodes["m1"].metadata.labels["zone"] = "z2"
    cache.update_node(nodes["m1"], nodes["m1"])
    both()
    assert snap.node_spec_epoch != epoch
    assert (kept.rows_built, kept.rows_reused) == (8, 12)


def test_a_host_port_signature_is_never_kept_and_the_rest_are_bounded():
    cache, _nodes = _mask_cluster()
    snap = Snapshot()
    cache.update_snapshot(snap)
    nt = NodeTensorCache().update(snap)
    kept = MaskRowCache()
    ports = [
        make_pod(f"hp{i}").container(cpu="1", host_port=9000 + i).obj()
        for i in range(3)
    ]
    for _ in range(2):
        static_mask_compact(ports, snap, nt, kept)
    assert (kept.rows_built, kept.rows_reused, len(kept)) == (6, 0, 0)
    many = [
        make_pod(f"s{i}").node_selector(zone=f"z{i}").container(cpu="1").obj()
        for i in range(host_masks.MASK_ROWS_KEPT + 10)
    ]
    static_mask_compact(many, snap, nt, kept)
    assert len(kept) == host_masks.MASK_ROWS_KEPT
    with pytest.raises(ValueError):
        next(iter(kept._rows.values()))[0] = True  # read-only
    # a snapshot no cache feeds, and another cache's tensor, keep nothing
    foreign = new_snapshot([], [_node("f0", "z0").obj()])
    static_mask_compact(many[:2], foreign, NodeTensorCache().update(foreign),
                        kept)
    assert len(kept) == 0
    static_mask_compact(many[:2], snap, nt, kept)
    assert len(kept) == 2
    static_mask_compact(many[:2], snap, NodeTensorCache().update(snap), kept)
    assert kept.rows_reused == 0


# -- 3. the one-write row repack -----------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_write_repack_equals_row_by_row(seed):
    """Tracked updates, membership changes and full repacks of a cluster
    with scalar resources, CSI volume limits, in-use volume counts and
    topology keys: every array equal to the row-by-row twin's."""
    churn = Churn(seed + 100)
    snap_new, snap_twin = Snapshot(), Snapshot()
    new, twin = NodeTensorCache(), RowByRowTensorCache()
    for tc in (new, twin):
        tc.topology.register_key("zone")
        tc.topology.register_key("kubernetes.io/hostname")
    vol = CSI_ATTACH_PREFIX + "ebs"
    in_use = limits = 0
    for step in range(80):
        for _ in range(3):
            churn.step()
        if step % 9 == 0 and churn.nodes:  # a pod that uses a CSI volume
            name = sorted(churn.nodes)[0]
            pod = make_pod(f"v{step}").uid(f"v{step}").node(name).container(
                cpu="100m").obj()
            pod.__dict__["_volcount_memo"] = ((vol, 2),)
            churn.added[pod.metadata.uid] = pod
            churn.cache.add_pod(pod)
        churn.cache.update_snapshot(snap_new)
        full_walk_update(churn.cache, snap_twin)
        a, b = new.update(snap_new), twin.update(snap_twin)
        assert a.names == b.names
        for field in ("allocatable", "requested", "non_zero_requested",
                      "valid", "topology"):
            assert np.array_equal(getattr(a, field), getattr(b, field)), field
        assert new._generations == twin._generations
        assert np.array_equal(new._row_epoch, twin._row_epoch)
        assert np.array_equal(a.delta.changed_rows, b.delta.changed_rows)
        col = new.dims.existing_column(vol)
        if col is not None:
            in_use += int(a.requested[:, col].sum())
            limits += int((a.allocatable[:, col] < 16).sum())
    assert new.rows_repacked == twin.rows_repacked
    # the volume columns were exercised: counts in use, CSINode limits
    assert new.dims.volume_columns() and in_use > 0 and limits > 0


# -- 4. the score packer's head -------------------------------------------------


def _gain(kind, w):
    if kind == "image":
        return w.image("pause", 500 * 1024 * 1024)
    if kind == "soft_taint":
        return w.taint("flaky", "y", effect="PreferNoSchedule")
    w.node_obj.metadata.annotations[AVOID_ANNOTATION] = "{}"
    return w


WEIGHTS = {"ImageLocality": 1, "NodePreferAvoidPods": 10000,
           "TaintToleration": 1, "NodeAffinity": 1, "InterPodAffinity": 1}


@pytest.mark.parametrize("kind", ["image", "soft_taint", "avoid"])
def test_score_pack_sees_what_a_node_gains(kind):
    cache, nodes = _mask_cluster()
    snap = Snapshot()
    tc = NodeTensorCache()
    pods = [make_pod(f"s{i}").container(cpu="100m").obj() for i in range(5)]

    def packed():
        cache.update_snapshot(snap)
        nt = tc.update(snap)
        # a snapshot no cache feeds sweeps its nodes at every call
        foreign = new_snapshot(
            [], [ni.node for ni in snap.node_info_list]
        )
        want = pack_score_batch(
            pods, foreign, NodeTensorCache().update(foreign), None, WEIGHTS
        )
        return pack_score_batch(pods, snap, nt, None, WEIGHTS), want

    got, want = packed()
    assert got is None and want is None
    assert packed()[0] is None  # from the kept facts
    _relabel(cache, nodes, "m3", lambda w: _gain(kind, w))
    got, want = packed()
    assert got is not None and want is not None
    for name in ("direct_rows", "nodeaff_rows", "taint_rows", "pod_sig",
                 "weights"):
        assert np.array_equal(getattr(got, name), getattr(want, name)), name
    assert got.direct_rows.any() or got.taint_rows.any()


# -- 5. the arrays handed to solve_packed ---------------------------------------


def _batches(kind, seed):
    rng = random.Random(seed)
    if kind == "plain":
        return [[make_pod(f"pl-{w}-{i}").container(
            cpu="250m", memory="512Mi") for i in range(48)]
            for w in range(3)]
    if kind == "node_selector":
        out = []
        for w in range(3):
            pods = [make_pod(f"ns-{w}-{i}").container(
                cpu="250m", memory="512Mi") for i in range(40)]
            for i, p in enumerate(pods):
                if i % 3:
                    p.node_selector(zone=ZONES[i % 3 - 1])
            rng.shuffle(pods)
            out.append(pods)
        return out
    out = []
    for w in range(3):
        pods = []
        for app in range(3):
            pods += [
                make_pod(f"sp-{w}-{app}-{i}").labels(app=f"sp{w}{app}")
                .spread_constraint(1, "zone", match_labels={
                    "app": f"sp{w}{app}"})
                .container(cpu="100m", memory="128Mi") for i in range(9)
            ]
        for app in range(2):
            pods += [
                make_pod(f"an-{w}-{app}-{i}").labels(app=f"an{w}{app}")
                .pod_affinity("kubernetes.io/hostname",
                              {"app": f"an{w}{app}"}, anti=True)
                .container(cpu="100m", memory="128Mi") for i in range(5)
            ]
        rng.shuffle(pods)
        out.append(pods)
    return out


def _uploads(kind, seed, monkeypatch, old_path):
    """Every piece list handed to ``solve_packed`` while three seeded
    batches of ``kind`` are dispatched one after another."""
    if old_path:
        monkeypatch.setattr(
            SchedulerCache, "update_snapshot", full_walk_update
        )
        monkeypatch.setattr(batch_mod, "static_mask_compact", static_mask_twin)
        monkeypatch.setattr(batch_mod, "pack_score_batch", score_pack_twin)
    seen = []
    real_solve = batch_mod.solve_packed

    def recording_solve(pieces, *args, **kwargs):
        seen.append([
            (name, arr if isinstance(arr, ConstPiece) else np.array(arr))
            for name, arr in pieces
        ])
        return real_solve(pieces, *args, **kwargs)

    monkeypatch.setattr(batch_mod, "solve_packed", recording_solve)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=64)
    if old_path:
        sched.tensor_cache = RowByRowTensorCache()
    try:
        for i in range(12):
            client.create_node(_node(f"u{i}", ZONES[i % 3]).obj())
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        bound = 0
        for wave in _batches(kind, seed):
            client.create_pods_bulk([w.obj() for w in wave])
            deadline = time.time() + 30
            while len(sched.queue.pending_pods()) < len(wave):
                assert time.time() < deadline, "the wave never queued"
                time.sleep(0.01)
            assert sched.schedule_batch(timeout=1.0) == len(wave)
            bound += len(wave)
            sched.wait_for_inflight_binds()
            while time.time() < deadline:  # every bind echoed and mirrored
                pods, _ = client.list_pods()
                if (
                    sum(1 for p in pods if p.spec.node_name) == bound
                    and not sched.cache._assumed_pods
                ):
                    break
                time.sleep(0.01)
            else:
                raise AssertionError("the wave never bound")
            time.sleep(0.05)
        assert sched.pods_fallback == 0
        rows_reused = sched.mask_row_cache.rows_reused
    finally:
        sched.stop()
        informers.stop()
        monkeypatch.undo()
    return seen, rows_reused


@pytest.mark.parametrize("kind", ["plain", "node_selector", "spread_anti"])
def test_solve_packed_receives_the_same_arrays(kind, monkeypatch):
    new, reused = _uploads(kind, 1234, monkeypatch, old_path=False)
    old, _ = _uploads(kind, 1234, monkeypatch, old_path=True)
    assert len(new) == len(old) >= 3
    assert reused > 0  # the new path did hand rows out again
    for got, want in zip(new, old):
        assert [name for name, _ in got] == [name for name, _ in want]
        for (name, a), (_name, b) in zip(got, want):
            if isinstance(a, ConstPiece):
                assert (a.shape, a.kind) == (b.shape, b.kind), name
            else:
                assert a.dtype == b.dtype and np.array_equal(a, b), name
    if kind == "spread_anti":
        assert any(name.startswith("sp") for name, _ in new[0])
