"""The refresh of a node that only gained or lost pods: its fixed parts
shared with the clone, its requested columns alone repacked, in native
loops over the changed nodes.

(a) after any sequence of events the node tensor's rows equal a full
    pack's, array for array, with the same ``changed_rows``;
(b) native ``node_info_clones`` / ``node_rows_gather`` against their
    Python twins on the same inputs;
(c) the parts a clone shares with the cache's NodeInfo leak no write
    into a snapshot, and the writers of a snapshot's NodeInfo copy
    first.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import CSINode, CSINodeDriver, ObjectMeta
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.node_info import (
    CSI_ATTACH_PREFIX,
    NodeInfo,
    node_info_clones_py,
)
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.tensors.node_tensor import (
    NodeTensorCache,
    _node_rows_gather_py,
)
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics

native = pytest.importorskip("kubernetes_tpu.native")
if native.hotpath is None:  # pragma: no cover - build failure environment
    pytest.skip("native module unavailable", allow_module_level=True)

ZONES = ("z0", "z1", "z2")
VOLUME = CSI_ATTACH_PREFIX + "ebs"
GPU = "example.com/gpu"
FIELDS = ("allocatable", "requested", "non_zero_requested", "valid",
          "topology")


@pytest.fixture(params=["native", "twin"])
def walk(request, monkeypatch):
    """Both walks of the refresh as the extension has them, or as their
    Python twins."""
    if request.param == "twin":
        monkeypatch.setenv("KTPU_NATIVE_INGEST", "0")
    return request.param


# -- (a) the rows after any sequence of events -------------------------------


class WholeRowTensorCache(NodeTensorCache):
    """The node tensor as it was before a row could be pods-only: every
    changed row is written whole."""

    def _gather_rows(self, rows, infos):
        ints, _full, extras, odd = super()._gather_rows(rows, infos)
        return ints, list(range(len(rows))), extras, odd


class Events:
    """Seeded events on one cache: pods added, removed, assumed,
    confirmed and forgotten; nodes re-labelled, tainted, resized,
    deleted and joined again under their names; CSINodes written."""

    KINDS = (
        "pod_add", "pod_add", "pod_add", "pod_remove", "pod_remove",
        "assume", "assume", "forget", "confirm", "node_labels",
        "node_taint", "node_allocatable", "node_same_object", "csi",
        "csi_remove", "node_delete", "node_rejoin", "pod_before_node",
    )

    def __init__(self, seed, *, scalars, volumes, nodes=10):
        self.rng = random.Random(seed)
        self.scalars = scalars
        self.volumes = volumes
        self.cache = SchedulerCache()
        self.nodes = {}
        self.gone = []
        self.added = {}
        self.assumed = {}
        self.seq = 0
        for i in range(nodes):
            self._join(f"n-{i}")

    def _wrapper(self, name, zone, cpu=32):
        w = make_node(name).labels(
            zone=zone, **{"kubernetes.io/hostname": name}
        )
        extra = {"example.com/gpu": 8} if self.scalars else {}
        return w.capacity(cpu=str(cpu), memory="64Gi", pods=110, **extra)

    def _join(self, name):
        node = self._wrapper(name, self.rng.choice(ZONES)).obj()
        self.nodes[name] = node
        self.cache.add_node(node)

    def _pod(self, node_name):
        self.seq += 1
        name = f"p-{self.seq}"
        w = make_pod(name).uid(name).node(node_name).labels(app="a")
        kind = self.rng.randrange(5)
        if kind == 0:
            w = w.pod_affinity(
                "kubernetes.io/hostname", {"app": "a"}, anti=True
            )
        if kind == 1:
            pod = w.container(
                cpu="100m", memory="64Mi",
                host_port=8000 + self.rng.randrange(40),
            ).obj()
        elif kind == 2 and self.scalars:
            pod = w.container(
                cpu="250m", memory="1000", **{"example.com/gpu": 1}
            ).obj()
        elif kind == 3 and self.scalars:
            # a resource no node advertises: a name the dims learn late
            pod = w.container(cpu="50m", **{"example.com/late": 2}).obj()
        else:
            pod = w.container(cpu="250m", memory="512Mi").obj()
        if self.volumes and self.rng.randrange(3) == 0:
            pod.__dict__["_volcount_memo"] = ((VOLUME, 1),)
        return pod

    def _replace(self, change):
        name = self.rng.choice(sorted(self.nodes))
        old = self.nodes[name]
        w = self._wrapper(name, old.metadata.labels["zone"])
        w.node_obj.spec.taints = list(old.spec.taints)
        change(w)
        new = self.nodes[name] = w.obj()
        self.cache.update_node(old, new)

    def step(self):
        getattr(self, self.rng.choice(self.KINDS))()

    def pod_add(self):
        pod = self._pod(self.rng.choice(sorted(self.nodes)))
        self.added[pod.metadata.uid] = pod
        self.cache.add_pod(pod)

    def pod_before_node(self):
        self.seq += 1
        pod = self._pod(f"ghost-{self.seq}")
        self.added[pod.metadata.uid] = pod
        self.cache.add_pod(pod)

    def pod_remove(self):
        if self.added:
            uid = self.rng.choice(sorted(self.added))
            self.cache.remove_pod(self.added.pop(uid))

    def assume(self):
        pod = self._pod(self.rng.choice(sorted(self.nodes)))
        self.assumed[pod.metadata.uid] = pod
        self.cache.assume_pods([pod])

    def forget(self):
        if self.assumed:
            uid = self.rng.choice(sorted(self.assumed))
            self.cache.forget_pod(self.assumed.pop(uid))

    def confirm(self):
        if self.assumed:
            uid = self.rng.choice(sorted(self.assumed))
            pod = self.assumed.pop(uid)
            self.cache.add_pod(pod)
            self.added[uid] = pod

    def node_labels(self):
        self._replace(lambda w: w.labels(zone=self.rng.choice(ZONES)))

    def node_taint(self):
        self._replace(lambda w: w.taint("dedicated", "x"))

    def node_allocatable(self):
        cpu = self.rng.choice((8, 16, 48))
        name = self.rng.choice(sorted(self.nodes))
        old = self.nodes[name]
        new = self.nodes[name] = self._wrapper(
            name, old.metadata.labels["zone"], cpu=cpu
        ).obj()
        self.cache.update_node(old, new)

    def node_same_object(self):
        """The node object edited where it stands and handed in again:
        ``set_node`` still replaces the allocatable, so the row is
        written whole."""
        name = self.rng.choice(sorted(self.nodes))
        node = self.nodes[name]
        node.metadata.labels["zone"] = self.rng.choice(ZONES)
        self.cache.update_node(node, node)

    def csi(self):
        name = self.rng.choice(sorted(self.nodes))
        self.cache.add_csi_node(CSINode(
            metadata=ObjectMeta(name=name),
            drivers=[CSINodeDriver(
                name="ebs", allocatable_count=self.rng.randrange(1, 9)
            )],
        ))

    def csi_remove(self):
        name = self.rng.choice(sorted(self.nodes))
        self.cache.remove_csi_node(CSINode(metadata=ObjectMeta(name=name)))

    def node_delete(self):
        if len(self.nodes) > 4:
            name = self.rng.choice(sorted(self.nodes))
            self.cache.remove_node(self.nodes.pop(name))
            self.gone.append(name)

    def node_rejoin(self):
        if self.gone:
            self._join(self.gone.pop(self.rng.randrange(len(self.gone))))


def _same_tensor(a, b):
    assert a.names == b.names
    for field in FIELDS:
        assert np.array_equal(getattr(a, field), getattr(b, field)), field
    assert np.array_equal(a.delta.changed_rows, b.delta.changed_rows)
    assert np.array_equal(a.delta.membership_rows, b.delta.membership_rows)
    assert (a.delta.epoch, a.delta.layout_epoch, a.delta.full) == (
        b.delta.epoch, b.delta.layout_epoch, b.delta.full)


def _same_rows_by_name(nt, fresh):
    assert sorted(n for n in nt.names if n) == sorted(fresh.names)
    for name in fresh.names:
        i, j = nt.row(name), fresh.row(name)
        for field in FIELDS:
            assert np.array_equal(
                getattr(nt, field)[i], getattr(fresh, field)[j]
            ), (name, field)
    assert int(nt.valid.sum()) == len(fresh.names)


@pytest.mark.parametrize("seed", [3, 17, 2147544001])
@pytest.mark.parametrize("scalars", [False, True], ids=["fixed", "scalars"])
@pytest.mark.parametrize("volumes", [False, True], ids=["novol", "volumes"])
@pytest.mark.parametrize("topology", [False, True], ids=["notopo", "topo"])
def test_rows_after_any_sequence_equal_the_full_packs(
    seed, scalars, volumes, topology, walk
):
    events = Events(seed, scalars=scalars, volumes=volumes)
    snap = Snapshot()
    new, whole = NodeTensorCache(), WholeRowTensorCache()
    if topology:
        for tc in (new, whole):
            tc.topology.register_key("zone")
            tc.topology.register_key("kubernetes.io/hostname")
    pods_only = rows = 0
    for _step in range(70):
        for _ in range(events.rng.randrange(1, 5)):
            events.step()
        events.cache.update_snapshot(snap)
        assert 0 <= snap.last_shared <= snap.last_refreshed
        a, b = new.update(snap), whole.update(snap)
        _same_tensor(a, b)
        assert new._generations == whole._generations
        assert np.array_equal(new._row_epoch, whole._row_epoch)
        assert b.delta.pods_only_rows == 0
        assert 0 <= a.delta.pods_only_rows <= a.delta.changed_rows.size
        pods_only += a.delta.pods_only_rows
        rows += int(a.delta.changed_rows.size)
        # a cache that has seen nothing packs the same snapshot whole
        fresh = NodeTensorCache(new.dims, new.topology).update(snap)
        assert fresh.delta.full and fresh.delta.pods_only_rows == 0
        _same_rows_by_name(a, fresh)
        # the slots remember what they were packed from
        for name, i in new._row_of.items():
            ni = snap.node_info_map[name]
            assert new._row_node[i] is ni.node
            assert new._row_alloc[i] is ni.allocatable
            assert new._row_csi[i] is ni.csi_volume_limits
        for i in new._free_rows:
            assert new._row_node[i] is new._row_alloc[i] is None
    # the sequence exercised both kinds of row
    assert 0 < pods_only < rows


def test_a_pod_event_is_a_pods_only_row_and_a_node_write_a_whole_one(walk):
    cache = SchedulerCache()
    nodes = {
        f"n{i}": make_node(f"n{i}").labels(zone="z0").capacity(
            cpu="8", memory="16Gi", pods=110).obj()
        for i in range(6)
    }
    for node in nodes.values():
        cache.add_node(node)
    snap = Snapshot()
    tc = NodeTensorCache()
    tc.topology.register_key("zone")
    cache.update_snapshot(snap)
    first = tc.update(snap)
    assert (first.delta.full, first.delta.pods_only_rows) == (True, 0)
    assert snap.refresh_stats() == {"nodes_refreshed": 6, "nodes_shared": 0}
    for i in (1, 3, 4):
        cache.add_pod(make_pod(f"p{i}").uid(f"p{i}").node(f"n{i}").container(
            cpu="1", memory="1Gi").obj())
    cache.update_snapshot(snap)
    assert snap.refresh_stats() == {"nodes_refreshed": 3, "nodes_shared": 3}
    nt = tc.update(snap)
    assert nt.delta.row_stats() == {"rows": 3, "rows_pods_only": 3}
    assert nt.requested[nt.row("n3")].tolist()[:4] == [1000, 1 << 20, 0, 1]
    # a label write on n3, a bind on n4: n3's row is whole, n4's not
    relabelled = make_node("n3").labels(zone="z1").capacity(
        cpu="8", memory="16Gi", pods=110).obj()
    cache.update_node(nodes["n3"], relabelled)
    cache.add_pod(make_pod("q4").uid("q4").node("n4").container(
        cpu="1").obj())
    zone_before = int(nt.topology[nt.row("n3"), 0])
    cache.update_snapshot(snap)
    assert snap.refresh_stats() == {"nodes_refreshed": 2, "nodes_shared": 1}
    nt = tc.update(snap)
    assert nt.delta.row_stats() == {"rows": 2, "rows_pods_only": 1}
    assert int(nt.topology[nt.row("n3"), 0]) != zone_before
    # a CSINode write alone: the volume limits are another object
    cache.add_csi_node(CSINode(
        metadata=ObjectMeta(name="n1"),
        drivers=[CSINodeDriver(name="ebs", allocatable_count=3)],
    ))
    cache.update_snapshot(snap)
    assert snap.refresh_stats() == {"nodes_refreshed": 1, "nodes_shared": 0}
    nt = tc.update(snap)
    assert nt.delta.full  # a volume column joined the schema
    col = tc.dims.existing_column(VOLUME)
    assert nt.allocatable[nt.row("n1"), col] == 3
    # deleted and joined again between two packs: the slot keeps its
    # name, and the new node object makes the row a whole one
    cache.remove_node(relabelled)
    smaller = make_node("n3").labels(zone="z2").capacity(
        cpu="4", memory="16Gi", pods=110).obj()
    cache.add_node(smaller)
    cache.update_snapshot(snap)
    nt = tc.update(snap)
    assert nt.delta.row_stats() == {"rows": 1, "rows_pods_only": 0}
    assert nt.allocatable[nt.row("n3"), 0] == 4000


def test_a_noted_node_at_its_rows_generation_is_left_out_of_the_pack(walk):
    """The change log can name a node its row was already packed from;
    the rows that did move keep their kind (whole, with a named
    resource) whatever their place among the names."""
    cache = SchedulerCache()
    nodes = {
        f"n{i}": make_node(f"n{i}").capacity(
            cpu="8", memory="16Gi", pods=110, **{GPU: 8}).obj()
        for i in range(5)
    }
    for node in nodes.values():
        cache.add_node(node)
    snap, tc = Snapshot(), NodeTensorCache()
    cache.update_snapshot(snap)
    tc.update(snap)
    cache.update_node(nodes["n3"], make_node("n3").capacity(
        cpu="4", memory="16Gi", pods=110, **{GPU: 8}).obj())
    cache.add_pod(make_pod("g").uid("g").node("n4").container(
        cpu="1", **{GPU: 2}).obj())
    cache.add_pod(make_pod("p").uid("p").node("n1").container(cpu="1").obj())
    cache.update_snapshot(snap)
    for name in ("n0", "n2"):
        snap.note_changed(name)
    nt = tc.update(snap)
    assert nt.delta.row_stats() == {"rows": 3, "rows_pods_only": 2}
    assert sorted(nt.delta.changed_rows.tolist()) == sorted(
        nt.row(name) for name in ("n1", "n3", "n4"))
    _same_rows_by_name(nt, NodeTensorCache(tc.dims, tc.topology).update(snap))
    assert nt.requested[nt.row("n4"), tc.dims.existing_column(GPU)] == 2


def test_a_pods_only_row_that_brings_a_new_resource_name_grows_the_schema(
    walk,
):
    """``requested.scalar`` of a pods-only row holds a name the dims do
    not know: the columns are registered and the tensor packed anew."""
    cache = SchedulerCache()
    for i in range(3):
        cache.add_node(make_node(f"n{i}").capacity(
            cpu="8", memory="16Gi", pods=110).obj())
    snap, tc = Snapshot(), NodeTensorCache()
    cache.update_snapshot(snap)
    assert tc.update(snap).allocatable.shape[1] == 4
    cache.add_pod(make_pod("g").uid("g").node("n1").container(
        cpu="1", **{"example.com/late": 2}).obj())
    late = make_pod("v").uid("v").node("n2").container(cpu="1").obj()
    late.__dict__["_volcount_memo"] = ((VOLUME, 2),)
    cache.add_pod(late)
    cache.update_snapshot(snap)
    assert snap.last_shared == 2
    nt = tc.update(snap)
    assert nt.delta.full and nt.requested.shape[1] == 6
    assert nt.requested[nt.row("n1"), tc.dims.column("example.com/late")] == 2
    assert nt.requested[nt.row("n2"), tc.dims.existing_column(VOLUME)] == 2
    # known names since: the same rows are pods-only, their columns kept
    cache.add_pod(make_pod("g2").uid("g2").node("n1").container(
        cpu="1", **{"example.com/late": 3}).obj())
    cache.update_snapshot(snap)
    nt = tc.update(snap)
    assert nt.delta.row_stats() == {"rows": 1, "rows_pods_only": 1}
    assert nt.requested[nt.row("n1"), tc.dims.column("example.com/late")] == 5


# -- (b) the native walks against their twins --------------------------------


def _node_infos(seed, count=24):
    """NodeInfos of every shape the walks read: with and without a node
    object, pods, affinity pods, ports, scalars, volumes, CSI limits."""
    rng = random.Random(seed)
    infos = []
    for i in range(count):
        extra = {"example.com/gpu": 8} if rng.randrange(2) else {}
        node = make_node(f"n{i}").capacity(
            cpu=str(rng.choice((4, 16, 64))),
            memory=f"{rng.choice((3, 16, 333))}Gi",
            pods=110, **extra).obj()
        ni = NodeInfo(node if rng.randrange(8) else None)
        for j in range(rng.randrange(4)):
            w = make_pod(f"p{i}-{j}").uid(f"p{i}-{j}").node(f"n{i}")
            if rng.randrange(3) == 0:
                w = w.labels(app="a").pod_affinity(
                    "kubernetes.io/hostname", {"app": "a"}, anti=True)
            scalars = {"example.com/gpu": 1} if rng.randrange(3) == 0 else {}
            port = {"host_port": 9000 + j} if rng.randrange(3) == 0 else {}
            pod = w.container(
                cpu=f"{rng.randrange(1, 900)}m",
                memory=str(rng.randrange(1, 1 << 31)),
                **scalars, **port).obj()
            if rng.randrange(4) == 0:
                pod.__dict__["_volcount_memo"] = ((VOLUME, 1),)
            ni.add_pod(pod)
        if rng.randrange(4) == 0:
            ni.set_csi_node(CSINode(
                metadata=ObjectMeta(name=f"n{i}"),
                drivers=[CSINodeDriver(name="ebs", allocatable_count=5)],
            ))
        infos.append(ni)
    return infos


def _clone_shape(ni, clone):
    """What a clone is to its source: equal in every part, sharing the
    four fixed ones and none of those a pod event moves."""
    assert type(clone) is NodeInfo and clone is not ni
    assert all(hasattr(clone, part) for part in NodeInfo.__slots__)
    for part in ("node", "allocatable", "image_states", "csi_volume_limits",
                 "generation"):
        assert getattr(clone, part) is getattr(ni, part), part
    for part in ("pods", "pods_with_affinity", "volume_in_use"):
        assert getattr(clone, part) == getattr(ni, part), part
        assert getattr(clone, part) is not getattr(ni, part), part
    assert [id(p) for p in clone.pods] == [id(p) for p in ni.pods]
    assert clone.used_ports is not ni.used_ports
    assert clone.used_ports.ports == ni.used_ports.ports
    assert clone.used_ports.ports is not ni.used_ports.ports
    for part in ("requested", "non_zero_requested"):
        mine, theirs = getattr(clone, part), getattr(ni, part)
        assert mine == theirs and mine is not theirs, part
        assert mine.scalar is not theirs.scalar, part
    return True


@pytest.mark.parametrize("seed", [1, 2, 2147544002])
@pytest.mark.parametrize(
    "fn", [native.hotpath.node_info_clones, node_info_clones_py],
    ids=["native", "twin"],
)
def test_node_info_clones_matches_clone_and_counts_the_pairs(seed, fn):
    infos = _node_infos(seed)
    # predecessors: an older clone (shares all four), a clone of a node
    # written since (shares none), none at all, and one that lost or
    # gained its node object
    rng = random.Random(seed)
    prevs, shared, transitions = [], 0, 0
    for ni in infos:
        kind = rng.randrange(4)
        if kind == 0:
            prevs.append(None)
            continue
        prev = ni.clone()
        if kind == 1 and ni.node is not None:
            prev.set_node(ni.node)  # another allocatable, other images
        elif kind == 2:
            prev.node = None if prev.node is not None else make_node(
                "other").obj()
            transitions += 1
        else:
            shared += 1
        prevs.append(prev)
    clones, got_shared, affinity, got_transitions = fn(infos, prevs)
    assert len(clones) == len(infos)
    assert all(_clone_shape(ni, c) for ni, c in zip(infos, clones))
    assert (got_shared, got_transitions) == (shared, transitions)
    assert affinity is any(
        ni.pods_with_affinity for ni in infos
    ) or affinity is True
    # a clone's copies are its own: the source moves, the clone stands
    before = [(c.requested.milli_cpu, len(c.pods)) for c in clones]
    for ni in infos:
        ni.add_pod(make_pod("late").uid(f"late-{id(ni)}").container(
            cpu="1", host_port=7777, **{"example.com/gpu": 1}).obj())
    assert [(c.requested.milli_cpu, len(c.pods)) for c in clones] == before
    assert all(("0.0.0.0", "TCP", 7777) not in c.used_ports.ports
               for c in clones)


def test_both_clone_walks_agree_on_the_same_inputs():
    infos = _node_infos(5)
    prevs = [ni.clone() if i % 3 else None for i, ni in enumerate(infos)]
    prevs[1].pods_with_affinity = [infos[1].pods[0]] if infos[1].pods else []
    a = native.hotpath.node_info_clones(infos, prevs)
    b = node_info_clones_py(infos, prevs)
    assert a[1:] == b[1:]
    for x, y in zip(a[0], b[0]):
        assert _clone_shape(y, x)  # two clones of one source: each other's
        assert x.requested == y.requested and x.pods == y.pods
        assert x.used_ports.ports == y.used_ports.ports
    # affinity is read off the predecessors too
    quiet = [ni for ni in infos if not ni.pods_with_affinity][:2]
    loud = quiet[0].clone()
    loud.pods_with_affinity = [make_pod("x").obj()]
    for fn in (native.hotpath.node_info_clones, node_info_clones_py):
        assert fn(quiet, [None, None])[2] is False
        assert fn(quiet, [loud, None])[2] is True
    with pytest.raises(ValueError):
        native.hotpath.node_info_clones(infos, prevs[:-1])


@pytest.mark.parametrize("seed", [1, 2, 2147544003])
def test_node_rows_gather_matches_its_twin(seed):
    infos = _node_infos(seed)
    rng = random.Random(seed)
    n = len(infos)
    rows = rng.sample(range(n + 5), n)
    slots = n + 5
    generations = [0] * slots
    row_node, row_alloc, row_csi = ([None] * slots for _ in range(3))
    for k, (i, ni) in enumerate(zip(rows, infos)):
        kind = rng.randrange(4)
        if kind:  # the slot was packed from this node's objects
            row_node[i], row_alloc[i] = ni.node, ni.allocatable
            row_csi[i] = ni.csi_volume_limits
        if kind == 2:
            row_alloc[i] = ni.allocatable.clone()  # the node resized
        if kind == 3:
            generations[i] = ni.generation  # nothing moved since
    ints_a = np.zeros((n, 10), dtype=np.int32)
    ints_b = np.zeros((n, 10), dtype=np.int32)
    args = (infos, rows, generations, row_node, row_alloc, row_csi)
    a = native.hotpath.node_rows_gather(*args, ints_a)
    b = _node_rows_gather_py(*args, ints_b)
    assert a == b
    assert np.array_equal(ints_a, ints_b)
    full, extras, odd = a
    assert full and extras and odd and len(full) < n
    for k, ni in enumerate(infos):
        assert (k in extras) == bool(
            ni.requested.scalar or ni.volume_in_use)
        assert (k in odd) == (
            ni.node is None or generations[rows[k]] == ni.generation)
    # bytes are floored for what a node offers and ceiled for what its
    # pods ask, as the tensor's units have it
    for k, ni in enumerate(infos):
        assert ints_a[k, 1] == ni.allocatable.memory // 1024
        assert ints_a[k, 5] == -(-ni.requested.memory // 1024)
        assert ints_a[k, 7] == len(ni.pods)


def test_node_rows_gather_refuses_what_the_twin_refuses():
    ni = NodeInfo(make_node("big").capacity(
        cpu="8", memory="16Gi", pods=110).obj())
    ni.requested.memory = (1 << 31) * 1024  # one KiB past int32
    args = ([ni], [0], [0], [None], [None], [None])
    for fn in (native.hotpath.node_rows_gather, _node_rows_gather_py):
        with pytest.raises(OverflowError):
            fn(*args, np.zeros((1, 10), dtype=np.int32))
    ni.requested.memory = -1500  # Python's floor and ceiling, below zero
    ni.allocatable.memory = -1500
    out = []
    for fn in (native.hotpath.node_rows_gather, _node_rows_gather_py):
        ints = np.zeros((1, 10), dtype=np.int32)
        fn(*args, ints)
        out.append(ints[0].tolist())
    assert out[0] == out[1] and out[0][1] == -2 and out[0][5] == -1
    with pytest.raises(IndexError):
        native.hotpath.node_rows_gather(
            [ni], [3], [0], [None], [None], [None],
            np.zeros((1, 10), dtype=np.int32))
    with pytest.raises(ValueError):
        native.hotpath.node_rows_gather(
            [ni], [0], [0], [None], [None], [None],
            np.zeros((1, 5), dtype=np.int32))


def test_a_missing_extension_is_counted_at_both_sites(monkeypatch):
    """Native wanted and absent: the twins run and
    ``scheduler_ingest_native_fallbacks_total`` says where."""
    monkeypatch.setitem(native._INGEST_FNS, "node_info_clones", None)
    monkeypatch.setitem(native._INGEST_FNS, "node_rows_gather", None)
    counter = metrics.ingest_native_fallbacks

    def count(site):
        return counter.value(site=site)

    before = count("snapshot-clone"), count("node-gather")
    cache = SchedulerCache()
    cache.add_node(make_node("n0").capacity(
        cpu="8", memory="16Gi", pods=110).obj())
    snap, tc = Snapshot(), NodeTensorCache()
    cache.update_snapshot(snap)  # a new name: the full walk, no list
    tc.update(snap)
    cache.add_pod(make_pod("p").uid("p").node("n0").container(cpu="1").obj())
    cache.update_snapshot(snap)
    nt = tc.update(snap)
    assert nt.delta.row_stats() == {"rows": 1, "rows_pods_only": 1}
    assert count("snapshot-clone") == before[0] + 1
    assert count("node-gather") >= before[1] + 2
    # the configured twin (KTPU_NATIVE_INGEST=0) is no fallback
    monkeypatch.setenv("KTPU_NATIVE_INGEST", "0")
    after = count("snapshot-clone"), count("node-gather")
    cache.add_pod(make_pod("q").uid("q").node("n0").container(cpu="1").obj())
    cache.update_snapshot(snap)
    tc.update(snap)
    assert (count("snapshot-clone"), count("node-gather")) == after


class _Duck:
    """A NodeInfo's fields in an instance dict, not in slots."""

    def __init__(self, ni):
        for part in NodeInfo.__slots__:
            setattr(self, part, getattr(ni, part))


def _pods_not_a_list(infos):
    infos[1].pods = tuple(infos[1].pods)
    return infos, [None] * 3, [0, 1, 2]


def _a_predecessor_of_another_type(infos):
    return infos, [None, _Duck(infos[1]), None], [0, 1, 2]


def _a_row_that_is_no_integer(infos):
    return infos, [None] * 3, [0, "1", 2]


def _fields_not_in_slots(infos):
    return [_Duck(ni) for ni in infos], [None] * 3, [0, 1, 2]


@pytest.mark.parametrize("fault, walks", [
    (_pods_not_a_list, "clone"),
    (_a_predecessor_of_another_type, "clone"),
    (_a_row_that_is_no_integer, "gather"),
    (_fields_not_in_slots, "clone gather"),
])
def test_a_fault_in_a_native_walk_is_an_error_and_no_fallback(fault, walks):
    """The twins run where the extension did not build or
    KTPU_NATIVE_INGEST=0 asks for them, and nowhere else: what the
    native walks refuse reaches the caller and ticks no counter."""
    from kubernetes_tpu.cache import cache as cache_mod

    infos, prevs, rows = fault(_node_infos(7, count=3))
    counter = metrics.ingest_native_fallbacks
    before = (counter.value(site="snapshot-clone"),
              counter.value(site="node-gather"))
    tc = NodeTensorCache()
    tc._generations = [0] * 3
    tc._row_node, tc._row_alloc, tc._row_csi = ([None] * 3 for _ in range(3))
    if "clone" in walks:
        with pytest.raises(TypeError):
            cache_mod._clones(infos, prevs)
    if "gather" in walks:
        with pytest.raises(TypeError):
            tc._gather_rows(rows, infos)
    assert (counter.value(site="snapshot-clone"),
            counter.value(site="node-gather")) == before


def _snapshot_reading(snap):
    return {
        name: (
            ni.requested.milli_cpu, ni.requested.memory,
            dict(ni.requested.scalar), ni.non_zero_requested.milli_cpu,
            [p.metadata.uid for p in ni.pods],
            [p.metadata.uid for p in ni.pods_with_affinity],
            sorted(ni.used_ports.ports), dict(ni.volume_in_use),
            ni.allocatable.milli_cpu, dict(ni.allocatable.scalar),
            dict(ni.image_states), dict(ni.csi_volume_limits),
            ni.node.metadata.labels.get("zone") if ni.node else None,
            ni.generation,
        )
        for name, ni in snap.node_info_map.items()
    }


def test_a_snapshot_reads_the_same_after_the_cache_moves_on(walk):
    """A snapshot taken before a batch holds its cycle's state while the
    cache assumes, confirms and removes pods on the same nodes, and
    while nodes are written: the clone's shared parts are replaced in
    the cache, never written into."""
    cache = SchedulerCache()
    nodes = {}
    for i in range(5):
        nodes[f"n{i}"] = make_node(f"n{i}").labels(zone="z0").capacity(
            cpu="16", memory="32Gi", pods=110, **{"example.com/gpu": 4}
        ).image("pause", 700 * 1024).obj()
        cache.add_node(nodes[f"n{i}"])
    cache.add_csi_node(CSINode(
        metadata=ObjectMeta(name="n1"),
        drivers=[CSINodeDriver(name="ebs", allocatable_count=4)],
    ))
    resident = make_pod("r").uid("r").node("n1").container(
        cpu="1", memory="1Gi", host_port=8080,
        **{"example.com/gpu": 1}).obj()
    resident.__dict__["_volcount_memo"] = ((VOLUME, 1),)
    cache.add_pod(resident)
    snap = Snapshot()
    cache.update_snapshot(snap)
    held = list(snap.node_info_list)
    reading = _snapshot_reading(snap)
    for ni in held:
        assert ni.allocatable is cache._nodes[ni.node_name].allocatable
    # the batch: assume, confirm, remove, on the nodes the snapshot holds
    assumed = [
        make_pod(f"a{i}").uid(f"a{i}").node(f"n{i % 5}").container(
            cpu="500m", memory="256Mi", host_port=9000 + i,
            **{"example.com/gpu": 1}).obj()
        for i in range(10)
    ]
    assert cache.assume_pods(assumed) == [None] * 10
    cache.finish_binding_bulk(assumed)
    cache.add_pods(assumed[:6])  # the binds' echoes
    cache.forget_pod(assumed[7])
    cache.remove_pods(assumed[:3] + [resident])
    # and node writes: labels, allocatable, images, volume limits
    cache.update_node(nodes["n2"], make_node("n2").labels(zone="z9").capacity(
        cpu="2", memory="1Gi", pods=10).obj())
    cache.add_csi_node(CSINode(
        metadata=ObjectMeta(name="n1"),
        drivers=[CSINodeDriver(name="ebs", allocatable_count=9)],
    ))
    cache.remove_node(nodes["n4"])
    assert _snapshot_reading(snap) == reading
    assert snap.node_info_list == held
    # the next refresh sees all of it
    cache.update_snapshot(snap)
    after = _snapshot_reading(snap)
    assert after["n2"][8] == 2000 and after["n2"][12] == "z9"
    assert after["n1"][11] == {VOLUME: 9} and after["n1"][4] == ["a6"]
    # the old list is what it was: a reader that held it saw one state
    assert {ni.node_name: r for ni, r in zip(held, (
        reading[ni.node_name] for ni in held
    ))} == {ni.node_name: _snapshot_reading(
        Snapshot({ni.node_name: ni}))[ni.node_name] for ni in held}


def test_the_writers_of_a_snapshots_node_info_copy_first():
    """The audit's two writers, ``select_victims_on_node`` (preemption)
    and ``_add_nominated_pods`` (the host path), write pods into a
    NodeInfo reached through a snapshot: into their own clone, whose
    pods, ports and requested are copies. Neither writes into the four
    parts the clone shares; the snapshot's NodeInfo reads as before."""
    from kubernetes_tpu.framework.interface import CycleState
    from kubernetes_tpu.scheduler.generic import GenericScheduler
    from kubernetes_tpu.scheduler.preemption import Preemptor

    class Profile:
        """The framework calls the two writers make."""

        def run_pre_filter_extension_add_pod(self, *args):
            return None

        def run_pre_filter_extension_remove_pod(self, *args):
            return None

        def run_filter_plugins(self, state, pod, node_info):
            self.saw = node_info
            wanted = node_info.requested.milli_cpu + 3000
            return {} if wanted <= node_info.allocatable.milli_cpu else {
                "NodeResourcesFit": "Insufficient cpu"}

    cache = SchedulerCache()
    cache.add_node(make_node("n0").capacity(
        cpu="4", memory="8Gi", pods=110).obj())
    low = make_pod("low").uid("low").node("n0").priority(1).container(
        cpu="3", host_port=8080).obj()
    cache.add_pod(low)
    snap = Snapshot()
    cache.update_snapshot(snap)
    ni = snap.get_node_info("n0")
    fixed = (ni.node, ni.allocatable, ni.image_states, ni.csi_volume_limits)
    alloc = (ni.allocatable.milli_cpu, ni.allocatable.memory,
             dict(ni.allocatable.scalar))
    reading = _snapshot_reading(snap)

    prof = Profile()
    incoming = make_pod("high").uid("high").priority(100).container(
        cpu="3").obj()
    host = GenericScheduler.__new__(GenericScheduler)
    host.nominated_pods_lister = None
    host._merge_statuses = lambda statuses: None
    preemptor = Preemptor.__new__(Preemptor)
    preemptor.algorithm = host
    victims, _violations, fits = preemptor.select_victims_on_node(
        prof, CycleState(), incoming, ni, []
    )
    assert fits and [p.metadata.uid for p in victims] == ["low"]
    worked_on = prof.saw
    assert worked_on is not ni and worked_on.pods == []
    assert worked_on.shares_fixed_parts(ni)  # read, never written
    assert _snapshot_reading(snap) == reading

    class Nominated:
        def nominated_pods_for_node(self, name):
            return [incoming]

    host.nominated_pods_lister = Nominated()
    other = make_pod("other").uid("other").priority(5).container(
        cpu="1").obj()
    added, _state, out = host._add_nominated_pods(
        prof, other, CycleState(), ni
    )
    assert added and out is not ni and len(out.pods) == 2
    assert out.requested.milli_cpu == 6000 and out.shares_fixed_parts(ni)
    assert _snapshot_reading(snap) == reading
    assert (ni.node, ni.allocatable, ni.image_states,
            ni.csi_volume_limits) == fixed
    assert (ni.allocatable.milli_cpu, ni.allocatable.memory,
            dict(ni.allocatable.scalar)) == alloc
