"""The score packer keeps what the Node objects alone decide (ISSUE 49).

``pack_score_batch`` built, at every live batch, the zone of every node
row and ImageLocality's row of every image list the batch names: both
read the Node objects and the node -> tensor row map and nothing a pod
can move. They are now kept in the dispatcher's ``FamilyFacts``: the
zone rows while the snapshot, its ``node_spec_epoch`` and the tensor's
slot list stand, an image list's row while ``Snapshot.image_holders()``
hands out the same index and the slot list stands.

Held here: over pod-only events every array of the ``ScoreBatch`` is,
bit for bit, what a call that keeps nothing builds, and nothing is built
twice; each event that can change a row (a zone label, a node's images,
a node added or removed, the tensor laid out anew, ``refresh_lists``)
has the rows built again, right; a snapshot no cache feeds keeps
nothing; a cluster of too many zones is refused at every batch; what is
kept cannot be written and is bounded.
"""

import dataclasses

import numpy as np
import pytest

from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.snapshot import Snapshot, new_snapshot
from kubernetes_tpu.ops import family_facts
from kubernetes_tpu.ops.family_facts import FamilyFacts
from kubernetes_tpu.ops.scoring import (
    MAX_ZONES,
    ScoreBatch,
    ScoreEnvelopeExceeded,
    pack_score_batch,
)
from kubernetes_tpu.tensors import NodeTensorCache
from kubernetes_tpu.testing import make_node, make_pod

MIB = 1024 * 1024
ZONE = "topology.kubernetes.io/zone"
NODES = 24
APPS = 6
WEIGHTS = {"ImageLocality": 2}
GHOST = "registry.example/nowhere:v1"  # an image no node holds


def _image(k: int) -> str:
    return f"registry.example/app-{k}:v1"


def _node(i: int, zone=None, images=None):
    """Node ``i``: in one of four zones (the last node in none), holding
    app k's image where ``(i + k) % 3`` says, at a size of its own."""
    w = make_node(f"n{i}").capacity(
        cpu=str(8 + i % 5), memory=f"{16 + i % 7}Gi", pods=110
    )
    if zone is None and i != NODES - 1:
        zone = f"z{i % 4}"
    if zone:
        w.label(ZONE, zone)
    if images is None:
        images = {
            _image(k): (300 + 97 * k + 11 * i) * MIB
            for k in range(APPS) if (i + k) % 3
        }
    for name, size in images.items():
        w.image(name, size)
    return w.obj()


def _pods(apps, stem="p", each=2):
    """``each`` pods of every app of ``apps``, then one of two
    containers (the first two apps' images), one that names an image no
    node holds and one that names none."""
    out = []
    for k in apps:
        for e in range(each):
            out.append(
                make_pod(f"{stem}-{k}-{e}").labels(app=f"app-{k}")
                .container(cpu="100m", memory="64Mi", image=_image(k)).obj()
            )
    out.append(
        make_pod(f"{stem}-two").container(cpu="100m", image=_image(0))
        .container(cpu="100m", image=_image(1)).obj()
    )
    out.append(make_pod(f"{stem}-ghost").container(
        cpu="100m", image=GHOST).obj())
    out.append(make_pod(f"{stem}-bare").container(cpu="100m").obj())
    return out


def _named(pods) -> set:
    """The distinct container image lists of ``pods``."""
    return {tuple(k.image for k in p.spec.containers) for p in pods}


def _lists(pods) -> int:
    return len(_named(pods))


class _Cluster:
    """A cache, the snapshot it feeds, one tensor cache and the
    dispatcher's ``FamilyFacts``."""

    def __init__(self, count=NODES):
        self.cache = SchedulerCache()
        self.nodes = {}
        for i in range(count):
            self.add(_node(i))
        self.snap = Snapshot()
        self.tc = NodeTensorCache()
        self.facts = FamilyFacts()

    def add(self, node):
        old = self.nodes.get(node.metadata.name)
        self.nodes[node.metadata.name] = node
        if old is None:
            self.cache.add_node(node)
        else:
            self.cache.update_node(old, node)

    def remove(self, name):
        self.cache.remove_node(self.nodes.pop(name))

    def built(self) -> int:
        return self.facts.score_node_rows - self.facts.score_node_rows_reused

    def pack(self, pods) -> ScoreBatch:
        """The pack that keeps, held to one of the same snapshot and
        tensor that keeps nothing: every array, bit for bit."""
        self.cache.update_snapshot(self.snap)
        nt = self.tc.update(self.snap)
        got = pack_score_batch(
            pods, self.snap, nt, None, WEIGHTS, facts=self.facts
        )
        want = pack_score_batch(pods, self.snap, nt, None, WEIGHTS)
        _assert_same(got, want)
        return got


def _assert_same(got: ScoreBatch, want: ScoreBatch) -> None:
    assert got is not None and want is not None
    for field in dataclasses.fields(ScoreBatch):
        a, b = getattr(got, field.name), getattr(want, field.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, field.name
            assert a.tobytes() == b.tobytes(), field.name
        else:
            assert a == b, field.name


def _bound(name, node, k=0):
    return (
        make_pod(name).labels(app=f"app-{k}").node(node)
        .container(cpu="200m", memory="128Mi", image=_image(k)).obj()
    )


# -- (a) pod-only events ------------------------------------------------------


def test_rows_are_built_once_over_pod_only_events():
    c = _Cluster()
    first = _pods(range(APPS), "a")
    got = c.pack(first)
    lists = _lists(first)
    assert got.zone_id.max() == 3 and got.zone_id[NODES - 1] == -1
    assert np.count_nonzero(got.direct_rows) > 0
    assert c.facts.score_node_rows == c.built() == 1 + lists
    index = c.snap.image_holders()
    names = c.tc.update(c.snap).names
    epoch = c.snap.node_spec_epoch

    held = [_bound(f"b{i}", f"n{i}", i % APPS) for i in range(8)]
    events = [
        lambda: [c.cache.add_pod(p) for p in held[:4]],  # binds
        lambda: c.cache.assume_pod(held[4]),
        lambda: c.cache.remove_pod(held[0]),  # a delete
        lambda: (c.cache.add_pod(held[5]), c.cache.remove_pod(held[1])),
        lambda: None,  # a batch right behind another
        lambda: c.cache.forget_pod(held[4]),
    ]
    asked = c.facts.score_node_rows
    for n, event in enumerate(events):
        event()
        pods = _pods(range(n % APPS, APPS), f"e{n}")
        reused = c.facts.score_node_rows_reused
        got = c.pack(pods)
        # the refresh put clones where their predecessors stood
        assert c.snap.image_holders() is index
        assert c.tc.update(c.snap).names is names
        assert c.snap.node_spec_epoch == epoch
        asked += 1 + _lists(pods)
        assert c.facts.score_node_rows == asked
        assert c.facts.score_node_rows_reused == reused + 1 + _lists(pods)
        assert c.built() == 1 + lists
    assert c.snap.last_refreshed  # the last event did reach the snapshot


def test_consecutive_batches_share_the_zone_rows_and_no_row_of_scores():
    c = _Cluster()
    one = c.pack(_pods(range(APPS), "a"))
    two = c.pack(_pods(range(APPS), "b"))
    assert two.zone_id is one.zone_id and two.zone_onehot is one.zone_onehot
    assert not np.shares_memory(one.direct_rows, two.direct_rows)
    # a batch's own rows are its own to write: the next is built right
    one.direct_rows[:] = -1.0
    two.direct_rows[:] = -1.0
    c.pack(_pods(range(APPS), "c"))


# -- (b) what moves a row -----------------------------------------------------


def _zone_label(c):
    c.add(_node(5, zone="z9"))  # a zone no other node is in


def _zone_lost(c):
    # the first node loses its zone: every later zone is interned anew
    node = _node(0)
    del node.metadata.labels[ZONE]
    c.add(node)


def _image_gained(c):
    c.add(_node(3, images={_image(k): 900 * MIB for k in range(APPS)}))


def _image_lost(c):
    c.add(_node(4, images={}))


def _node_added(c):
    c.add(_node(NODES + 1, zone="z2"))


def _node_removed(c):
    c.remove("n7")


def _relaid(c):
    # another tensor cache: the slot the removed node left is closed up,
    # so every later node's row moves, under the same epoch and index
    c.tc = NodeTensorCache()


def _lists_refreshed(c):
    c.snap.refresh_lists()


#: event -> (does it move the node-spec epoch, are the zone rows built
#: anew, do the batch's arrays change)
EVENTS = {
    "zone_label": (_zone_label, True, True, True),
    "zone_lost": (_zone_lost, True, True, True),
    "image_gained": (_image_gained, True, True, True),
    "image_lost": (_image_lost, True, True, True),
    "node_added": (_node_added, True, True, True),
    "node_removed": (_node_removed, True, True, True),
    "tensor_relaid": (_relaid, False, True, True),
    "refresh_lists": (_lists_refreshed, False, False, False),
}


@pytest.mark.parametrize("kind", sorted(EVENTS))
def test_a_kept_row_is_dropped_by(kind):
    event, moves_epoch, zones_anew, arrays_change = EVENTS[kind]
    c = _Cluster()
    pods = _pods(range(APPS), "a")
    lists = _lists(pods)
    c.pack(pods)
    c.remove("n2")  # a free slot: a tensor laid out anew has none
    before = c.pack(pods)
    c.pack(pods)
    built = c.built()
    assert built == 2 * (1 + lists)
    epoch = c.snap.node_spec_epoch
    index = c.snap.image_holders()

    event(c)
    after = c.pack(pods)  # equal to the fresh build, or pack() fails
    assert (c.snap.node_spec_epoch != epoch) == moves_epoch
    assert c.snap.image_holders() is not index or kind == "tensor_relaid"
    assert c.built() == built + zones_anew + lists
    same = all(
        np.array_equal(getattr(before, f), getattr(after, f))
        for f in ("zone_id", "zone_onehot", "direct_rows")
    )
    assert same != arrays_change  # a row kept past the event would show
    assert (after.zone_id is before.zone_id) != zones_anew
    # and once: the batch after it builds nothing
    built = c.built()
    c.pack(_pods(range(APPS), "b"))
    assert c.built() == built


def test_another_weight_takes_rows_of_its_own():
    c = _Cluster()
    pods = _pods(range(APPS), "a")
    c.pack(pods)
    c.cache.update_snapshot(c.snap)
    nt = c.tc.update(c.snap)
    for weight in (5, 2, 5):
        weights = {"ImageLocality": weight}
        got = pack_score_batch(pods, c.snap, nt, None, weights, facts=c.facts)
        _assert_same(got, pack_score_batch(pods, c.snap, nt, None, weights))
    # the zone rows once, each weight's lists once
    assert c.built() == 1 + 2 * _lists(pods)


# -- (c) where nothing may be kept --------------------------------------------


def test_a_snapshot_no_cache_feeds_keeps_nothing():
    nodes = [_node(i) for i in range(NODES)]
    snap = new_snapshot([], nodes)
    assert snap.node_spec_epoch == 0
    nt = NodeTensorCache().update(snap)
    facts = FamilyFacts()
    pods = _pods(range(APPS), "a")
    one = pack_score_batch(pods, snap, nt, None, WEIGHTS, facts=facts)
    # a write no cache tells of: the next call has to see it
    snap.node_info_list[5].node.metadata.labels[ZONE] = "z9"
    two = pack_score_batch(pods, snap, nt, None, WEIGHTS, facts=facts)
    _assert_same(two, pack_score_batch(pods, snap, nt, None, WEIGHTS))
    assert two.zone_id is not one.zone_id
    assert two.zone_id[5] == 4 and one.zone_id[5] == 1
    assert facts.score_node_rows_reused == 0
    assert not facts._image_rows and facts._image_index is None
    assert facts.score_live == 2  # the counts of the call itself stay


# -- (d) too many zones -------------------------------------------------------


def test_a_cluster_of_too_many_zones_is_refused_at_every_batch():
    c = _Cluster(count=0)
    for i in range(MAX_ZONES + 3):
        c.add(_node(i, zone=f"z{i}"))
    pods = _pods(range(APPS), "a")
    c.cache.update_snapshot(c.snap)
    nt = c.tc.update(c.snap)
    for n in range(3):
        with pytest.raises(ScoreEnvelopeExceeded):
            pack_score_batch(pods, c.snap, nt, None, WEIGHTS, facts=c.facts)
        with pytest.raises(ScoreEnvelopeExceeded):
            pack_score_batch(pods, c.snap, nt, None, WEIGHTS)
        # the verdict is kept as a row is: the nodes are walked once
        assert c.built() == 1 + _lists(pods)
        assert c.facts.score_node_rows == (n + 1) * (1 + _lists(pods))
    # a zone fewer than too many, and the batches pack again
    for i in range(MAX_ZONES, MAX_ZONES + 3):
        c.add(_node(i, zone="z0"))
    assert c.pack(pods).zone_id.max() == MAX_ZONES - 1


# -- (e) what is kept cannot be written, and is bounded -----------------------


def test_kept_arrays_are_not_writeable():
    c = _Cluster()
    got = c.pack(_pods(range(APPS), "a"))
    for kept in (got.zone_id, got.zone_onehot, *c.facts._image_rows.values()):
        if kept is None:
            continue  # a list that scores nothing keeps its verdict alone
        assert not kept.flags.writeable
        with pytest.raises(ValueError):
            kept[0] = 1
    assert any(row is not None for row in c.facts._image_rows.values())
    assert any(row is None for row in c.facts._image_rows.values())
    assert got.direct_rows.flags.writeable  # the batch's own


def test_kept_image_lists_never_exceed_their_bound(monkeypatch):
    monkeypatch.setattr(family_facts, "IMAGE_ROWS_KEPT", 4)
    c = _Cluster()
    for n in range(3):
        for first in range(APPS):
            pods = _pods([first, (first + 1) % APPS], f"r{n}-{first}")
            c.pack(pods)
            assert len(c.facts._image_rows) <= 4
            # least recently used out first: the batch's own lists stay
            kept = {images for _w, images in c.facts._image_rows}
            assert len(_named(pods) & kept) == min(_lists(pods), 4)
    assert c.built() > 1 + APPS  # lists pushed out were built again
