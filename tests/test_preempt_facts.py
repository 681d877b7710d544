"""The victim pack kept per node (``ops/preempt_facts.py``) against the
whole build it stands for (``ops/preemption.pack_preemption_state``).

Through the real ``SchedulerCache`` -> ``update_snapshot`` -> store:
after every refresh the store's pack equals a fresh whole build of the
same snapshot array for array, and ``node_names`` / ``pods_by_node`` pod
for pod in order. Every reason the store gives its rows up has its
case, each of which also shows that the next small change is advanced
again. The clock is held still, so that a pod without a start time
reads the same ``now`` on both sides."""

import random
import threading
import types

import numpy as np
import pytest

from kubernetes_tpu.api.types import LabelSelector, PodDisruptionBudget
from kubernetes_tpu.cache import snapshot as snapshot_mod
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.ops import preempt_facts, preemption
from kubernetes_tpu.ops.preempt_facts import PreemptFacts
from kubernetes_tpu.scheduler.preemption import Preemptor
from kubernetes_tpu.tensors import NodeTensorCache
from kubernetes_tpu.testing import make_node, make_pod
from test_preemption_wave import _env, _fail, _queue

NOW = 1_800_000_000.0
ARRAYS = ("prio", "start_rel", "req", "active", "pdb_match", "alloc",
          "base_requested", "pdb_allowed")


@pytest.fixture(autouse=True)
def still_clock(monkeypatch):
    clock = types.SimpleNamespace(time=lambda: NOW)
    monkeypatch.setattr(preemption, "time", clock)
    monkeypatch.setattr(preempt_facts, "time", clock)
    return clock


def resident(name, node, prio=0, start=None, cpu="3000m", **labels):
    w = (make_pod(name).node(node).container(cpu=cpu, memory="6Gi")
         .priority(prio))
    if labels:
        w.labels(**labels)
    p = w.obj()
    p.status.start_time = start
    return p


def budget(name, app, allowed, version="1"):
    pdb = PodDisruptionBudget(
        selector=LabelSelector(match_labels={"app": app}))
    pdb.metadata.name = name
    pdb.metadata.namespace = "default"
    pdb.metadata.resource_version = version
    pdb.status.disruptions_allowed = allowed
    return pdb


class Cluster:
    """A cache, its snapshot, a tensor cache and the store under test:
    ``nodes`` nodes of 32 CPU, each with ``per_node`` residents of which
    one is a ``mid`` (priority 10), some started and some not."""

    def __init__(self, seed=0, nodes=12, per_node=10):
        self.rng = random.Random(seed)
        self.cache = SchedulerCache()
        self.nodes = {}
        self.pods = {}
        for i in range(nodes):
            self.add_node(f"n{i}")
        for i in range(nodes):
            for k in range(per_node):
                self.add(resident(
                    f"r{i}-{k}", f"n{i}", prio=10 if k == 0 else 0,
                    start=self.start(), app=self.rng.choice("abc")))
        self.snapshot = Snapshot()
        self.tensors = NodeTensorCache()
        self.facts = PreemptFacts()
        self.pdbs = []
        self.serial = 0

    def start(self):
        """A start time in the past, or none for one pod in three."""
        if self.rng.random() < 1 / 3:
            return None
        return NOW - 10_000 + self.rng.randrange(5_000)

    def add_node(self, name, **scalars):
        node = make_node(name).capacity(
            cpu="32", memory="64Gi", pods=110, **scalars).obj()
        self.cache.add_node(node)
        self.nodes[name] = node

    def add(self, pod):
        self.cache.add_pod(pod)
        self.pods[pod.metadata.name] = pod
        return pod

    def remove(self, name):
        self.cache.remove_pod(self.pods.pop(name))

    def on(self, node):
        return [p for p in self.pods.values() if p.spec.node_name == node]

    def new_name(self, kind):
        self.serial += 1
        return f"{kind}{self.serial}"

    def refresh(self):
        """Refresh the snapshot, take the store's pack and hold it to
        the whole build of the same snapshot."""
        self.cache.update_snapshot(self.snapshot)
        nt = self.tensors.update(self.snapshot)
        got = self.facts.pack(self.snapshot, nt, self.pdbs)
        want = preemption.pack_preemption_state(self.snapshot, nt, self.pdbs)
        assert_same_pack(got, want)
        assert got.nodes_kept + got.nodes_repacked == len(got.node_names)
        return got


def assert_same_pack(got, want):
    for name in ARRAYS:
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name
    assert got.v_max == want.v_max
    assert got.generation == want.generation
    assert got.node_names == want.node_names
    assert got.node_index == want.node_index
    assert len(got.pods_by_node) == len(want.pods_by_node)
    for i, (a, b) in enumerate(zip(got.pods_by_node, want.pods_by_node)):
        assert [p.metadata.name for p in a] == [p.metadata.name for p in b], i
        assert all(p is q for p, q in zip(a, b)), i


# -- the cell's own events ----------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_waves_of_the_cell_advance_to_the_whole_build(seed):
    """Evict one resident a node, bind a preemptor there, delete it,
    refill: after every one of the four refreshes of every wave."""
    c = Cluster(seed)
    first = c.refresh()
    assert (first.made, first.why) == ("built", "first")
    assert (first.nodes_kept, first.nodes_repacked) == (0, 12)
    for wave in range(3):
        hit = c.rng.sample(sorted(c.nodes), 5)
        for node in hit:
            victim = c.rng.choice(
                [p for p in c.on(node) if p.spec.priority == 0])
            c.remove(victim.metadata.name)
        pack = c.refresh()
        assert (pack.made, pack.nodes_kept, pack.nodes_repacked) == (
            "advanced", 7, 5)
        high = [c.add(resident(c.new_name("high"), node, prio=100))
                for node in hit]
        assert c.refresh().nodes_repacked == 5
        for p in high:
            c.remove(p.metadata.name)
        assert c.refresh().nodes_repacked == 5
        for node in hit:
            c.add(resident(c.new_name("filler"), node, start=c.start()))
        pack = c.refresh()
        assert (pack.made, pack.nodes_repacked) == ("advanced", 5)
    # and a refresh that changed nothing keeps every row
    pack = c.refresh()
    assert (pack.made, pack.nodes_kept, pack.nodes_repacked) == (
        "advanced", 12, 0)


def test_a_pod_without_a_start_time_reads_each_packs_own_clock(still_clock):
    """``start_rel`` of a kept row follows the clock and the earliest
    pod of the pack it is published in, not of the pack that sorted it."""
    c = Cluster(1)
    c.add(resident("unstarted", "n3", start=None))
    c.refresh()
    still_clock.time = lambda: NOW + 500.0
    # the cluster's earliest pod leaves from another node: every kept
    # row's offset moves
    earliest = min(
        (p for p in c.pods.values() if p.status.start_time is not None),
        key=lambda p: p.status.start_time)
    c.remove(earliest.metadata.name)
    pack = c.refresh()
    assert (pack.made, pack.nodes_repacked) == ("advanced", 1)
    i = pack.node_index["n3"]
    slot = [p.metadata.name for p in pack.pods_by_node[i]].index("unstarted")
    started = min(p.status.start_time for p in c.pods.values()
                  if p.status.start_time is not None)
    assert pack.start_rel[i, slot] == NOW + 500.0 - started


def test_a_priority_changed_by_delete_and_add_again_moves_the_pod():
    c = Cluster(2)
    c.refresh()
    old = next(p for p in c.on("n4") if p.spec.priority == 0)
    c.remove(old.metadata.name)
    c.add(resident(old.metadata.name, "n4", prio=50,
                   start=old.status.start_time))
    pack = c.refresh()
    assert (pack.made, pack.nodes_repacked) == ("advanced", 1)
    row = pack.pods_by_node[pack.node_index["n4"]]
    assert row[0].metadata.name == old.metadata.name  # now the most important
    assert pack.prio[pack.node_index["n4"], 0] == 50


def test_a_node_gains_an_eleventh_pod_and_loses_it():
    c = Cluster(3)
    assert c.refresh().v_max == 16
    c.add(resident("eleventh", "n7", start=NOW - 1))
    pack = c.refresh()
    assert (pack.made, pack.v_max, pack.nodes_repacked) == ("advanced", 16, 1)
    assert pack.active[pack.node_index["n7"]].sum() == 11
    c.remove("eleventh")
    pack = c.refresh()
    assert (pack.made, pack.nodes_repacked) == ("advanced", 1)
    assert pack.active[pack.node_index["n7"]].sum() == 10


def test_pdb_rows_of_a_changed_node_are_matched_again():
    c = Cluster(4)
    c.pdbs = [budget("pdb-a", "a", 1), budget("pdb-b", "b", 0)]
    c.refresh()
    c.add(resident("guarded", "n2", start=NOW - 5, app="a"))
    c.remove(next(p for p in c.on("n5")
                  if p.metadata.labels.get("app") == "b").metadata.name)
    pack = c.refresh()
    assert (pack.made, pack.nodes_repacked) == ("advanced", 2)
    assert pack.pdb_match.shape[2] == 2 and pack.pdb_match.any()


# -- where the rows are given up ----------------------------------------------


def truncate_log(c, monkeypatch):
    monkeypatch.setattr(snapshot_mod, "CHANGE_TRACK_MIN", 4)
    for k in range(4):  # 4 refreshes x 12 nodes: past twice the node count
        for node in sorted(c.nodes):
            c.add(resident(c.new_name(f"t{k}-"), node, start=NOW - 9))
        c.cache.update_snapshot(c.snapshot)
        c.tensors.update(c.snapshot)


def add_a_node(c, monkeypatch):
    c.add_node("joined")
    c.add(resident("on-joined", "joined", start=NOW - 3))


def remove_a_node(c, monkeypatch):
    for p in c.on("n6"):
        c.remove(p.metadata.name)
    c.cache.remove_node(c.nodes.pop("n6"))


def bucket_up(c, monkeypatch):
    for k in range(7):  # 17 pods: past the bucket of 16
        c.add(resident(f"crowd{k}", "n1", start=NOW - 20 + k))


def bucket_down(c, monkeypatch):
    bucket_up(c, monkeypatch)
    assert c.refresh().v_max == 32
    for k in range(7):
        c.remove(f"crowd{k}")


def pdb_appears(c, monkeypatch):
    c.pdbs = [budget("pdb-a", "a", 1)]


def pdb_budget_moves(c, monkeypatch):
    c.pdbs = [budget("pdb-a", "a", 1)]
    assert c.refresh().why == "pdbs"
    c.pdbs = [budget("pdb-a", "a", 0, version="2")]


def new_dimension(c, monkeypatch):
    c.add_node("n9", example_com__gpu=4)  # the node, anew, with a scalar


def another_snapshot(c, monkeypatch):
    c.snapshot = Snapshot()


@pytest.mark.parametrize("event, why", [
    (truncate_log, "log_truncated"),
    (add_a_node, "membership"),
    (remove_a_node, "membership"),
    (bucket_up, "v_max"),
    (bucket_down, "v_max"),
    (pdb_appears, "pdbs"),
    (pdb_budget_moves, "pdbs"),
    (new_dimension, "dims"),
    (another_snapshot, "snapshot"),
], ids=lambda v: v.__name__ if callable(v) else v)
def test_the_whole_build_is_taken_where_the_rows_cannot_be_seen_to_hold(
        event, why, monkeypatch):
    c = Cluster(5)
    c.refresh()
    c.remove(c.on("n0")[3].metadata.name)
    assert c.refresh().made == "advanced"
    event(c, monkeypatch)
    pack = c.refresh()
    assert (pack.made, pack.why) == ("built", why)
    assert (pack.nodes_kept, pack.nodes_repacked) == (
        0, len(pack.node_names))
    # the store starts again from the whole build
    c.add(resident("after", "n0", start=NOW - 2))
    pack = c.refresh()
    assert (pack.made, pack.why, pack.nodes_repacked) == ("advanced", "", 1)


def _drain(c, name):
    for pod in c.on(name):
        c.remove(pod.metadata.name)


def cordon(c, name):
    old = c.nodes[name]
    new = old.deepcopy()
    new.spec.unschedulable = True
    c.cache.update_node(old, new)
    c.nodes[name] = new


def delete(c, name):
    _drain(c, name)
    c.cache.remove_node(c.nodes.pop(name))


def rejoin(c, name):
    """Gone, packed without, and back under its own name, not Ready."""
    delete(c, name)
    assert c.refresh().why == "membership"
    node = make_node(name).capacity(cpu="32", memory="64Gi", pods=110).taint(
        "node.kubernetes.io/not-ready", "", "NoSchedule").obj()
    c.cache.add_node(node)
    c.nodes[name] = node


@pytest.mark.parametrize("write, made", [
    (cordon, "advanced"), (delete, "built"), (rejoin, "built"),
], ids=["cordon", "delete", "rejoin"])
def test_the_victim_pack_is_right_after_a_rolled_nodes_write(write, made):
    """The store ``rolling-upgrade-5000`` cannot reach (nothing preempts
    there), held on the CPU until a deployment reaches it: after a
    cordon, a delete and a re-join under the same name the pack equals
    the whole build (``refresh`` holds it array for array); a change of
    membership builds it whole, once, and the next small change is
    advanced again."""
    c = Cluster(9)
    c.refresh()
    c.remove(c.on("n0")[3].metadata.name)
    assert c.refresh().made == "advanced"
    write(c, "n5")
    pack = c.refresh()
    assert pack.made == made
    assert ("n5" in pack.node_index) == (write is not delete)
    if made == "built":
        assert pack.why == "membership"
        assert (pack.nodes_kept, pack.nodes_repacked) == (
            0, len(pack.node_names))
    c.add(resident("after", "n0", start=NOW - 2))
    pack = c.refresh()
    assert (pack.made, pack.why, pack.nodes_repacked) == ("advanced", "", 1)


def test_a_start_time_ahead_of_the_clock_keeps_nothing(still_clock):
    """A pod without a start time sorts before one that starts
    tomorrow, and after it the day after: rows sorted by an earlier
    clock are kept only while every start time is behind every clock."""
    c = Cluster(5)
    c.refresh()
    c.add(resident("skewed", "n8", start=NOW + 60))
    c.add(resident("beside-it", "n8", start=None))
    pack = c.refresh()  # sorted by this pack's own clock: still exact
    assert (pack.made, pack.nodes_repacked) == ("advanced", 1)
    row = [p.metadata.name for p in pack.pods_by_node[pack.node_index["n8"]]]
    assert row.index("beside-it") < row.index("skewed")
    c.remove(c.on("n0")[3].metadata.name)
    pack = c.refresh()
    assert (pack.made, pack.why) == ("built", "clock")
    still_clock.time = lambda: NOW + 120.0
    c.remove(c.on("n0")[3].metadata.name)
    pack = c.refresh()  # n8 was not named: the whole build sorts it anew
    assert (pack.made, pack.why) == ("built", "clock")
    row = [p.metadata.name for p in pack.pods_by_node[pack.node_index["n8"]]]
    assert row.index("skewed") < row.index("beside-it")
    c.remove(c.on("n0")[3].metadata.name)
    pack = c.refresh()  # every start time is behind the clock again
    assert (pack.made, pack.nodes_repacked) == ("advanced", 1)


def test_a_named_node_the_store_lists_otherwise_is_not_trusted():
    """The log names a node whose place in the list holds another: the
    rows are given up, whatever the membership mark says."""
    c = Cluster(6)
    c.refresh()
    c.remove(c.on("n2")[1].metadata.name)
    c.cache.update_snapshot(c.snapshot)
    lst = list(c.snapshot.node_info_list)
    lst[2], lst[3] = lst[3], lst[2]
    c.snapshot.node_info_list = lst
    nt = c.tensors.update(c.snapshot)
    pack = c.facts.pack(c.snapshot, nt, [])
    assert (pack.made, pack.why) == ("built", "membership")
    assert_same_pack(
        pack, preemption.pack_preemption_state(c.snapshot, nt, []))


# -- readers of an older pack, and two threads at once -------------------------


def test_a_published_pack_is_not_written_by_the_next_advance():
    c = Cluster(7)
    c.refresh()
    c.remove(c.on("n1")[2].metadata.name)
    held = c.refresh()
    assert held.made == "advanced"
    before = {name: getattr(held, name).copy() for name in ARRAYS}
    pods = [list(row) for row in held.pods_by_node]
    for node in ("n1", "n4", "n9"):
        c.remove(c.on(node)[0].metadata.name)
        c.add(resident(c.new_name("high"), node, prio=100))
    newer = c.refresh()
    assert newer.nodes_repacked == 3 and newer.prio is not held.prio
    for name in ARRAYS:
        assert np.array_equal(getattr(held, name), before[name]), name
    assert [list(row) for row in held.pods_by_node] == pods


def test_the_prewarm_thread_and_a_wave_race_for_one_generation(monkeypatch):
    """Both want the pack of the same generation: one advances, the
    other waits for it or advances nothing, and the wave's victims are
    those a preemptor with no kept rows finds."""
    c = Cluster(8, nodes=6)
    nodes = list(c.nodes.values())
    algorithm, fw = _env(list(c.pods.values()), nodes)
    queue = _queue(fw)  # earlier rounds' nominations count for both
    raced = Preemptor(algorithm, queue, None)
    calls = []
    real = PreemptFacts.pack

    def counted(self, snapshot, nt, pdbs):
        pack = real(self, snapshot, nt, pdbs)
        if self is raced._facts:
            calls.append((threading.current_thread().name, pack.made,
                          pack.nodes_repacked))
        return pack

    monkeypatch.setattr(PreemptFacts, "pack", counted)
    for rnd in range(4):
        wave = [make_pod(f"wave{rnd}-{k}").container(
            cpu="3000m", memory="6Gi").priority(100).obj() for k in range(3)]
        items = [(p, _fail(algorithm, fw, p)) for p in wave]
        del calls[:]
        raced.prewarm_pack_async()
        chosen, _ = raced.preempt_batch(fw, items)
        with raced._pack_cv:
            while raced._prewarm_busy:
                raced._pack_cv.wait(0.05)
        alone = Preemptor(algorithm, queue, None)
        assert alone.preempt_batch(fw, items)[0] == chosen
        assert all(chosen)
        stats = raced.last_wave
        assert stats["pack_nodes_kept"] + stats["pack_nodes_repacked"] == 6
        assert stats["pack"] in ("reused", "advanced", "built")
        assert (stats["pack"] == "built") == (
            rnd == 0 and calls[0][0] != "preempt-prewarm")
        assert sum(repacked for _t, _m, repacked in calls) == (
            6 if rnd == 0 else 2)
        assert_same_pack(raced._pack, preemption.pack_preemption_state(
            algorithm.snapshot, raced._tensor_cache.update(algorithm.snapshot),
            []))
        # the next generation: on two nodes a resident gives way to another
        for node in ("n1", "n4"):
            gone = next(p for p in algorithm.snapshot.get_node_info(node).pods
                        if p.spec.priority == 0)
            algorithm.cache.remove_pod(gone)
            algorithm.cache.add_pod(resident(f"next{rnd}-{node}", node))
        algorithm.cache.update_snapshot(algorithm.snapshot)
