"""Snapshot: an immutable-for-the-cycle view of cluster state.

Reference: /root/reference/pkg/scheduler/internal/cache/snapshot.go:31 and
pkg/scheduler/listers/listers.go (SharedLister). The snapshot carries both
the object view (NodeInfo list for the host/oracle path) and, lazily, the
packed tensor view consumed by the TPU solver
(kubernetes_tpu.tensors.node_tensor).
"""

from __future__ import annotations

import threading
from bisect import bisect_right
from typing import Dict, Iterable, List, NamedTuple, Optional, Set, Tuple

import numpy as np

from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.cache.node_info import NodeInfo, pod_has_affinity_constraints
from kubernetes_tpu.utils import flightrecorder

#: the change log holds at least this many names, and twice the node
#: count where that is more: one refresh notes each node at most once,
#: so the newer half always holds the last refresh whole, and a consumer
#: that reads at every refresh is never sent to the full generation walk
#: (a constant of 4,096 was what one full batch of a 5,000-node burst hit)
CHANGE_TRACK_MIN = 4096


def _entry_seq(entry: Tuple[int, str]) -> int:
    return entry[0]


class ImageHolders(NamedTuple):
    """One image of ``Snapshot.image_holders``: the nodes that hold it."""

    positions: np.ndarray  # [count] int64, into node_info_list, ascending
    sizes: np.ndarray  # [count] float64, the image's bytes on that node
    count: int  # ImageStateSummary.NumNodes
    largest: int  # the most bytes any one node reports for it


class Snapshot:
    def __init__(self, node_infos: Optional[Dict[str, NodeInfo]] = None) -> None:
        self.node_info_map: Dict[str, NodeInfo] = node_infos or {}
        # Stable iteration order for the cycle (reference keeps nodeInfoList).
        self.node_info_list: List[NodeInfo] = [
            ni for ni in self.node_info_map.values() if ni.node is not None
        ]
        self.have_pods_with_affinity_list: List[NodeInfo] = [
            ni for ni in self.node_info_list if ni.pods_with_affinity
        ]
        self.generation: int = 0
        # -- what the cache that feeds this snapshot has told it ------------
        # (SchedulerCache.update_snapshot writes these under its lock)
        self.source: Optional[object] = None
        self.removals_seen = 0
        #: moves when the cache adds or removes a Node object or takes
        #: one whose labels, taints, ``unschedulable``, annotations or
        #: images differ; 0 while no cache has fed the snapshot. What
        #: depends on those alone is kept while it stands.
        self.node_spec_epoch = 0
        #: NodeInfos the last refresh cloned, and how many of the clones
        #: hold the fixed parts (``NodeInfo.shares_fixed_parts``) of the
        #: NodeInfo they took the place of: only their pods moved
        self.last_refreshed = 0
        self.last_shared = 0
        self._list_pos: Optional[Dict[str, int]] = None
        self._image_holders: Optional[Dict[str, ImageHolders]] = None
        #: the score packer's node-side facts (ops/scoring.py) with the
        #: node-spec epoch and the change-log cursor they were taken at;
        #: None until taken
        self.score_facts: Optional[
            Tuple[int, int, Tuple[bool, bool, bool]]
        ] = None
        # -- change tracking (epoch plumbing for the tensor packer) ---------
        # update_snapshot notes every name it re-clones in an APPEND-ONLY
        # sequence-stamped log so any NodeTensorCache can repack O(changed)
        # rows without walking all N NodeInfos per dispatch. Reads are
        # cursor-based and never mutate the log: the scheduler's cache,
        # the preemptor's sibling cache, and the prewarm thread's fresh
        # cache all share this snapshot, so a one-shot consume would let
        # one consumer steal another's notes (silently stale rows).
        self._change_lock = threading.Lock()
        self._change_log: List[Tuple[int, str]] = []
        self._change_seq = 0
        # seqs <= _dropped_seq may be missing from the log (cap overflow):
        # a cursor behind it must take the full generation walk
        self._dropped_seq = 0
        # seq of the last membership / ordering change
        self._membership_seq = 0

    # SharedLister surface ---------------------------------------------------

    def list_node_infos(self) -> List[NodeInfo]:
        return self.node_info_list

    def get_node_info(self, name: str) -> Optional[NodeInfo]:
        return self.node_info_map.get(name)

    def list_pods(self) -> List[Pod]:
        return [p for ni in self.node_info_list for p in ni.pods]

    def num_nodes(self) -> int:
        return len(self.node_info_list)

    # -- change tracking -----------------------------------------------------

    def note_changed(self, name: str) -> None:
        """update_snapshot re-cloned this node's NodeInfo."""
        self.note_changed_many((name,))

    def note_changed_many(self, names: Iterable[str]) -> None:
        with self._change_lock:
            log = self._change_log
            first = len(log)
            log.extend(enumerate(names, self._change_seq + 1))
            self._change_seq += len(log) - first
            if len(log) > max(CHANGE_TRACK_MIN, 2 * len(self.node_info_map)):
                # drop the older half; a cursor behind it takes the
                # full walk, one that reads at every refresh never is
                drop = len(log) // 2
                self._dropped_seq = log[drop - 1][0]
                del log[:drop]

    def note_membership_change(self) -> None:
        """A node appeared in / disappeared from the map (or lost its
        Node object): row identity may have moved."""
        with self._change_lock:
            self._change_seq += 1
            self._membership_seq = self._change_seq

    def change_cursor(self) -> int:
        """Current change-log position: the baseline for a NEW consumer
        (which must full-walk once, then read ``changes_since`` from
        here)."""
        with self._change_lock:
            return self._change_seq

    def changes_since(
        self, cursor: int
    ) -> Tuple[Optional[Set[str]], bool, int]:
        """Read-only cursor advance over the change log:
        ``(changed_names_or_None, membership_moved, new_cursor)``.
        ``None`` names mean the log was truncated past ``cursor`` (cap
        overflow) and the caller must fall back to the full generation
        walk. Never mutates the log, so any number of NodeTensorCache
        consumers can share one snapshot without stealing each other's
        notes."""
        with self._change_lock:
            membership_moved = self._membership_seq > cursor
            if cursor < self._dropped_seq:
                return None, membership_moved, self._change_seq
            # the log is seq-sorted (append-only, monotonic): bisect to
            # the cursor instead of rescanning all (up to cap) entries
            i = bisect_right(self._change_log, cursor, key=_entry_seq)
            names = {n for _s, n in self._change_log[i:]}
            return names, membership_moved, self._change_seq

    def replace_in_place(
        self,
        names: List[str],
        prevs: List[Optional[NodeInfo]],
        clones: List[NodeInfo],
        affinity_moved: bool,
    ) -> bool:
        """Put each clone where its predecessor stands in the map and
        the lists: the refresh of a snapshot whose node set has not
        moved. ``prevs[k]`` is what the map holds under ``names[k]``,
        ``affinity_moved`` whether any clone or predecessor has pods
        with affinity. Nothing is touched and False returned when
        the position index does not hold (the caller then takes the
        full walk). The list is copied before it is written: a reader
        on another thread that holds the old one (the preemptor, the
        prewarm thread) keeps seeing one refresh's state whole."""
        if not clones:
            return True
        pos = self._list_pos
        lst = self.node_info_list
        if pos is None:
            pos = self._list_pos = {
                ni.node.metadata.name: i for i, ni in enumerate(lst)
            }
        lst = list(lst)
        for i, prev, clone in zip(map(pos.get, names), prevs, clones):
            if i is None:
                # a NodeInfo with no node object is in the map alone
                if prev.node is not None:
                    return False
            elif lst[i] is not prev:
                return False
            else:
                lst[i] = clone
        self.node_info_map.update(zip(names, clones))
        self.node_info_list = lst
        if affinity_moved:
            self.have_pods_with_affinity_list = [
                ni for ni in lst if ni.pods_with_affinity
            ]
        self.note_changed_many(names)
        return True

    def refresh_stats(self) -> Dict[str, int]:
        """The last refresh as the ``sched/pack.snapshot`` span says it."""
        return {
            "nodes_refreshed": self.last_refreshed,
            "nodes_shared": self.last_shared,
        }

    def set_node_spec_epoch(self, epoch: int) -> None:
        if epoch != self.node_spec_epoch:
            self.node_spec_epoch = epoch
            self._image_holders = None

    def refresh_lists(self) -> None:
        old = self.node_info_list
        self.node_info_list = [
            ni for ni in self.node_info_map.values() if ni.node is not None
        ]
        # any change to the NAME SEQUENCE (add/remove/reorder) moves row
        # identity for the tensor packer -- flag it so the change-tracked
        # fast path never packs against a stale row layout
        if len(old) != len(self.node_info_list) or any(
            a.node_name != b.node_name
            for a, b in zip(old, self.node_info_list)
        ):
            self.note_membership_change()
        self.have_pods_with_affinity_list = [
            ni for ni in self.node_info_list if ni.pods_with_affinity
        ]
        self._image_holders = None
        self._list_pos = None

    def image_holders(self) -> Dict[str, ImageHolders]:
        """image name -> the nodes that hold it (reference
        ImageStateSummary, snapshot.go:124 createImageStates, which keeps
        the count alone). One walk of every node's ``image_states`` for
        each of the snapshot's node-spec epochs: a node's images are part
        of what moves the epoch, and ``refresh_lists`` drops the index
        with the positions it holds. A snapshot no cache feeds (epoch 0)
        cannot tell when its nodes change, and walks at every call. The
        walk is a ``sched/pack.image_index`` span of a profiler's trace,
        with the ``images`` and the (node, image) ``pairs`` it indexed."""
        index = self._image_holders if self.node_spec_epoch else None
        if index is None:
            with flightrecorder.stage("pack.image_index") as building:
                index = self._image_holders = self._build_image_holders()
                building.set_metadata(
                    images=len(index),
                    pairs=sum(h.count for h in index.values()),
                )
        return index

    def _build_image_holders(self) -> Dict[str, ImageHolders]:
        held: Dict[str, Tuple[List[int], List[int]]] = {}
        for pos, ni in enumerate(self.node_info_list):
            for image, size in (ni.image_states or {}).items():
                entry = held.get(image)
                if entry is None:
                    entry = held[image] = ([], [])
                entry[0].append(pos)
                entry[1].append(size)
        return {
            image: ImageHolders(
                np.array(positions, dtype=np.int64),
                np.array(sizes, dtype=np.float64),
                len(positions),
                max(sizes),
            )
            for image, (positions, sizes) in held.items()
        }


def new_snapshot(pods: Iterable[Pod], nodes: Iterable[Node]) -> Snapshot:
    """Test/bench helper, reference snapshot.go:51 NewSnapshot."""
    infos: Dict[str, NodeInfo] = {}
    for node in nodes:
        infos[node.metadata.name] = NodeInfo(node)
    for pod in pods:
        name = pod.spec.node_name
        if name and name in infos:
            infos[name].add_pod(pod)
    snap = Snapshot(infos)
    return snap
