"""What the victim search keeps from wave to wave.

``pack_preemption_state`` (ops/preemption.py) sorts and packs every
resident of the cluster. Between two waves the pods of few nodes move
(a wave's victims, its preemptors, the refill), and the snapshot says
which: ``Snapshot.changes_since``, the cursor-read change log that
``NodeTensorCache`` and ``ops/family_facts.py`` follow. ``PreemptFacts``
keeps the pack's per-node rows for the snapshot it is bound to and, for
the next pack, sorts and packs again only the nodes the log names.

What is kept: the node order and ``node_index``; each node's pods in
``MoreImportantPod`` order; the rows ``prio``, ``req``, ``active`` and
``pdb_match``; the pods' ABSOLUTE start times with a mark for the pods
that have none; a cursor into the log. What every pack takes anew:
``alloc`` and ``base_requested`` from the node tensor, and
``start_rel``, which is relative to the earliest active pod of *this*
pack, a pod without a start time reading *this* pack's clock.

A published ``PreemptionPack`` is never written again (a wave, the
prewarm thread and ``victims_for_node`` may hold an older one): an
advance copies the kept arrays, writes the changed rows into the
copies, and keeps and publishes those.

The result is the whole build's, array for array
(``tests/test_preempt_facts.py``). Wherever the store cannot see that
its rows still hold, it takes the whole build and starts from that
(``PreemptionPack.why`` names the reason): first use, another snapshot
object, a truncated log, a membership move, a named node the store
lists otherwise, a pod count that leaves the ``v_max`` bucket either
way, other PDBs, a resource dimension more, or a start time that is
not in the past of every clock a kept row was sorted by (the order of
a pod without a start time among the others would then move with the
clock). No option chooses: the code does less where less changed.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from kubernetes_tpu.api.selectors import labels_match_mask
from kubernetes_tpu.api.types import Pod, PodDisruptionBudget
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.ops import preemption as _whole
from kubernetes_tpu.ops.preemption import _INT_MIN, PreemptionPack
from kubernetes_tpu.tensors.node_tensor import NodeTensor, pod_request_rows


def pdb_key(pdbs: List[PodDisruptionBudget]) -> Tuple:
    """What of the PDBs a pack depends on, and what tells that it
    moved: the PDB part of the Preemptor's pack key."""
    return tuple(
        (
            pdb.metadata.namespace, pdb.metadata.name,
            pdb.metadata.resource_version,
            pdb.status.disruptions_allowed,
        )
        for pdb in pdbs
    )


def victim_bucket(most: int) -> int:
    """``pack_preemption_state``'s power-of-two victim axis for the
    fullest node's pod count."""
    return max(8, 1 << (most - 1).bit_length() if most > 1 else 8)


def start_times(pods: List[Pod]) -> Tuple[np.ndarray, np.ndarray]:
    """``(absolute start times, which pods have none)``; 0 stands where
    there is none, for the pack's clock to be written over."""
    starts = np.array(
        [
            np.nan if p.status.start_time is None else p.status.start_time
            for p in pods
        ],
        dtype=np.float64,
    )
    none = np.isnan(starts)
    starts[none] = 0.0
    return starts, none


class PreemptFacts:
    def __init__(self) -> None:
        # one thread advances at a time (the wave's and the prewarm's)
        self._lock = threading.Lock()
        self._snapshot: Optional[Snapshot] = None
        self._cursor = 0
        self._pdb_key: Tuple = ()
        self._node_names: List[str] = []
        self._node_index: Dict[str, int] = {}
        # the rows: the last published pack's and the store's own two,
        # all copied before they are written
        self._pods_by_node: List[List[Pod]] = []
        self._prio = self._req = self._active = self._pdb_match = None
        self._pdb_allowed = None
        self._v_max = 0
        self._start_abs = self._no_start = None
        # the kept orders stand while every start time is earlier than
        # every clock a pod without one was (and is now) sorted by
        self._latest_start = -np.inf
        self._earliest_now = np.inf

    def pack(
        self, snapshot: Snapshot, nt: NodeTensor,
        pdbs: List[PodDisruptionBudget],
    ) -> PreemptionPack:
        """The pack of ``snapshot`` as it stands: the kept rows advanced
        by the change log, or the whole build where they cannot be."""
        with self._lock:
            now = time.time()
            pack = self._advance(snapshot, nt, pdbs, now)
            if isinstance(pack, str):
                pack = self._build(snapshot, nt, pdbs, now, why=pack)
            return pack

    # -- the whole build, and starting from it --------------------------------

    def _build(
        self, snapshot: Snapshot, nt: NodeTensor,
        pdbs: List[PodDisruptionBudget], now: float, why: str,
    ) -> PreemptionPack:
        self._snapshot = None  # nothing is kept if the build raises
        # the log's position BEFORE the lists are read: what a racing
        # refresh adds is named again by the next read
        cursor = snapshot.change_cursor()
        pack = _whole.pack_preemption_state(snapshot, nt, pdbs)
        n = len(pack.node_names)
        pack.made, pack.why = "built", why
        pack.nodes_kept, pack.nodes_repacked = 0, n
        self._cursor = cursor
        self._pdb_key = pdb_key(pdbs)
        self._node_names = pack.node_names
        self._node_index = pack.node_index
        self._pods_by_node = pack.pods_by_node
        self._prio, self._req = pack.prio, pack.req
        self._active, self._pdb_match = pack.active, pack.pdb_match
        self._pdb_allowed = pack.pdb_allowed
        self._v_max = pack.v_max
        # a node's pods fill its first slots, so ``active`` in row-major
        # order is the pods node after node, each node's in its order
        starts, none = start_times(
            [p for pods in pack.pods_by_node for p in pods]
        )
        self._start_abs = np.zeros(pack.active.shape, dtype=np.float64)
        self._start_abs[pack.active] = starts
        self._no_start = np.zeros(pack.active.shape, dtype=bool)
        self._no_start[pack.active] = none
        self._latest_start = starts[~none].max(initial=-np.inf)
        self._earliest_now = now  # taken before the build read its own
        self._snapshot = snapshot
        return pack

    # -- the advance ----------------------------------------------------------

    def _advance(
        self, snapshot: Snapshot, nt: NodeTensor,
        pdbs: List[PodDisruptionBudget], now: float,
    ):
        """The next pack from the kept rows, or the reason it cannot be
        made from them. Nothing kept is touched before the last check,
        and then by assignment of whole new arrays."""
        if self._snapshot is None:
            return "first"
        if snapshot is not self._snapshot:
            return "snapshot"
        if pdb_key(pdbs) != self._pdb_key:
            return "pdbs"
        if nt.dims.num_dims != self._req.shape[2]:
            return "dims"
        if not self._latest_start < min(self._earliest_now, now):
            return "clock"
        names, moved, cursor = snapshot.changes_since(self._cursor)
        if names is None:
            return "log_truncated"
        # the list AFTER the log: a named node's row is at least as new
        # as its note, and a refresh racing this read is named again
        infos = snapshot.node_info_list
        if moved or len(infos) != len(self._node_names):
            return "membership"
        v_max = self._v_max
        node_index = self._node_index
        changed: List[Tuple[int, NodeInfo]] = []
        for name in names:
            i = node_index.get(name)
            if i is None:
                ni = snapshot.node_info_map.get(name)
                if ni is not None and ni.node is not None:
                    return "membership"
                continue  # pods held for a name with no Node: on no row
            ni = infos[i]
            if ni.node_name != name:
                return "membership"
            if len(ni.pods) > v_max:
                return "v_max"
            changed.append((i, ni))
        if changed:
            counts = self._active.sum(axis=1)
            counts[[i for i, _ni in changed]] = [
                len(ni.pods) for _i, ni in changed
            ]
            if victim_bucket(int(counts.max())) != v_max:
                return "v_max"
            self._repack(changed, nt, pdbs, now)
        self._cursor = cursor
        self._earliest_now = min(self._earliest_now, now)
        return self._publish(snapshot, nt, now, repacked=len(changed))

    def _repack(
        self, changed: List[Tuple[int, NodeInfo]], nt: NodeTensor,
        pdbs: List[PodDisruptionBudget], now: float,
    ) -> None:
        """Sort and pack the pods of the ``(row, NodeInfo)`` pairs as
        ``pack_preemption_state`` does the cluster's: one lexsort, one
        gather of the request rows, one scatter a row array."""
        rows = np.array([i for i, _ni in changed], dtype=np.int64)
        pods: List[Pod] = []
        at: List[int] = []  # index into ``changed``
        for k, (_i, ni) in enumerate(changed):
            pods.extend(ni.pods)
            at.extend([k] * len(ni.pods))
        pods_by_node = list(self._pods_by_node)
        for i, _ni in changed:
            pods_by_node[i] = []
        prio = self._prio.copy()
        req = self._req.copy()
        active = self._active.copy()
        pdb_match = self._pdb_match.copy()
        start_abs = self._start_abs.copy()
        no_start = self._no_start.copy()
        prio[rows] = _INT_MIN
        req[rows] = 0
        active[rows] = False
        pdb_match[rows] = False
        start_abs[rows] = 0.0
        no_start[rows] = False
        if pods:
            at_arr = np.asarray(at, dtype=np.int64)
            prio_arr = np.array(
                [p.spec.priority for p in pods], dtype=np.int64
            )
            starts, none = start_times(pods)
            order = np.lexsort(
                (np.where(none, now, starts), -prio_arr, at_arr)
            )
            flat_pods = [pods[j] for j in order]
            flat_at = at_arr[order]
            per_node = np.bincount(at_arr, minlength=len(changed))
            first = np.zeros(len(changed), dtype=np.int64)
            first[1:] = np.cumsum(per_node)[:-1]
            flat_slot = np.arange(len(pods), dtype=np.int64) - first[flat_at]
            flat_node = rows[flat_at]
            for i, p in zip(flat_node.tolist(), flat_pods):
                pods_by_node[i].append(p)
            req[flat_node, flat_slot] = pod_request_rows(
                flat_pods, nt.dims
            )[0]
            prio[flat_node, flat_slot] = prio_arr[order]
            active[flat_node, flat_slot] = True
            start_abs[flat_node, flat_slot] = starts[order]
            no_start[flat_node, flat_slot] = none[order]
            self._latest_start = max(
                self._latest_start, starts[~none].max(initial=-np.inf)
            )
            if pdbs:
                labels_list = [p.metadata.labels for p in flat_pods]
                ns_arr = np.array(
                    [p.metadata.namespace for p in flat_pods], dtype=object
                )
                has_labels = np.array(
                    [bool(p.metadata.labels) for p in flat_pods], dtype=bool
                )
                for k, pdb in enumerate(pdbs):
                    if pdb.selector is None:
                        continue
                    mask = np.frombuffer(
                        labels_match_mask(labels_list, pdb.selector),
                        dtype=np.uint8,
                    ).astype(bool)
                    mask &= has_labels
                    mask &= ns_arr == pdb.metadata.namespace
                    pdb_match[flat_node, flat_slot, k] = mask
        self._pods_by_node = pods_by_node
        self._prio, self._req = prio, req
        self._active, self._pdb_match = active, pdb_match
        self._start_abs, self._no_start = start_abs, no_start

    def _publish(
        self, snapshot: Snapshot, nt: NodeTensor, now: float, repacked: int,
    ) -> PreemptionPack:
        names = self._node_names
        n = len(names)
        r = nt.dims.num_dims
        rows = np.array([nt.row(name) for name in names], dtype=np.int64)
        active = self._active
        # this pack's clock and this pack's earliest pod, whatever the
        # pack that sorted a row read
        start_rel = np.where(self._no_start, now, self._start_abs)
        if active.any():
            start_rel -= start_rel[active].min()
        pack = PreemptionPack()
        pack.node_names = names
        pack.node_index = self._node_index
        pack.pods_by_node = self._pods_by_node
        pack.alloc = (
            nt.allocatable[rows].astype(np.int32)
            if n else np.zeros((0, r), dtype=np.int32)
        )
        pack.base_requested = (
            nt.requested[rows].astype(np.int32)
            if n else np.zeros((0, r), dtype=np.int32)
        )
        pack.prio = self._prio
        pack.start_rel = start_rel
        pack.req = self._req
        pack.active = active
        pack.pdb_match = self._pdb_match
        pack.pdb_allowed = self._pdb_allowed
        pack.v_max = self._v_max
        pack.generation = getattr(snapshot, "generation", 0)
        pack.dev = {}
        pack.last_adims = None
        pack.made, pack.why = "advanced", ""
        pack.nodes_kept, pack.nodes_repacked = n - repacked, repacked
        return pack

