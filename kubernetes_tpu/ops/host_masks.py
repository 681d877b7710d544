"""Host-side static feasibility masks for label-dependent filters.

Strings don't exist on device (SURVEY.md section 7 "hardest parts (c)"),
so the label-dependent Filter plugins -- NodeUnschedulable, NodeName,
NodeAffinity/nodeSelector, TaintToleration(NoSchedule) -- are evaluated on
the host into a ``[B, N]`` boolean mask the solver consumes. These checks
depend only on (pod spec, node spec), not on what else the batch places,
so they are safely hoisted out of the device replay loop.

Cost control: pods sharing a constraint signature (same selector/affinity/
toleration/nodeName shape) share one mask row, so the work is
O(distinct_templates x N), not O(B x N) -- the batch analogue of the
reference evaluating per pod with 16 goroutines
(generic_scheduler.go:490).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from kubernetes_tpu.api.types import (
    Pod,
    TAINT_EFFECT_NO_EXECUTE,
    TAINT_EFFECT_NO_SCHEDULE,
    Taint,
)
from kubernetes_tpu.cache.node_info import pod_host_ports, pod_hot_info
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.plugins.nodeaffinity import (
    pod_matches_node_selector_and_affinity,
)
from kubernetes_tpu.plugins.nodeunschedulable import TAINT_NODE_UNSCHEDULABLE
from kubernetes_tpu.tensors.node_tensor import NodeTensor

_UNSCHEDULABLE_TAINT = Taint(
    key=TAINT_NODE_UNSCHEDULABLE, effect=TAINT_EFFECT_NO_SCHEDULE
)

_EMPTY_SIG: Tuple = ("", (), (), ())

#: static mask rows a ``MaskRowCache`` keeps, least recently used out
#: first (a row is one byte a node slot: 64 rows of a 5,000-node
#: cluster are 360 KB)
MASK_ROWS_KEPT = 64


class MaskRowCache:
    """Static mask rows kept from batch to batch, by constraint
    signature. A row depends on the signature, the Node objects and the
    node -> tensor row map, so the rows stand while (a) the snapshot's
    ``node_spec_epoch`` says no Node object was added or removed and
    none changed its labels, taints or ``unschedulable`` (a status write
    that moves none of them keeps the rows) and (b) the tensor's slot ->
    name list is the one they were built for: a NodeTensorCache
    replaces that list at every change of membership and every full
    repack (layout, capacity), and shares it with no other cache. Anything else empties the cache. A snapshot no
    cache feeds (epoch 0) keeps nothing, and a signature with host
    ports is never kept: its row depends on ``used_ports``, which pods
    change. Rows are handed out read-only."""

    def __init__(self) -> None:
        self._epoch = 0
        self._names: Optional[List[str]] = None
        self._rows: "OrderedDict[Tuple, np.ndarray]" = OrderedDict()
        self.rows_built = 0
        self.rows_reused = 0

    def __len__(self) -> int:
        return len(self._rows)

    def validate(self, snapshot: Snapshot, nt: NodeTensor) -> bool:
        """Empty the cache unless it was filled for these Node objects
        and this row map; False when nothing may be kept at all."""
        epoch = snapshot.node_spec_epoch
        if epoch != self._epoch or nt.names is not self._names:
            self._epoch = epoch
            self._names = nt.names
            self._rows.clear()
        return bool(epoch)

    def get(self, sig: Tuple) -> Optional[np.ndarray]:
        row = self._rows.get(sig)
        if row is not None:
            self._rows.move_to_end(sig)
            self.rows_reused += 1
        return row

    def put(self, sig: Tuple, row: np.ndarray) -> None:
        row.flags.writeable = False
        self._rows[sig] = row
        if len(self._rows) > MASK_ROWS_KEPT:
            self._rows.popitem(last=False)


def _constraint_signature(pod: Pod) -> Tuple:
    """Pods with equal signatures produce identical static mask rows.
    Memoized per pod object (the pod-spec immutability contract of
    ``pod_resource_requests``): retries re-pack the same pod every
    batch."""
    memo = pod.__dict__.get("_sig_memo")
    if memo is not None:
        return memo
    spec = pod.spec
    if (
        not spec.node_name
        and not spec.node_selector
        and not spec.tolerations
        and (spec.affinity is None or spec.affinity.node_affinity is None)
        and not pod_hot_info(pod)[7]  # host ports, memoized at ingest
    ):
        # the burst common case: no placement constraints at all -- skip
        # the per-pod tuple assembly entirely
        pod.__dict__["_sig_memo"] = _EMPTY_SIG
        return _EMPTY_SIG
    sel = tuple(sorted(spec.node_selector.items()))
    aff = ()
    if spec.affinity is not None and spec.affinity.node_affinity is not None:
        na = spec.affinity.node_affinity
        if na.required_during_scheduling is not None:
            aff = tuple(
                (
                    tuple(
                        (r.key, r.operator, tuple(r.values))
                        for r in term.match_expressions
                    ),
                    tuple(
                        (r.key, r.operator, tuple(r.values))
                        for r in term.match_fields
                    ),
                )
                for term in na.required_during_scheduling.node_selector_terms
            )
    tols = tuple(
        (t.key, t.operator, t.value, t.effect) for t in spec.tolerations
    )
    memo = (spec.node_name, sel, aff, tols, pod_hot_info(pod)[7])
    pod.__dict__["_sig_memo"] = memo
    return memo


def _tolerates_node_taints(pod: Pod, node) -> bool:
    """tainttoleration filter semantics: every NoSchedule/NoExecute taint
    must be tolerated (v1/toleration.go + tainttoleration plugin)."""
    for taint in node.spec.taints:
        if taint.effect not in (TAINT_EFFECT_NO_SCHEDULE, TAINT_EFFECT_NO_EXECUTE):
            continue
        if not any(t.tolerates(taint) for t in pod.spec.tolerations):
            return False
    return True


def _build_mask_row(
    pod: Pod, infos, node_rows: List[int], capacity: int
) -> np.ndarray:
    row = np.zeros(capacity, dtype=bool)
    ports = pod_host_ports(pod)
    for j, ni in zip(node_rows, infos):
        node = ni.node
        if node is None:
            continue
        # same fake-taint check as the NodeUnschedulable plugin
        if node.spec.unschedulable and not any(
            t.tolerates(_UNSCHEDULABLE_TAINT)
            for t in pod.spec.tolerations
        ):
            continue
        if pod.spec.node_name and pod.spec.node_name != node.metadata.name:
            continue
        if not pod_matches_node_selector_and_affinity(pod, ni):
            continue
        if not _tolerates_node_taints(pod, node):
            continue
        # NodePorts (node_ports.go): exclude nodes whose
        # usedPorts conflict with the pod's host ports -- the
        # static row covers EXISTING pods; within-batch port
        # interactions are serialized by the dispatcher
        # (batch.py routes host-port pods one per solver batch)
        if ports and any(
            ni.used_ports.conflicts(ip, proto, port)
            for ip, proto, port in ports
        ):
            continue
        row[j] = True
    return row


def static_mask_compact(
    pods: List[Pod],
    snapshot: Snapshot,
    nt: NodeTensor,
    row_cache: Optional[MaskRowCache] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Deduplicated mask: (rows [U, capacity] bool, index [B] int32) with
    ``mask[b] == rows[index[b]]``. U = distinct constraint signatures --
    typically a handful -- so shipping (rows, index) to the device and
    gathering there cuts the per-batch host->device transfer from
    O(B x N) to O(U x N + B). With a ``row_cache`` a signature's row is
    built once for as long as the cache's conditions hold, and a batch
    costs O(distinct signatures), not O(signatures x N)."""
    index: List[int] = []
    usable = row_cache is not None and row_cache.validate(snapshot, nt)
    infos = node_rows = None
    seen: Dict[Tuple, int] = {}
    rows: List[np.ndarray] = []
    for pod in pods:
        sig = _constraint_signature(pod)
        u = seen.get(sig)
        if u is None:
            # a signature with host ports (its fifth part) is built anew
            keepable = usable and not (len(sig) > 4 and sig[4])
            row = row_cache.get(sig) if keepable else None
            if row is None:
                if infos is None:
                    infos = snapshot.list_node_infos()
                    node_rows = nt.rows_for(infos).tolist()
                row = _build_mask_row(pod, infos, node_rows, nt.capacity)
                if row_cache is not None:
                    row_cache.rows_built += 1
                if keepable:
                    row_cache.put(sig, row)
            u = len(rows)
            rows.append(row)
            seen[sig] = u
        index.append(u)
    return np.stack(rows), np.array(index, dtype=np.int32)


def static_mask(
    pods: List[Pod], snapshot: Snapshot, nt: NodeTensor
) -> np.ndarray:
    """[B, capacity] bool: label-level feasibility per (pod, node)."""
    rows, index = static_mask_compact(pods, snapshot, nt)
    return rows[index]


def mask_rows_upload(rows: np.ndarray, mesh=None) -> np.ndarray:
    """The ``[U, N]`` mask rows in their upload form. Single-device
    dispatch concatenates them into the int32 single-buffer upload
    (ops/assignment.solve_packed), so they convert to int32 here. On a
    MESH the rows ship as a bool piece: above
    ``assignment.MESH_MASK_SHARD_MIN_BYTES`` ``solve_packed`` pulls
    them out of the replicated buffer and device_puts them COLUMN-
    sharded over the node axis -- each shard's host->device link then
    carries only its ``[U, N/P]`` 1-byte columns instead of the full
    replicated 4-byte rows, the same routing the delta-scatter slots
    get (below the cutoff they stay in the buffer: the extra
    per-operand link round trip would cost more than the bytes
    save)."""
    if mesh is not None:
        return np.ascontiguousarray(rows, dtype=bool)
    return rows.astype(np.int32)
