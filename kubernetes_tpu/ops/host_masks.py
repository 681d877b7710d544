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

from typing import Dict, List, Tuple

import numpy as np

from kubernetes_tpu.api.types import (
    Pod,
    TAINT_EFFECT_NO_EXECUTE,
    TAINT_EFFECT_NO_SCHEDULE,
    Taint,
)
from kubernetes_tpu.cache.node_info import pod_host_ports
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
        and not any(p.host_port for c in spec.containers for p in c.ports)
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
    memo = (spec.node_name, sel, aff, tols, tuple(pod_host_ports(pod)))
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


def static_mask_compact(
    pods: List[Pod], snapshot: Snapshot, nt: NodeTensor
) -> Tuple[np.ndarray, np.ndarray]:
    """Deduplicated mask: (rows [U, capacity] bool, index [B] int32) with
    ``mask[b] == rows[index[b]]``. U = distinct constraint signatures --
    typically a handful -- so shipping (rows, index) to the device and
    gathering there cuts the per-batch host->device transfer from
    O(B x N) to O(U x N + B)."""
    infos = snapshot.list_node_infos()
    node_rows = nt.rows_for(infos).tolist()
    index = np.zeros(len(pods), dtype=np.int32)
    cache: Dict[Tuple, int] = {}
    rows: List[np.ndarray] = []
    for b, pod in enumerate(pods):
        sig = _constraint_signature(pod)
        u = cache.get(sig)
        if u is None:
            row = np.zeros(nt.capacity, dtype=bool)
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
