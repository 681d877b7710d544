"""Device-side topology-spread: pair-count tensors + within-batch updates.

This vectorizes PodTopologySpread's DoNotSchedule filtering (reference
podtopologyspread/filtering.go: TpPairToMatchNum + criticalPaths min) for
the batch solver:

- Host side, constraints are deduplicated into GROUPS keyed by
  (namespace, topology_key, selector): one row of a ``[G, V]`` count
  tensor per group, where V indexes interned topology values for that
  group's key. Initial counts replicate calPreFilterState (existing
  matching pods per topology value over eligible nodes).
- Device side, the assignment scan carries the count tensor: placing a
  selector-matching pod scatter-adds into its group rows, which is the
  AddPod/updateWithPod increment (filtering.go:127) generalized to the
  whole batch -- pod i's placement changes pod j's skew the same way
  nominated-pod virtual adds do sequentially (SURVEY.md section 7 stage 5).
- The Filter check per candidate node: for every group g of the pod,
  ``count[g, value_of(node)] + self_match - min_value(count[g, :]) <=
  max_skew`` and the node must carry the topology key, mirroring
  filtering.go:322-330.

The min over values runs over pairs that exist among eligible nodes
(``value_valid``), matching the reference's min over pairs recorded at
PreFilter time.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from kubernetes_tpu.api.selectors import labels_match_selector
from kubernetes_tpu.api.types import LabelSelector, Pod
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.ops import family_facts
from kubernetes_tpu.ops.family_facts import (
    FamilyFacts,
    eligibility_sig as _eligibility_sig,
    hard_spread_constraints,
    selector_sig as _selector_sig,
)
from kubernetes_tpu.tensors.node_tensor import NodeTensor, value_capacity

MAX_GROUPS = 16  # batches needing more fall back to the host path
MAX_VALUES = 128  # floor; tensors.node_tensor.value_capacity grows it
MAX_CONSTRAINTS_PER_POD = 4
BIG = np.int32(1 << 20)  # "absent value" sentinel for the min-reduce


@dataclass
class SpreadBatch:
    """Packed spread state for one solver batch.

    group_counts  [G, V] int32   initial match counts per (group, value)
    value_valid   [G, V] bool    value exists among eligible nodes
    node_value    [G, N] int32   per-group interned value index of each
                                 node (-1 when the node lacks the key or
                                 fails the pod-independent eligibility)
    pod_groups    [B, C] int32   group index per pod constraint (-1 pad)
    pod_max_skew  [B, C] int32
    pod_self      [B, C] int32   1 if the pod matches the group selector
    pod_match     [B, G] int32   1 if placing the pod bumps the group's
                                 count (same namespace + selector match)
                                 -- the AddPod increment for EVERY group,
                                 not just the pod's own constraints
    """

    group_counts: np.ndarray
    value_valid: np.ndarray
    node_value: np.ndarray
    pod_groups: np.ndarray
    pod_max_skew: np.ndarray
    pod_self: np.ndarray
    pod_match: np.ndarray

    @property
    def num_groups(self) -> int:
        return self.group_counts.shape[0]


def pack_spread_batch(
    pods: List[Pod],
    snapshot: Snapshot,
    nt: NodeTensor,
    facts: Optional[FamilyFacts] = None,
) -> Optional[SpreadBatch]:
    """Returns None when the batch exceeds the device envelope (too many
    groups/values/constraints) -- caller falls back to the host path.
    The pod rows are built once a pod template, the node rows and the
    initial counts come from ``facts`` (ops/family_facts.py), which
    keeps them between batches where it may."""
    facts = family_facts.attach(facts, snapshot, nt)
    index, firsts = facts.batch_templates(pods)
    groups: Dict[Tuple, int] = {}
    # ns, key, sel, its signature, the scoping, a pod that has it
    specs: List[Tuple[str, str, Optional[LabelSelector], Tuple, Tuple, Pod]]
    specs = []

    t = len(firsts)
    tpl_groups = np.full((t, MAX_CONSTRAINTS_PER_POD), -1, dtype=np.int32)
    tpl_max_skew = np.zeros((t, MAX_CONSTRAINTS_PER_POD), dtype=np.int32)
    tpl_self = np.zeros((t, MAX_CONSTRAINTS_PER_POD), dtype=np.int32)

    for ti, pod in enumerate(firsts):
        hard = hard_spread_constraints(pod)
        if len(hard) > MAX_CONSTRAINTS_PER_POD:
            return None
        # Reference pair counting (common.go
        # nodeLabelsMatchSpreadConstraints) excludes a node from ALL of
        # a pod's constraints when it lacks ANY constraint key. Shared
        # group counts can't express that per-pod eligibility, so a pod
        # whose constraints span 2+ keys with incomplete node coverage
        # falls back to the host path (ADVICE round-1, medium).
        keys = {c.topology_key for c in hard}
        if len(keys) > 1 and any(facts.key_incomplete(k) for k in keys):
            return None
        # pair counting is scoped to nodes passing the pod's own
        # nodeSelector/affinity (filtering.go:245): the scoping is
        # part of the group identity, and the group's node_value
        # row is -1 on out-of-scope nodes (no counts, no bumps,
        # infeasible there -- matching the static mask)
        scope = _eligibility_sig(pod) if hard else None
        for ci, c in enumerate(hard):
            sel_sig = _selector_sig(c.label_selector)
            sig = (pod.metadata.namespace, c.topology_key, sel_sig, scope)
            g = groups.get(sig)
            if g is None:
                if len(groups) >= MAX_GROUPS:
                    return None
                g = len(groups)
                groups[sig] = g
                specs.append(
                    (
                        pod.metadata.namespace, c.topology_key,
                        c.label_selector, sel_sig, scope, pod,
                    )
                )
            tpl_groups[ti, ci] = g
            tpl_max_skew[ti, ci] = c.max_skew
            tpl_self[ti, ci] = int(
                labels_match_selector(pod.metadata.labels, c.label_selector)
            )

    if not groups:
        return None

    tpl_match = np.zeros((t, MAX_GROUPS), dtype=np.int32)
    for ti, pod in enumerate(firsts):
        for g, (ns, _key, sel, _sig, _scope, _rep) in enumerate(specs):
            if pod.metadata.namespace == ns and labels_match_selector(
                pod.metadata.labels, sel
            ):
                tpl_match[ti, g] = 1

    n_cap = nt.capacity
    v_cap = value_capacity(n_cap)
    group_counts = np.zeros((MAX_GROUPS, v_cap), dtype=np.int32)
    value_valid = np.zeros((MAX_GROUPS, v_cap), dtype=bool)
    node_value = np.full((MAX_GROUPS, n_cap), -1, dtype=np.int32)

    for g, (ns, key, sel, sel_sig, scope, rep) in enumerate(specs):
        row = facts.node_values(key, scope, rep)
        if row is None:
            return None
        node_value[g] = row.values
        value_valid[g] = row.valid
        # initial counts: existing same-namespace matching pods
        # (filtering.go:255; terminating pods skipped)
        group_counts[g] = facts.counts(
            facts.matching([((ns,), sel, sel_sig)]), row.values,
            live_only=True,
        )

    return SpreadBatch(
        group_counts=group_counts,
        value_valid=value_valid,
        node_value=node_value,
        pod_groups=tpl_groups[index],
        pod_max_skew=tpl_max_skew[index],
        pod_self=tpl_self[index],
        pod_match=tpl_match[index],
    )


def noop_spread_tensors(padded: int, n_cap: int):
    """All-inactive spread tensors (kernel no-op), in
    greedy_assign_constrained argument order."""
    return (
        np.zeros((MAX_GROUPS, value_capacity(n_cap)), dtype=np.int32),
        np.zeros((MAX_GROUPS, value_capacity(n_cap)), dtype=bool),
        np.full((MAX_GROUPS, n_cap), -1, dtype=np.int32),
        np.full((padded, MAX_CONSTRAINTS_PER_POD), -1, dtype=np.int32),
        np.zeros((padded, MAX_CONSTRAINTS_PER_POD), dtype=np.int32),
        np.zeros((padded, MAX_CONSTRAINTS_PER_POD), dtype=np.int32),
        np.zeros((padded, MAX_GROUPS), dtype=np.int32),
    )


def pad_spread_tensors(sp: SpreadBatch, padded: int):
    """Pad the per-pod arrays (already in solve order) to the fixed batch
    axis."""
    b = sp.pod_groups.shape[0]

    def pad_pods(a, fill):
        out = np.full((padded,) + a.shape[1:], fill, dtype=a.dtype)
        out[:b] = a
        return out

    return (
        sp.group_counts,
        sp.value_valid,
        sp.node_value,
        pad_pods(sp.pod_groups, -1),
        pad_pods(sp.pod_max_skew, 0),
        pad_pods(sp.pod_self, 0),
        pad_pods(sp.pod_match, 0),
    )
