"""Device-side score parity: the full default-provider Score plugin set
for the batch solver.

The default provider (reference algorithmprovider/registry.go:118-125)
scores with BalancedAllocation, ImageLocality, InterPodAffinity,
LeastAllocated, NodeAffinity, NodePreferAvoidPods w10000,
DefaultPodTopologySpread, TaintToleration (+ gated PodTopologySpread
soft scoring). The resource scorers already run in the scan
(ops/scores.py); this module packs the REST so batch-path rankings equal
the sequential path:

- **static rows** -- ImageLocality (image_locality.go:60
  calculatePriority), NodePreferAvoidPods (node_prefer_avoid_pods.go:53),
  preferred NodeAffinity raw weights (node_affinity.go Score), and
  TaintToleration's intolerable PreferNoSchedule count
  (taint_toleration.go Score) depend only on (pod spec, node spec), so
  pods sharing a score signature share one precomputed row. ImageLocality
  and PreferAvoidPods are final values (no normalize); NodeAffinity and
  TaintToleration ship RAW and are normalized per scan step over the
  step's feasible set, because the reference normalizes over the filtered
  node list (helper/normalize_score.go). ImageLocality is decided and
  built from the images' side (``Snapshot.image_holders``, one walk a
  node-spec epoch): a batch none of whose image lists can score a node
  above 0 carries no row for it, and a live list's row is built at the
  holders alone, once for as long as that index and the tensor's slot
  list stand (kept, with the zone rows, in the dispatcher's
  ``FamilyFacts``); the three others ask every node for each signature.
- **selector spread** (DefaultPodTopologySpread,
  default_pod_topology_spread.go:107) -- per combined-selector-group
  match counts per node, zone-blended (2/3) at normalize; counts replay
  within the batch like every other dynamic family.
- **soft topology spread** (podtopologyspread/scoring.go) -- per-group
  (namespace, key, selector) match counts per topology value with the
  flipped-linear normalize against (total - min) over feasible eligible
  nodes.

- **preferred inter-pod affinity** (interpodaffinity/scoring.go:110-268)
  -- weighted topology count tensors per deduplicated term: the incoming
  pod's preferred (anti-)affinity terms gather unweighted match counts
  (``ipa_counts``) scaled by the pod-side signed weights, and existing
  pods' terms (required affinity x hardPodAffinityWeight, preferred
  affinity +w, preferred anti-affinity -w) accumulate owner-weighted
  mass at the owner's topology value (``ipa_wcounts``) gathered where
  the incoming pod matches. Both tensors replay within the batch (a
  placed pod bumps counts it matches and contributes its own terms'
  mass), normalized per step [min,max] -> [0,100] over the feasible set
  with zero-seeded extremes (scoring.go:294).

The three dynamic families read what the dispatcher's ``FamilyFacts``
keeps (ops/family_facts.py): the pod census (a group's or a term's
matching residents by node row, advanced by the snapshot's change log),
the node-value rows (a topology key's value on every node row), the term
owners (the residents' signed weight by node row, counted with the
census) and, for the batch's own side, one ``default_selector`` and one
set of rows a pod TEMPLATE, not a pod. Their rows come in two shapes:
``MAX_SEL_GROUPS`` / ``MAX_IPA_ROWS`` where batch and cluster fit them,
``WIDE_SEL_GROUPS`` / ``WIDE_IPA_ROWS`` (a cluster's worth of Services
and of charts' soft anti-affinity terms) where not; a batch whose pods
ask for more than the wide shape is cut where the row past it is asked
for (``ScoreEnvelopeCut``), as a batch past the static rows is.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from kubernetes_tpu.api.selectors import labels_match_selector
from kubernetes_tpu.api.types import (
    Pod,
    TAINT_EFFECT_PREFER_NO_SCHEDULE,
)
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.ops import family_facts
from kubernetes_tpu.plugins.imagelocality import ImageLocality
from kubernetes_tpu.plugins.nodeaffinity import match_node_selector_term
from kubernetes_tpu.plugins.nodepreferavoidpods import (
    ANNOTATION_KEY as AVOID_ANNOTATION,
)
from kubernetes_tpu.plugins.podtopologyspread import (
    SCHEDULE_ANYWAY,
)
from kubernetes_tpu.plugins.selectorspread import (
    CombinedSelector,
    default_selector,
    get_zone_key,
)
from kubernetes_tpu.tensors.node_tensor import (
    NodeTensor,
    value_capacity as _value_capacity_shared,
)
from kubernetes_tpu.utils import flightrecorder, metrics

#: the static rows of a LIVE score family, always: one shape, so what
#: warm-up compiles is what every live batch runs, whatever it names.
#: Three ``[64, n]`` operands are 4.3 MB of VMEM and of upload at 5,632
#: node slots (``pallas_constrained.constrained_vmem_bytes`` counts them)
MAX_SCORE_SIGS = 64
#: the rows of an ABSENT family's placeholders: constants on the device,
#: operands of the constrained kernel all the same, so kept small
SIG_BUCKET = 4
MAX_SEL_GROUPS = 8
MAX_ZONES = 64
MAX_SOFT_GROUPS = 16
MAX_SOFT_CONSTRAINTS = 4
MAX_IPA_ROWS = 16
#: the wide shape of the selector-spread and preferred-affinity rows: a
#: batch (with the residents' terms, which make a row each whatever the
#: batch holds) that needs more than the two maxima above carries these
#: instead. At 5,632 node slots the constrained kernel holds them beside
#: ``SIG_BUCKET`` static rows inside its VMEM gate, and not beside
#: ``MAX_SCORE_SIGS`` (``pallas_constrained.constrained_vmem_bytes``)
WIDE_SEL_GROUPS = 64
WIDE_IPA_ROWS = 64


def _preferred_aff_terms(pod: Pod):
    a = pod.spec.affinity
    if a is None or a.pod_affinity is None:
        return []
    return a.pod_affinity.preferred_during_scheduling


def _preferred_anti_terms(pod: Pod):
    a = pod.spec.affinity
    if a is None or a.pod_anti_affinity is None:
        return []
    return a.pod_anti_affinity.preferred_during_scheduling


def _required_aff_terms(pod: Pod):
    a = pod.spec.affinity
    if a is None or a.pod_affinity is None:
        return []
    return a.pod_affinity.required_during_scheduling


def cluster_has_affinity_scoring(snapshot: Snapshot) -> bool:
    """True when any existing pod carries terms that score EVERY incoming
    pod symmetrically (scoring.go:111 processExistingPod: required
    affinity x hardPodAffinityWeight, preferred (anti-)affinity) -- such
    clusters need the preferred-affinity tensors for every batch."""
    for ni in snapshot.have_pods_with_affinity_list:
        for p in ni.pods_with_affinity:
            if family_facts.scoring_terms(p):
                return True
    return False


def batch_has_scoring_terms(pods: List[Pod]) -> bool:
    """True when placing any of these pods makes it a symmetric scorer
    for later pods (preferred terms, or required affinity terms via
    hardPodAffinityWeight) -- an in-flight batch with such pods must
    land before a later batch packs its ipa tensors."""
    return any(family_facts.scoring_terms(p) for p in pods)


def batch_score_dynamic(
    pods: List[Pod], informers, ipa_weight: int = 1
) -> bool:
    """True when the batch's scoring depends on host pod-placement state
    (selector spread, soft topology spread, or preferred inter-pod
    affinity) -- the dispatch pipeline must drain in-flight batches
    BEFORE packing such batches. ``ipa_weight`` gates the
    preferred-affinity check on the profile actually scoring with
    InterPodAffinity."""
    if any(_soft_constraints(p) for p in pods):
        return True
    if ipa_weight and any(
        _preferred_aff_terms(p) or _preferred_anti_terms(p) for p in pods
    ):
        return True
    return batch_selector_spread_live(pods, informers)


def batch_selector_spread_live(pods: List[Pod], informers) -> bool:
    """The informer-dependent slice of ``batch_score_dynamic``: selector
    spread is live for the batch when workload objects exist AND a pod
    without its own spread constraints matches one. Split out so the
    dispatcher can answer the spec-derived parts from the cached
    admission bits (scheduler/admission.py) and only pay this check
    against live cluster state."""
    if informers is None:
        return False
    if not any(
        (
            informers.services().list(),
            informers.replication_controllers().list(),
            informers.replica_sets().list(),
            informers.stateful_sets().list(),
        )
    ):
        return False
    asked = set()
    for p in pods:
        if p.spec.topology_spread_constraints:
            continue
        # the selector reads the namespace and the labels alone
        meta = p.metadata
        key = (meta.namespace, frozenset(meta.labels.items()))
        if key in asked:
            continue
        asked.add(key)
        if not default_selector(p, informers).empty:
            return True
    return False


class ScoreEnvelopeExceeded(Exception):
    """Batch exceeds the device scoring envelope: fall back to host.
    ``reason`` names the envelope, and every one raised is counted under
    it (``metrics.score_envelope_exceeded``)."""

    def __init__(self, reason: str) -> None:
        super().__init__(f"score envelope exceeded: {reason}")
        self.reason = reason
        metrics.score_envelope_exceeded.inc(reason=reason)


class ScoreEnvelopeCut(ScoreEnvelopeExceeded):
    """The batch's own pods ask for more rows than the envelope
    ``reason`` holds. ``fit`` (above 0) is how many of them, in the
    order given, come before the one that asked for the row past it: a
    batch of those is inside it, so the dispatcher cuts there and keeps
    both parts on the device (scheduler/batch.py ``ScoreSignatureCut``)."""

    def __init__(self, reason: str, fit: int) -> None:
        super().__init__(reason)
        self.fit = fit


def _past(reason: str, fit: int) -> ScoreEnvelopeExceeded:
    """The exception for a row asked for past the envelope ``reason`` by
    the pod at ``fit``: a cut where pods come before it, else (the first
    pod alone is past it) the host path."""
    if fit > 0:
        return ScoreEnvelopeCut(reason, fit)
    return ScoreEnvelopeExceeded(reason)


@dataclass
class ScoreBatch:
    """Packed score state (greedy_assign_constrained ``scoring`` operand).

    direct_rows    [U, N] float32  pre-weighted final scores (ImageLocality
                                   + NodePreferAvoidPods)
    nodeaff_rows   [U, N] int32    raw preferred-node-affinity weights
    taint_rows     [U, N] int32    raw intolerable PreferNoSchedule counts
    pod_sig        [B] int32       row index per pod
    sel_counts     [Gs, N] int32   selector-group match counts per node
    zone_onehot    [N, Z] bool     node -> zone membership
    zone_id        [N] int32       -1 = unzoned
    pod_sel_group  [B] int32       the pod's own selector group (-1 skip)
    pod_sel_match  [B, Gs] int32   placement bumps these groups
    soft_counts    [Gt, V] int32   soft-spread match counts per value
    soft_node_value[Gt, N] int32   per-group node topology value (-1 absent)
    pod_soft_groups[B, C] int32    the pod's soft constraint groups
    pod_soft_match [B, Gt] int32   placement bumps these groups
    ipa_node_value [Rp, N] int32   per-ipa-row node topology value
    ipa_counts     [Rp, V] f32     unweighted match counts per value
    ipa_wcounts    [Rp, V] f32     owner-weighted symmetric mass
    pod_ipa_weight [B, Rp] f32     incoming preferred +-weights per row
    pod_ipa_match  [B, Rp] f32     pod matches the row's selector
    pod_ipa_bump   [B, Rp] f32     pod's own signed term mass (replay)
    weights        [5] float32     (nodeaffinity, tainttoleration,
                                   selectorspread, softspread,
                                   interpodaffinity)
    """

    direct_rows: np.ndarray
    nodeaff_rows: np.ndarray
    taint_rows: np.ndarray
    pod_sig: np.ndarray
    sel_counts: np.ndarray
    zone_onehot: np.ndarray
    zone_id: np.ndarray
    pod_sel_group: np.ndarray
    pod_sel_match: np.ndarray
    soft_counts: np.ndarray
    soft_node_value: np.ndarray
    pod_soft_groups: np.ndarray
    pod_soft_match: np.ndarray
    ipa_node_value: np.ndarray  # [Rp, N] int32 per-row node topo value
    ipa_counts: np.ndarray  # [Rp, V] f32 unweighted match counts
    ipa_wcounts: np.ndarray  # [Rp, V] f32 owner-weighted symmetric mass
    pod_ipa_weight: np.ndarray  # [B, Rp] f32 incoming preferred +-w
    pod_ipa_match: np.ndarray  # [B, Rp] f32 pod matches row selector
    pod_ipa_bump: np.ndarray  # [B, Rp] f32 pod's own signed term mass
    weights: np.ndarray
    dynamic: bool = False  # True when sel/soft/ipa families are live


def _selector_sig(sel) -> Tuple:
    if sel is None:
        return ("<nil>",)
    return (
        tuple(sorted(sel.match_labels.items())),
        tuple(
            (r.key, r.operator, tuple(r.values)) for r in sel.match_expressions
        ),
    )


def _combined_sig(cs: CombinedSelector) -> Tuple:
    return (
        tuple(sorted(cs.match_labels.items())),
        tuple(_selector_sig(s) for s in cs.extra),
    )


def _static_sig(
    pod: Pod, image_scores: Dict, nodeaff: bool, taints: bool, avoid: bool
) -> Tuple:
    """What decides the pod's static score rows, and no more: a part
    whose family is not live for the batch is left out, so pods share a
    row whatever they differ in there. The container images (in the
    plugin's order: its sum is taken in it) where that list scores some
    node above 0 (``image_scores``, of ``_image_scores``); the preferred
    node affinity where some pod of the batch has one; the tolerations
    where some node carries a PreferNoSchedule taint; the controller
    where some node asks to be avoided."""
    images = None
    if image_scores:
        images = tuple([c.image for c in pod.spec.containers])
        if images not in image_scores:
            images = None
    aff = ()
    a = pod.spec.affinity
    if nodeaff and a is not None and a.node_affinity is not None:
        aff = tuple(
            (
                t.weight,
                tuple(
                    (r.key, r.operator, tuple(r.values))
                    for r in t.preference.match_expressions
                ),
                tuple(
                    (r.key, r.operator, tuple(r.values))
                    for r in t.preference.match_fields
                ),
            )
            for t in a.node_affinity.preferred_during_scheduling
        )
    tols = tuple(
        (t.key, t.operator, t.value, t.effect) for t in pod.spec.tolerations
    ) if taints else ()
    ctrl = None
    if avoid:
        controller = next(
            (r for r in pod.metadata.owner_references if r.controller), None
        )
        ctrl = (controller.kind, controller.uid) if controller else None
    return (images, aff, tols, ctrl)


def _soft_constraints(pod: Pod):
    return [
        c
        for c in pod.spec.topology_spread_constraints
        if c.when_unsatisfiable == SCHEDULE_ANYWAY
    ]


def _holds_image(ni) -> bool:
    return bool(ni.image_states)


def _soft_tainted(ni) -> bool:
    return ni.node is not None and any(
        t.effect == TAINT_EFFECT_PREFER_NO_SCHEDULE
        for t in ni.node.spec.taints
    )


def _asks_to_be_avoided(ni) -> bool:
    return (
        ni.node is not None
        and AVOID_ANNOTATION in ni.node.metadata.annotations
    )


_NODE_SIDE_FACTS = (_holds_image, _soft_tainted, _asks_to_be_avoided)


def _node_side_facts(snapshot: Snapshot) -> Tuple[bool, bool, bool]:
    """(any node holds an image, any carries a PreferNoSchedule taint,
    any carries the avoid-pods annotation): facts of the Node objects
    alone, so taken once for each of the snapshot's node-spec epochs and
    not at every batch, and kept on the snapshot with the epoch and the
    change-log cursor they were taken at. When the epoch has moved, a
    fact that did not hold can have come to hold only on a node that a
    refresh has cloned since (``Snapshot.changes_since``: every node
    whose object was written is among them), so those alone are asked; a
    fact that held is looked for again on every node, up to the first
    that has it. A truncated log asks every node, as first use does. A
    snapshot no cache feeds (epoch 0) cannot tell when its nodes change,
    and is walked at every call."""
    epoch = snapshot.node_spec_epoch
    kept = snapshot.score_facts if epoch else None
    if kept is not None and kept[0] == epoch:
        return kept[2]
    infos = snapshot.list_node_infos()
    held, changed = (True, True, True), None
    if kept is None:
        cursor = snapshot.change_cursor()
    else:
        names, _membership, cursor = snapshot.changes_since(kept[1])
        if names is not None:
            held = kept[2]
            info_map = snapshot.node_info_map
            changed = [info_map[name] for name in names if name in info_map]
    facts = tuple(
        any(map(fact, infos if was else changed))
        for fact, was in zip(_NODE_SIDE_FACTS, held)
    )
    snapshot.score_facts = (epoch, cursor, facts)
    return facts


def _image_rows(
    pods: List[Pod], snapshot: Snapshot, nt: NodeTensor, w_img: float,
    kept: family_facts.FamilyFacts,
) -> Tuple[int, Dict[Tuple[str, ...], np.ndarray]]:
    """ImageLocality for the batch, from the images' side
    (``Snapshot.image_holders``): how many distinct container image lists
    of ``pods`` name an image, and for each list that scores some node
    above 0 its row ``[n_cap]`` float32, ``w_img`` times the plugin's
    score at the tensor row of every node that holds any of its images
    and 0 elsewhere; every node of a list left out scores 0
    (image_locality.go:60-76). A list's row, or its being left out,
    depends on the image index, the node -> row map and the weight
    alone, so ``kept`` holds it for as long as the first two stand
    (``FamilyFacts.score_image_row``) and it is built where it is not
    there. The rows are shared between batches and not writeable."""
    holders = snapshot.image_holders()
    named = 0
    live: Dict[Tuple[str, ...], np.ndarray] = {}
    seen = set()
    for p in pods:
        images = tuple([c.image for c in p.spec.containers])
        if images in seen:
            continue
        seen.add(images)
        named += any(images)
        row = kept.score_image_row(
            holders, (w_img, images),
            lambda: _image_row(images, holders, snapshot, nt, w_img),
        )
        if row is not None:
            live[images] = row
    return named, live


def _image_row(
    images: Tuple[str, ...], holders: Dict, snapshot: Snapshot,
    nt: NodeTensor, w_img: float,
) -> Optional[np.ndarray]:
    """One image list's weighted row, or None where it scores 0 on
    every node.

    The list is first held to the most any node could have: every image
    of it at the largest size reported, on the share of nodes that hold
    it. A node's own sum takes a subset of those terms, in the same
    order, with sizes no larger, and float addition and
    ``calculatePriority`` are monotonic, so a bound of 0 is every node's
    0 exactly: no array is touched for an image no node holds, or one too
    small or on too few nodes to pass the plugin's threshold. Past the
    bound each holder's sum is taken over the containers in order, in
    float64, and put through the plugin's own ``calculatePriority``: the
    plugin's value for that node, bit for bit."""
    total_nodes = snapshot.num_nodes()
    held = [h for h in map(holders.get, images) if h is not None]
    bound = 0.0
    for h in held:
        bound += h.largest * (h.count / total_nodes)
    if not ImageLocality._calculate_priority(bound):
        return None
    sums = np.zeros(total_nodes, dtype=np.float64)
    for h in held:
        sums[h.positions] += h.sizes * (h.count / total_nodes)
    positions = np.nonzero(sums)[0]
    distinct, which = np.unique(sums[positions], return_inverse=True)
    scores = np.array(
        [ImageLocality._calculate_priority(float(x)) for x in distinct],
        dtype=np.int64,
    )[which]
    if not scores.any():
        return None
    row = np.zeros(nt.capacity, dtype=np.float32)
    row[nt.rows_for(snapshot.list_node_infos())[positions]] = w_img * scores
    row.flags.writeable = False
    return row


def _zone_rows(
    infos, node_rows: List[int], n_cap: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """``(zone_id [n_cap] int32, zone_onehot [n_cap, MAX_ZONES] bool)``
    of the nodes ``infos`` at the tensor rows ``node_rows``, zones
    interned first-seen in that order; None where the nodes name more
    than ``MAX_ZONES`` zones. Shared between batches and not writeable."""
    zone_ids: Dict[str, int] = {}
    zone_id = np.full(n_cap, -1, dtype=np.int32)
    for j, ni in zip(node_rows, infos):
        zk = get_zone_key(ni.node)
        if not zk:
            continue
        z = zone_ids.get(zk)
        if z is None:
            if len(zone_ids) >= MAX_ZONES:
                return None
            z = len(zone_ids)
            zone_ids[zk] = z
        zone_id[j] = z
    zone_onehot = np.zeros((n_cap, MAX_ZONES), dtype=bool)
    present = zone_id >= 0
    zone_onehot[np.nonzero(present)[0], zone_id[present]] = True
    zone_id.flags.writeable = False
    zone_onehot.flags.writeable = False
    return zone_id, zone_onehot


class _Templates(NamedTuple):
    """A batch's pods by what the dynamic families read of them."""

    index: np.ndarray  # [B] int64: the template of pod i
    firsts: List[Pod]  # a template's first pod, in first-pod order
    first_at: List[int]  # where in the batch that pod is


def _score_template_key(pod: Pod) -> Tuple:
    """Pods with equal keys get equal rows from the selector-spread and
    preferred-affinity sections: namespace, labels, whether the pod has
    spread constraints of its own (DefaultPodTopologySpread then skips
    it) and its scoring terms with their weights. Kept on the pod."""
    key = pod.__dict__.get("_score_tpl_memo")
    if key is None:
        meta = pod.metadata
        key = pod.__dict__["_score_tpl_memo"] = (
            meta.namespace, frozenset(meta.labels.items()),
            bool(pod.spec.topology_spread_constraints),
            tuple([
                (sig, weight, required)
                for sig, _term, weight, required
                in family_facts.scoring_terms(pod)
            ]),
        )
    return key


def _score_templates(pods: List[Pod]) -> _Templates:
    seen: Dict[Tuple, int] = {}
    firsts: List[Pod] = []
    first_at: List[int] = []
    index = np.empty(len(pods), dtype=np.int64)
    for i, pod in enumerate(pods):
        key = _score_template_key(pod)
        t = seen.get(key)
        if t is None:
            t = seen[key] = len(firsts)
            firsts.append(pod)
            first_at.append(i)
        index[i] = t
    return _Templates(index, firsts, first_at)


def _selector_rows(
    templates: _Templates,
    tpl_selectors: List[Optional[CombinedSelector]],
    kept: family_facts.FamilyFacts,
    n_cap: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Selector spread (default_pod_topology_spread.go:78): the groups
    ``(namespace, combined selector)`` of the batch's templates, each
    with the live residents it matches by node row, from the census.
    ``(counts [G, n_cap] int32, group of each template [T] int32 (-1:
    none), templates a group matches [T, G] int32)``."""
    firsts = templates.firsts
    groups: Dict[Tuple, int] = {}
    specs: List[Tuple[str, CombinedSelector, Tuple]] = []
    tpl_group = np.full(len(firsts), -1, dtype=np.int32)
    for t, cs in enumerate(tpl_selectors):
        if cs is None:
            continue
        namespace = firsts[t].metadata.namespace
        sig = ("combined", _combined_sig(cs))
        g = groups.get((namespace, sig))
        if g is None:
            if len(specs) >= WIDE_SEL_GROUPS:
                raise _past("selector_groups", templates.first_at[t])
            g = groups[(namespace, sig)] = len(specs)
            specs.append((namespace, cs, sig))
        tpl_group[t] = g
    own_row = kept.node_values(family_facts.ROW_INDEX).values
    counts = np.zeros((len(specs), n_cap), dtype=np.int32)
    tpl_match = np.zeros((len(firsts), len(specs)), dtype=np.int32)
    for g, (namespace, cs, sig) in enumerate(specs):
        classes = kept.matching_in(namespace, sig, cs.matches)
        counts[g] = kept.counts(classes, own_row, live_only=True)[:n_cap]
        for t, p in enumerate(firsts):
            if p.metadata.namespace == namespace and cs.matches(
                p.metadata.labels
            ):
                tpl_match[t, g] = 1
    return counts, tpl_group, tpl_match


def _soft_rows(
    pods: List[Pod], kept: family_facts.FamilyFacts, n_cap: int, b: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Soft topology spread (podtopologyspread/scoring.go): the groups
    ``(namespace, key, selector)`` of the batch's ScheduleAnyway
    constraints, each with its key's node-value row and the live
    residents it matches by value, from the census. ``(soft_counts,
    soft_node_value, pod_soft_groups, pod_soft_match)``."""
    v_soft = _value_capacity_shared(n_cap)
    soft_counts = np.zeros((MAX_SOFT_GROUPS, v_soft), dtype=np.int32)
    soft_node_value = np.full((MAX_SOFT_GROUPS, n_cap), -1, dtype=np.int32)
    pod_soft_groups = np.full((b, MAX_SOFT_CONSTRAINTS), -1, dtype=np.int32)
    pod_soft_match = np.zeros((b, MAX_SOFT_GROUPS), dtype=np.int32)
    soft_specs: List[Tuple[str, str, object]] = []
    soft_group_ids: Dict[Tuple, int] = {}
    for i, p in enumerate(pods):
        soft = _soft_constraints(p)
        if len(soft) > MAX_SOFT_CONSTRAINTS:
            raise _past("soft_constraints", i)
        # per-pod node eligibility scoping (the pod's own
        # nodeSelector/affinity, scoring.go:120) can't share group
        # counts -- the caller routes such pods to the host path
        for ci, c in enumerate(soft):
            sig = (
                p.metadata.namespace,
                c.topology_key,
                _selector_sig(c.label_selector),
            )
            g = soft_group_ids.get(sig)
            if g is None:
                if len(soft_specs) >= MAX_SOFT_GROUPS:
                    raise _past("soft_groups", i)
                g = len(soft_specs)
                soft_group_ids[sig] = g
                soft_specs.append(
                    (p.metadata.namespace, c.topology_key, c.label_selector)
                )
            pod_soft_groups[i, ci] = g
    for g, (ns, key, sel) in enumerate(soft_specs):
        values = kept.node_values(key)
        if values is None:
            raise ScoreEnvelopeExceeded("soft_values")
        soft_node_value[g] = values.values
        classes = kept.matching(
            [((ns,), sel, family_facts.selector_sig(sel))]
        )
        soft_counts[g] = kept.counts(classes, values.values, live_only=True)
        for i, p in enumerate(pods):
            if p.metadata.namespace == ns and labels_match_selector(
                p.metadata.labels, sel
            ):
                pod_soft_match[i, g] = 1
    return soft_counts, soft_node_value, pod_soft_groups, pod_soft_match


def _ipa_rows(
    templates: _Templates,
    kept: family_facts.FamilyFacts,
    n_cap: int,
    hard_weight: int,
) -> Tuple[np.ndarray, ...]:
    """Preferred inter-pod affinity (scoring.go:110-268): a row for
    every distinct term ``(namespaces, selector, key)`` that a resident
    carries (the term owners, which score every incoming pod) or a pod
    of the batch does, the residents' first, in an order their history
    does not change. For each row: the key's node-value row, the
    residents that MATCH the term by value (the incoming pod's own terms
    gather these, family a) and the signed weight of the residents that
    CARRY it by value (gathered where the incoming pod matches, family
    c); for each template: its signed weights by row, the rows it
    matches, and what it adds to the carried weight once placed.
    ``(node values [R, n_cap], counts [R, V], wcounts [R, V], weight
    [T, R], match [T, R], bump [T, R])``."""
    firsts = templates.firsts
    rows: List[Tuple] = []  # (namespaces, selector, selector sig, key)
    row_ids: Dict[Tuple, int] = {}

    def row_of(sig: Tuple, selector, at: int) -> int:
        r = row_ids.get(sig)
        if r is None:
            if len(rows) >= WIDE_IPA_ROWS:
                raise _past("preferred_affinity_rows", at)
            r = row_ids[sig] = len(rows)
            namespaces, sel_sig, key = sig
            rows.append((namespaces, selector, sel_sig, key))
        return r

    owners = [
        o for o in sorted(kept.term_owners(), key=lambda o: repr(o.sig))
        if o.preferred or (o.required and hard_weight > 0)
    ]
    for o in owners:
        row_of(o.sig, o.selector, 0)
    tpl_weight = np.zeros((len(firsts), WIDE_IPA_ROWS), dtype=np.float32)
    tpl_bump = np.zeros((len(firsts), WIDE_IPA_ROWS), dtype=np.float32)
    for t, p in enumerate(firsts):
        for sig, term, weight, required in family_facts.scoring_terms(p):
            if required and hard_weight <= 0:
                continue
            r = row_of(sig, term.label_selector, templates.first_at[t])
            if required:
                # the pod's own symmetric contribution once placed
                tpl_bump[t, r] += float(hard_weight)
            else:
                tpl_weight[t, r] += weight
                tpl_bump[t, r] += weight
    n_rows = len(rows)
    v_ipa = _value_capacity_shared(n_cap)
    node_value = np.full((n_rows, n_cap), -1, dtype=np.int32)
    counts = np.zeros((n_rows, v_ipa), dtype=np.float32)
    wcounts = np.zeros((n_rows, v_ipa), dtype=np.float32)
    tpl_match = np.zeros((len(firsts), n_rows), dtype=np.float32)
    for r, (namespaces, selector, sel_sig, key) in enumerate(rows):
        values = kept.node_values(key)
        if values is None:
            raise ScoreEnvelopeExceeded("preferred_affinity_values")
        node_value[r] = values.values
        classes = kept.matching([(namespaces, selector, sel_sig)])
        counts[r] = kept.counts(classes, values.values, live_only=False)
        for t, p in enumerate(firsts):
            if p.metadata.namespace in namespaces and labels_match_selector(
                p.metadata.labels, selector
            ):
                tpl_match[t, r] = 1.0
    for o in owners:
        r = row_ids[o.sig]
        at, mass = o.mass(hard_weight)
        vals = node_value[r][at]
        on = vals >= 0
        wcounts[r] = np.bincount(
            vals[on], weights=mass[on], minlength=v_ipa
        ).astype(np.float32)
    return (
        node_value, counts, wcounts,
        tpl_weight[:, :n_rows], tpl_match, tpl_bump[:, :n_rows],
    )


def pack_score_batch(
    pods: List[Pod],
    snapshot: Snapshot,
    nt: NodeTensor,
    informers,
    weights: Dict[str, int],
    hard_pod_affinity_weight: int = 1,
    cluster_affinity_scoring: Optional[bool] = None,
    admissions=None,
    facts=None,
) -> Optional[ScoreBatch]:
    """Returns None when no non-resource scorer can influence ranking for
    this batch (the common fast path); raises ScoreEnvelopeExceeded when
    the batch needs the host path. ``admissions`` are the pods'
    admission records (scheduler/admission.py), in any order. ``facts``
    is the dispatcher's ``FamilyFacts``, whose tally takes what this call
    found: ``score_image_sigs`` (distinct image lists the batch names,
    counted where some node holds an image), ``score_image_sigs_live``
    (those that score some node above 0), ``score_live`` (a
    ``ScoreBatch`` was returned) and ``score_sigs`` (the static rows its
    pods asked for, ``_static_sig``). A batch that asks for more than
    ``MAX_SCORE_SIGS`` rows raises ``ScoreEnvelopeCut`` with where to
    cut it."""
    infos = snapshot.list_node_infos()
    n_cap = nt.capacity
    b = len(pods)

    any_images, any_soft_taints, any_avoid = _node_side_facts(snapshot)
    # ImageLocality is live where some image list of the batch scores
    # some node above 0: a row of zeros ranks as no row does
    w_img = float(weights.get("ImageLocality", 0))
    image_sigs, image_scores = 0, {}
    # where the node-side rows are kept: ``facts`` made valid for this
    # snapshot and tensor, or an object dropped with the batch
    kept = None
    if any_images and w_img:
        kept = family_facts.attach(facts, snapshot, nt)
        with flightrecorder.stage("pack.score.images"):
            image_sigs, image_scores = _image_rows(
                pods, snapshot, nt, w_img, kept
            )
    need_images = bool(image_scores)
    if facts is not None:
        facts.score_image_sigs += image_sigs
        facts.score_image_sigs_live += len(image_scores)
    # per pod: the admission record's bits where it has them (the
    # dispatcher classified every pod at ingest), the walk where not
    if admissions is not None:
        need_nodeaff = any(a.node_pref for a in admissions)
        need_soft = any(a.score_soft for a in admissions)
        pods_prefer = any(a.score_pref for a in admissions)
    else:
        need_nodeaff = any(
            p.spec.affinity is not None
            and p.spec.affinity.node_affinity is not None
            and p.spec.affinity.node_affinity.preferred_during_scheduling
            for p in pods
        )
        need_soft = any(_soft_constraints(p) for p in pods)
        pods_prefer = any(
            _preferred_aff_terms(p) or _preferred_anti_terms(p)
            for p in pods
        )
    need_avoid = any_avoid
    need_taint = any_soft_taints

    # combined selectors only exist when owner objects do; one a pod
    # template (namespace, labels: all that ``default_selector`` reads)
    templates: Optional[_Templates] = None
    tpl_selectors: List[Optional[CombinedSelector]] = []
    need_sel = False
    if informers is not None and any(
        inf_list
        for inf_list in (
            informers.services().list(),
            informers.replication_controllers().list(),
            informers.replica_sets().list(),
            informers.stateful_sets().list(),
        )
    ):
        templates = _score_templates(pods)
        for p in templates.firsts:
            cs = None
            # DefaultPodTopologySpread skips a pod with constraints
            if not p.spec.topology_spread_constraints:
                cs = default_selector(p, informers)
                if cs.empty:
                    cs = None
                else:
                    need_sel = True
            tpl_selectors.append(cs)
    # preferred inter-pod affinity is live when any incoming pod carries
    # preferred terms OR any existing pod scores incoming pods
    # symmetrically (scoring.go:111; the caller may pass the cluster
    # answer it already computed for its drain decision)
    if cluster_affinity_scoring is None:
        cluster_affinity_scoring = cluster_has_affinity_scoring(snapshot)
    need_ipa = bool(weights.get("InterPodAffinity", 0)) and bool(
        pods_prefer or cluster_affinity_scoring
    )

    if not (
        need_images or need_nodeaff or need_avoid or need_taint
        or need_soft or need_sel or need_ipa
    ):
        metrics.score_family_batches.inc(live="false")
        return None

    info_rows = nt.rows_for(infos)
    node_rows = info_rows.tolist()
    # ---- static rows ------------------------------------------------------
    # where no static family is live every pod's signature is the same
    # one, and its row of zeros ranks as no row does: the placeholders'
    # ``SIG_BUCKET`` rows then, so that a batch the dynamic families
    # alone make live leaves the kernel's VMEM to their rows
    static_live = need_images or need_nodeaff or need_avoid or need_taint
    sig_rows = MAX_SCORE_SIGS if static_live else SIG_BUCKET
    sig_ids: Dict[Tuple, int] = {}
    pod_sig = np.zeros(b, dtype=np.int32)
    sig_pods: List[Pod] = []
    if not static_live:
        sig_pods = pods[:1]  # every pod at row 0
    else:
        for i, p in enumerate(pods):
            sig = _static_sig(
                p, image_scores, need_nodeaff, need_taint, need_avoid
            )
            u = sig_ids.get(sig)
            if u is None:
                if len(sig_pods) >= MAX_SCORE_SIGS:
                    raise ScoreEnvelopeCut("score_signatures", i)
                u = len(sig_pods)
                sig_ids[sig] = u
                sig_pods.append(p)
            pod_sig[i] = u

    # one shape whatever the batch names: the rows past ``u_count`` stay 0
    u_count = len(sig_pods)
    direct_rows = np.zeros((sig_rows, n_cap), dtype=np.float32)
    nodeaff_rows = np.zeros((sig_rows, n_cap), dtype=np.int32)
    taint_rows = np.zeros((sig_rows, n_cap), dtype=np.int32)

    w_avoid = float(weights.get("NodePreferAvoidPods", 0))
    if need_images:
        for u, p in enumerate(sig_pods):
            row = image_scores.get(
                tuple([c.image for c in p.spec.containers])
            )
            if row is not None:
                direct_rows[u] = row
    # the families no image index serves: every node, for each signature
    if need_avoid or need_nodeaff or need_taint:
        for u, p in enumerate(sig_pods):
            na = (
                p.spec.affinity.node_affinity.preferred_during_scheduling
                if (
                    p.spec.affinity is not None
                    and p.spec.affinity.node_affinity is not None
                )
                else []
            )
            for j, ni in zip(node_rows, infos):
                node = ni.node
                if node is None:
                    continue
                if need_avoid:
                    direct_rows[u, j] += w_avoid * _avoid_score(p, node)
                if need_nodeaff:
                    count = 0
                    for term in na:
                        if term.weight and match_node_selector_term(
                            node.metadata.labels,
                            term.preference,
                            {"metadata.name": node.metadata.name},
                        ):
                            count += term.weight
                    nodeaff_rows[u, j] = count
                if need_taint:
                    taint_rows[u, j] = sum(
                        1
                        for t in node.spec.taints
                        if t.effect == TAINT_EFFECT_PREFER_NO_SCHEDULE
                        and not any(
                            tol.tolerates(t) for tol in p.spec.tolerations
                        )
                    )

    # ---- zones ------------------------------------------------------------
    with flightrecorder.stage("pack.score.zones"):
        if kept is None:
            kept = family_facts.attach(facts, snapshot, nt)
        zones = kept.score_zones(lambda: _zone_rows(infos, node_rows, n_cap))
        if zones is None:
            raise ScoreEnvelopeExceeded("zones")
        zone_id, zone_onehot = zones

    # ---- the dynamic families, from the kept facts ------------------------
    dynamic = need_sel or need_soft or need_ipa
    recounted0 = kept.nodes_recounted
    if dynamic and templates is None:
        templates = _score_templates(pods)
    sel = soft = ipa = None
    if dynamic:
        with flightrecorder.stage("pack.score.dynamic"):
            if need_sel:
                with flightrecorder.stage("pack.score.selectors") as st:
                    sel = _selector_rows(
                        templates, tpl_selectors, kept, n_cap
                    )
                    st.set_metadata(groups=len(sel[0]))
            if need_soft:
                soft = _soft_rows(pods, kept, n_cap, b)
            if need_ipa:
                with flightrecorder.stage("pack.score.ipa") as st:
                    ipa = _ipa_rows(
                        templates, kept, n_cap, hard_pod_affinity_weight
                    )
                    st.set_metadata(rows=len(ipa[0]))
    # one of two shapes: the wide rows where either family needs them
    n_sel = 0 if sel is None else len(sel[0])
    n_ipa = 0 if ipa is None else len(ipa[0])
    wide = n_sel > MAX_SEL_GROUPS or n_ipa > MAX_IPA_ROWS
    sel_cap = WIDE_SEL_GROUPS if wide else MAX_SEL_GROUPS
    ipa_cap = WIDE_IPA_ROWS if wide else MAX_IPA_ROWS
    v_cap = _value_capacity_shared(n_cap)

    sel_counts = np.zeros((sel_cap, n_cap), dtype=np.int32)
    pod_sel_group = np.full(b, -1, dtype=np.int32)
    pod_sel_match = np.zeros((b, sel_cap), dtype=np.int32)
    if sel is not None:
        counts, tpl_group, tpl_match = sel
        sel_counts[:n_sel] = counts
        pod_sel_group = tpl_group[templates.index]
        pod_sel_match[:, :n_sel] = tpl_match[templates.index]

    if soft is None:
        soft = (
            np.zeros((MAX_SOFT_GROUPS, v_cap), dtype=np.int32),
            np.full((MAX_SOFT_GROUPS, n_cap), -1, dtype=np.int32),
            np.full((b, MAX_SOFT_CONSTRAINTS), -1, dtype=np.int32),
            np.zeros((b, MAX_SOFT_GROUPS), dtype=np.int32),
        )
    soft_counts, soft_node_value, pod_soft_groups, pod_soft_match = soft

    ipa_node_value = np.full((ipa_cap, n_cap), -1, dtype=np.int32)
    ipa_counts = np.zeros((ipa_cap, v_cap), dtype=np.float32)
    ipa_wcounts = np.zeros((ipa_cap, v_cap), dtype=np.float32)
    pod_ipa_weight = np.zeros((b, ipa_cap), dtype=np.float32)
    pod_ipa_match = np.zeros((b, ipa_cap), dtype=np.float32)
    pod_ipa_bump = np.zeros((b, ipa_cap), dtype=np.float32)
    if ipa is not None:
        values, counts, wcounts, tpl_weight, tpl_match, tpl_bump = ipa
        ipa_node_value[:n_ipa] = values
        ipa_counts[:n_ipa] = counts
        ipa_wcounts[:n_ipa] = wcounts
        pod_ipa_weight[:, :n_ipa] = tpl_weight[templates.index]
        pod_ipa_match[:, :n_ipa] = tpl_match[templates.index]
        pod_ipa_bump[:, :n_ipa] = tpl_bump[templates.index]
    if facts is not None and dynamic:
        facts.score_dynamic_rows += n_sel + n_ipa
        facts.score_census_nodes += len(infos)
        facts.score_census_recounted += kept.nodes_recounted - recounted0

    w = np.array(
        [
            float(weights.get("NodeAffinity", 0)),
            float(weights.get("TaintToleration", 0)),
            float(weights.get("DefaultPodTopologySpread", 0)),
            float(weights.get("PodTopologySpread", 0)),
            float(weights.get("InterPodAffinity", 0)),
        ],
        dtype=np.float32,
    )
    metrics.score_family_batches.inc(live="true")
    if facts is not None:
        facts.score_live += 1
        facts.score_sigs += u_count
    return ScoreBatch(
        direct_rows=direct_rows,
        nodeaff_rows=nodeaff_rows,
        taint_rows=taint_rows,
        pod_sig=pod_sig,
        sel_counts=sel_counts,
        zone_onehot=zone_onehot,
        zone_id=zone_id,
        pod_sel_group=pod_sel_group,
        pod_sel_match=pod_sel_match,
        soft_counts=soft_counts,
        soft_node_value=soft_node_value,
        pod_soft_groups=pod_soft_groups,
        pod_soft_match=pod_soft_match,
        ipa_node_value=ipa_node_value,
        ipa_counts=ipa_counts,
        ipa_wcounts=ipa_wcounts,
        pod_ipa_weight=pod_ipa_weight,
        pod_ipa_match=pod_ipa_match,
        pod_ipa_bump=pod_ipa_bump,
        weights=w,
        dynamic=dynamic,
    )


def _avoid_score(pod: Pod, node) -> float:
    """node_prefer_avoid_pods.go:53 semantics on raw objects."""
    raw = node.metadata.annotations.get(AVOID_ANNOTATION)
    if not raw:
        return 100.0
    import json as _json

    controller = next(
        (r for r in pod.metadata.owner_references if r.controller), None
    )
    if controller is None or controller.kind not in (
        "ReplicationController",
        "ReplicaSet",
    ):
        return 100.0
    try:
        avoids = _json.loads(raw).get("preferAvoidPods", [])
    except (ValueError, AttributeError):
        return 100.0
    for entry in avoids:
        ref = entry.get("podSignature", {}).get("podController", {})
        # exact UID equality: the reference compares the full controller
        # ref including UID (node_prefer_avoid_pods.go), so a malformed
        # annotation without a uid never matches
        if (
            ref.get("kind") == controller.kind
            and ref.get("uid") == controller.uid
        ):
            return 0.0
    return 100.0


def noop_score_tensors(
    padded: int, n_cap: int, live_shape: bool = False
) -> Tuple[np.ndarray, ...]:
    """All-inactive scoring tensors, in kernel argument order: the
    placeholders of an absent family (``SIG_BUCKET`` static rows) or,
    with ``live_shape``, what a live batch that scores nothing would
    upload (``MAX_SCORE_SIGS`` rows), which is the shape warm-up
    compiles the family at."""
    sig_rows = MAX_SCORE_SIGS if live_shape else SIG_BUCKET
    return (
        np.zeros((sig_rows, n_cap), dtype=np.float32),
        np.zeros((sig_rows, n_cap), dtype=np.int32),
        np.zeros((sig_rows, n_cap), dtype=np.int32),
        np.zeros(padded, dtype=np.int32),
        np.zeros((MAX_SEL_GROUPS, n_cap), dtype=np.int32),
        np.zeros((n_cap, MAX_ZONES), dtype=bool),
        np.full(n_cap, -1, dtype=np.int32),
        np.full(padded, -1, dtype=np.int32),
        np.zeros((padded, MAX_SEL_GROUPS), dtype=np.int32),
        np.zeros(
            (MAX_SOFT_GROUPS, _value_capacity_shared(n_cap)), dtype=np.int32
        ),
        np.full((MAX_SOFT_GROUPS, n_cap), -1, dtype=np.int32),
        np.full((padded, MAX_SOFT_CONSTRAINTS), -1, dtype=np.int32),
        np.zeros((padded, MAX_SOFT_GROUPS), dtype=np.int32),
        np.full((MAX_IPA_ROWS, n_cap), -1, dtype=np.int32),
        np.zeros(
            (MAX_IPA_ROWS, _value_capacity_shared(n_cap)), dtype=np.float32
        ),
        np.zeros(
            (MAX_IPA_ROWS, _value_capacity_shared(n_cap)), dtype=np.float32
        ),
        np.zeros((padded, MAX_IPA_ROWS), dtype=np.float32),
        np.zeros((padded, MAX_IPA_ROWS), dtype=np.float32),
        np.zeros((padded, MAX_IPA_ROWS), dtype=np.float32),
        np.zeros(5, dtype=np.float32),
    )


def pad_score_tensors(sb: ScoreBatch, padded: int) -> Tuple[np.ndarray, ...]:
    """Pad per-pod arrays (already in solve order) to the fixed batch
    axis, kernel argument order."""
    b = sb.pod_sig.shape[0]

    def pad_pods(a: np.ndarray, fill) -> np.ndarray:
        out = np.full((padded,) + a.shape[1:], fill, dtype=a.dtype)
        out[:b] = a
        return out

    return (
        sb.direct_rows,
        sb.nodeaff_rows,
        sb.taint_rows,
        pad_pods(sb.pod_sig, 0),
        sb.sel_counts,
        sb.zone_onehot,
        sb.zone_id,
        pad_pods(sb.pod_sel_group, -1),
        pad_pods(sb.pod_sel_match, 0),
        sb.soft_counts,
        sb.soft_node_value,
        pad_pods(sb.pod_soft_groups, -1),
        pad_pods(sb.pod_soft_match, 0),
        sb.ipa_node_value,
        sb.ipa_counts,
        sb.ipa_wcounts,
        pad_pods(sb.pod_ipa_weight, 0.0),
        pad_pods(sb.pod_ipa_match, 0.0),
        pad_pods(sb.pod_ipa_bump, 0.0),
        sb.weights,
    )
