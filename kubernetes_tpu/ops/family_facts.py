"""What the family packers keep from batch to batch.

``pack_spread_batch``, ``pack_affinity_batch`` and ``add_host_port_rows``
(ops/topology.py, ops/affinity.py) read three kinds of facts, each kept
for as long as the code can see that it still holds, and no longer:

- **node-value rows**: for a ``(topology key, eligibility signature)``,
  the interned value of that key's label on every node row (-1 where
  the node lacks the key or is out of the signature's scope) and which
  value slots exist. They depend on the Node objects and on the
  node -> tensor row map, so they stand while the snapshot's
  ``node_spec_epoch`` and the tensor's slot list (``nt.names``, by
  identity) stand: what ``host_masks.MaskRowCache`` keys on. Values
  are interned first-seen in ``node_info_list`` order.
- **the pod census**: for every node row, how many resident pods of
  each class ``(namespace, labels)`` it holds, the terminating ones
  told apart (topology spread skips them, filtering.go:255; the
  affinity counts do not). It keeps, for every row it has counted, the
  pod objects it counted there and the class it put each in, and is
  advanced by the snapshot's change log (``Snapshot.changes_since``,
  read by cursor and never consumed): on a row the log names, the
  ``NodeInfo``'s pods now are held against the pods counted, by
  identity, and the counts move by the pods that came and the pods
  that went alone; a row whose pods are the ones counted is left as it
  is, however many rows the log names. Identity is enough because no
  writer of a cached pod edits it where it stands: the cache's
  ``update_pod`` takes one object out of the ``NodeInfo`` and puts
  another in, the apiserver's ``guaranteed_update`` copies before it
  writes, and a pod's class is read once, when the pod comes. A pod is
  held from the visit that counted it to the first visit of its row
  that no longer finds it, which is how long the snapshot's
  ``NodeInfo`` holds it anyway. First use, a truncated log, a
  membership move (the log's own flag, or a name it gives that the rows
  do not know), another snapshot and another slot list forget all of
  it and count every node from nothing, through the same routine: every
  pod of every row then "came". A group's count row is the sum, over
  the classes its selector matches, of the census scattered through
  the group's node-value row. Memory is O(resident pods), not classes
  x nodes: two list slots a pod.
- **pod templates**: a batch's pods by ``(namespace, labels,
  constraints)``; the packers build each template's rows once and
  write them for all its pods with one indexed numpy write.

The score packer (ops/scoring.py ``pack_score_batch``) keeps its own
node-side rows here too, and builds them itself:

- **the zone rows**: every node row's zone and the one-hot of it, or
  the verdict that the nodes name more zones than the tensors hold.
  They read the Node objects' labels and the row map alone, so they
  stand as the node-value rows do.
- **the image rows**: for a container image list, ImageLocality's
  weighted score on every node row, or the verdict that the list
  scores 0 everywhere. They are built from ``Snapshot.image_holders()``
  and stand while that index is the same object (the snapshot drops it
  when the epoch moves and when ``refresh_lists`` moves the positions
  it holds) and the slot list is.

Its dynamic sections (selector spread, soft topology spread, preferred
inter-pod affinity) read the census and the node-value rows as the hard
families do, and one fact more:

- **the term owners**: for every distinct scoring term some resident
  carries (``interpodaffinity/scoring.go`` processExistingPod: preferred
  affinity +w, preferred anti-affinity -w, required affinity times the
  profile's hardPodAffinityWeight), the signed weight its owners put on
  each node row, and how many owners that is. Counted from
  ``NodeInfo.pods_with_affinity`` at the first ``term_owners()`` and
  with the census after that, pod by pod: a pod that comes adds its
  terms to its row, one that goes takes them off, and a row leaves a
  term when its last owner does (a +w and a -w owner that cancel keep
  their 0.0). A dispatcher whose batches never score by such terms
  counts none.

The dispatcher owns one ``FamilyFacts`` beside its ``MaskRowCache`` and
hands it to the packers. Without one, or on a snapshot no cache feeds
(``node_spec_epoch`` 0), ``attach`` hands out a fresh object that is
dropped with the batch: the same code builds everything once and keeps
nothing. The census is fed only here, at pack time, by a batch that has
family pods; no cache write, commit or ingest path knows of it.
"""

from __future__ import annotations

from collections import OrderedDict
from operator import is_
from typing import (
    Callable, Dict, FrozenSet, List, NamedTuple, Optional, Sequence, Set,
    Tuple,
)

import numpy as np

from kubernetes_tpu.api.selectors import labels_match_selector
from kubernetes_tpu.api.types import LabelSelector, Pod, PodAffinityTerm
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.ops.host_masks import _constraint_signature
from kubernetes_tpu.plugins.nodeaffinity import (
    pod_matches_node_selector_and_affinity,
)
from kubernetes_tpu.plugins.podtopologyspread import DO_NOT_SCHEDULE
from kubernetes_tpu.tensors.node_tensor import NodeTensor, value_capacity

#: node-value rows kept, least recently used out first (a row is five
#: bytes a node slot); selector memos kept, oldest out first; template
#: keys held so that the pods of one template share one key object;
#: image lists whose score row is kept, least recently used out first (a
#: live list's row is four bytes a node slot, 48 lists of a 5,632-slot
#: tensor 1 MB; a list that scores nothing keeps its verdict alone)
ROWS_KEPT = 64
IMAGE_ROWS_KEPT = 256
SELECTORS_KEPT = 256
TEMPLATES_KEPT = 4096

#: the eligibility signature of a pod with no node selector and no
#: required node affinity: its groups count over every node
UNSCOPED: Tuple = ((), ())

#: the "key" of the synthetic row whose value is the node's own row
#: (``add_host_port_rows``); no label key is a tuple
ROW_INDEX = ("<row>",)

#: the cumulative counters ``tally`` returns, in its order (read by
#: position in tests/test_incremental_pack.py: new names go at the end).
#: ``nodes_recounted`` is the node rows the census visited to advance
#: (every row where it counted from nothing). The ten from
#: ``score_sigs`` on are ``pack_score_batch``'s (ops/scoring.py):
#: ``score_node_rows`` the node-side rows it asked for (one for the
#: zones, one for each distinct image list it looked at),
#: ``score_node_rows_reused`` those it did not have to build; of a batch
#: whose dynamic families are live, ``score_dynamic_rows`` the
#: selector-spread groups plus the preferred-affinity rows it carried
#: (each kind is on its own span, ``pack.score.selectors`` ``groups``
#: and ``pack.score.ipa`` ``rows``), ``score_dynamic_cuts`` the batches
#: a dynamic envelope cut in two, ``score_census_nodes`` the node rows
#: the census answered for and ``score_census_recounted`` those of them
#: it visited to advance. The last two say what a visit costs:
#: ``census_pods_held`` the pods resident on the rows visited, before
#: the visit or after it, ``census_pods_moved`` those of them put into
#: the count or taken out of it (all of them where it counted from
#: nothing, the pods bound or deleted since the last batch otherwise)
TALLY = ("nodes", "nodes_recounted", "node_rows", "node_rows_reused",
         "templates", "score_sigs", "score_live",
         "score_image_sigs", "score_image_sigs_live",
         "score_node_rows", "score_node_rows_reused",
         "score_dynamic_rows", "score_dynamic_cuts",
         "score_census_nodes", "score_census_recounted",
         "census_pods_moved", "census_pods_held")

#: the zone rows' slot before the first build (None is a verdict)
_UNBUILT = object()

#: one term of a group: (namespaces, selector, selector signature)
Term = Tuple[Tuple[str, ...], Optional[LabelSelector], Tuple]


# -- what identifies a group, a term and a pod template -----------------------


def selector_sig(sel: Optional[LabelSelector]) -> Tuple:
    if sel is None:
        return ("<nil>",)
    labels = sel.match_labels
    return (
        tuple(sorted(labels.items())) if len(labels) > 1
        else tuple(labels.items()),
        tuple([
            (r.key, r.operator, tuple(r.values)) for r in sel.match_expressions
        ]) if sel.match_expressions else (),
    )


def eligibility_sig(pod: Pod) -> Tuple:
    """Signature of the pod's node-affinity/selector scoping: spread
    pair counting runs only over nodes the pod itself could land on
    (filtering.go:245 PodMatchesNodeSelectorAndAffinityTerms), so pods
    with different scoping cannot share a group. It is the node
    selector and the required node affinity of the static mask's
    signature, which is kept on the pod."""
    return _constraint_signature(pod)[1:3]


def hard_spread_constraints(pod: Pod) -> List:
    return [
        c
        for c in pod.spec.topology_spread_constraints
        if c.when_unsatisfiable == DO_NOT_SCHEDULE
    ]


def term_namespaces(owner: Pod, term: PodAffinityTerm) -> Tuple[str, ...]:
    """topologies.go:28: empty term namespaces default to the owner's."""
    if term.namespaces:
        return tuple(sorted(term.namespaces))
    return (owner.metadata.namespace,)


def term_sig(owner: Pod, term: PodAffinityTerm) -> Tuple:
    return (
        term_namespaces(owner, term),
        selector_sig(term.label_selector),
        term.topology_key,
    )


def required_affinity(pod: Pod) -> List[PodAffinityTerm]:
    a = pod.spec.affinity
    if a is None or a.pod_affinity is None:
        return []
    return a.pod_affinity.required_during_scheduling


def required_anti_affinity(pod: Pod) -> List[PodAffinityTerm]:
    a = pod.spec.affinity
    if a is None or a.pod_anti_affinity is None:
        return []
    return a.pod_anti_affinity.required_during_scheduling


def template_key(pod: Pod) -> Tuple:
    """Pods with equal keys get equal rows from the family packers:
    namespace, labels, scoping, hard spread constraints, required
    (anti-)affinity terms. Once a pod of every batch, so the empty
    parts cost nothing."""
    meta = pod.metadata
    spec = pod.spec
    spread: Tuple = ()
    if spec.topology_spread_constraints:
        spread = tuple([
            (c.topology_key, c.max_skew, selector_sig(c.label_selector))
            for c in spec.topology_spread_constraints
            if c.when_unsatisfiable == DO_NOT_SCHEDULE
        ])
    aff: Tuple = ()
    anti: Tuple = ()
    if spec.affinity is not None:
        aff = tuple([term_sig(pod, t) for t in required_affinity(pod)])
        anti = tuple([term_sig(pod, t) for t in required_anti_affinity(pod)])
    return (
        meta.namespace, frozenset(meta.labels.items()),
        eligibility_sig(pod), spread, aff, anti,
    )


class NodeValues(NamedTuple):
    values: np.ndarray  # [n_cap] int32, read-only
    valid: np.ndarray  # [v_cap] bool, read-only


class _PodClass:
    """The resident pods of one ``(namespace, labels)``, by node row."""

    __slots__ = ("namespace", "key", "labels", "pods", "terminating")

    def __init__(
        self, namespace: str, key: FrozenSet, labels: Dict[str, str]
    ) -> None:
        self.namespace = namespace
        self.key = key  # of ``_classes[namespace]``
        self.labels = dict(labels)
        self.pods: Dict[int, int] = {}  # node row -> pods, all of them
        self.terminating: Dict[int, int] = {}  # of which terminating


def _preferred(pod: Pod, anti: bool) -> List:
    a = pod.spec.affinity
    side = None if a is None else (
        a.pod_anti_affinity if anti else a.pod_affinity
    )
    return [] if side is None else side.preferred_during_scheduling


def scoring_terms(pod: Pod) -> Tuple:
    """What ``pod`` scores every incoming pod with once it is placed
    (scoring.go:111 processExistingPod), as ``(term signature, term,
    signed preferred weight, required-affinity count)``: preferred
    affinity +w, preferred anti-affinity -w, and each required affinity
    term once, to be multiplied by the profile's hardPodAffinityWeight.
    A pod's affinity does not change, so the tuple is kept on the pod."""
    memo = pod.__dict__.get("_scoring_terms_memo")
    if memo is None:
        out = []
        if pod.spec.affinity is not None:
            for t in required_affinity(pod):
                out.append((term_sig(pod, t), t, 0.0, 1))
            for wt in _preferred(pod, anti=False):
                t = wt.pod_affinity_term
                out.append((term_sig(pod, t), t, float(wt.weight), 0))
            for wt in _preferred(pod, anti=True):
                t = wt.pod_affinity_term
                out.append((term_sig(pod, t), t, -float(wt.weight), 0))
        memo = pod.__dict__["_scoring_terms_memo"] = tuple(out)
    return memo


def _one_less(counts: Dict[int, int], j: int) -> int:
    """Row ``j`` of ``counts`` less one, the row gone at 0: what is left."""
    left = counts[j] - 1
    if left:
        counts[j] = left
    else:
        del counts[j]
    return left


class TermOwners:
    """The residents that carry one scoring term, by node row."""

    __slots__ = ("sig", "selector", "preferred", "preferred_owners",
                 "required")

    def __init__(self, sig: Tuple, term: PodAffinityTerm) -> None:
        self.sig = sig  # (namespaces, selector signature, topology key)
        self.selector = term.label_selector
        # weights are whole numbers carried as float64: a sum that loses
        # an owner is the sum without it, exactly
        self.preferred: Dict[int, float] = {}  # node row -> signed weight
        self.preferred_owners: Dict[int, int] = {}  # node row -> its owners
        self.required: Dict[int, int] = {}  # node row -> owners

    def mass(self, hard_weight: int) -> Tuple[np.ndarray, np.ndarray]:
        """``(node rows, signed mass)`` at ``hard_weight`` a required
        affinity term."""
        at = dict(self.preferred)
        if hard_weight > 0:
            for j, count in self.required.items():
                at[j] = at.get(j, 0.0) + float(hard_weight) * count
        return (
            np.fromiter(at.keys(), dtype=np.int64, count=len(at)),
            np.fromiter(at.values(), dtype=np.float64, count=len(at)),
        )


class FamilyFacts:
    def __init__(self) -> None:
        self.keeps = True
        self._snapshot: Optional[Snapshot] = None
        self._nt: Optional[NodeTensor] = None
        self._names: Optional[List[str]] = None
        self._epoch = 0
        self._info_rows: Optional[List[int]] = None
        # node-value rows (None: more values than slots)
        self._rows: "OrderedDict[Tuple, Optional[NodeValues]]" = OrderedDict()
        self._incomplete: Dict[str, bool] = {}
        # the score packer's rows: (zone_id, zone_onehot), None where the
        # zones are too many; image list -> weighted row, None where the
        # list scores nothing, for ``_image_index`` alone
        self._zones: object = _UNBUILT
        self._image_index: Optional[Dict] = None
        self._image_rows: "OrderedDict[Tuple, Optional[np.ndarray]]" = (
            OrderedDict()
        )
        # the census
        self._cursor: Optional[int] = None  # None: recount every node
        self._counted = False  # the census is this attach's snapshot's
        self._row_of: Dict[str, int] = {}
        self._classes: Dict[str, Dict[FrozenSet, _PodClass]] = {}
        # node row -> (the pods counted there, the class of each), and
        # the ``id`` of every held pod that was counted as terminating
        self._held: Dict[int, Tuple[List[Pod], List[_PodClass]]] = {}
        self._held_terminating: Set[int] = set()
        # the term owners by signature, counted from the first
        # ``term_owners()`` on
        self._owners_kept = False
        self._owners: Dict[Tuple, TermOwners] = {}
        self._matches: "OrderedDict[Tuple, Dict[_PodClass, bool]]" = (
            OrderedDict()
        )
        self._template_keys: Dict[Tuple, Tuple] = {}
        # the batch's templates, by the identity of its pod list
        self._tpl_pods: Optional[Sequence[Pod]] = None
        self._tpl: Tuple[np.ndarray, List[Pod]] = (
            np.zeros(0, dtype=np.int64), [],
        )
        self.nodes = 0
        self.nodes_recounted = 0
        self.node_rows = 0
        self.node_rows_reused = 0
        self.templates = 0
        self.score_live = 0
        self.score_image_sigs = 0
        self.score_image_sigs_live = 0
        self.score_sigs = 0
        self.score_node_rows = 0
        self.score_node_rows_reused = 0
        self.score_dynamic_rows = 0
        self.score_dynamic_cuts = 0
        self.score_census_nodes = 0
        self.score_census_recounted = 0
        self.census_pods_moved = 0
        self.census_pods_held = 0

    def tally(self) -> Tuple[int, ...]:
        return tuple(getattr(self, name) for name in TALLY)

    # -- validity -------------------------------------------------------------

    def _bind(self, snapshot: Snapshot, nt: NodeTensor) -> None:
        moved = snapshot is not self._snapshot or nt.names is not self._names
        epoch = snapshot.node_spec_epoch
        if moved or epoch != self._epoch:
            self._epoch = epoch
            self._rows.clear()
            self._incomplete.clear()
            self._zones = _UNBUILT
            self._image_index = None
            self._image_rows.clear()
        if moved:
            self._snapshot = snapshot
            self._names = nt.names
            self._cursor = None
        self._nt = nt
        self._info_rows = None
        self._counted = False

    @property
    def infos(self) -> List[NodeInfo]:
        return self._snapshot.node_info_list

    def info_rows(self) -> List[int]:
        """Tensor row per entry of ``infos``."""
        rows = self._info_rows
        if rows is None:
            rows = self._info_rows = self._nt.rows_for(self.infos).tolist()
        return rows

    # -- node-value rows ------------------------------------------------------

    def node_values(
        self, key, scope: Tuple = UNSCOPED, rep: Optional[Pod] = None
    ) -> Optional[NodeValues]:
        """The row of ``key`` over the nodes ``scope`` admits (``rep`` is
        a pod with that eligibility signature), or None when the key
        has more values than the count tensors have slots."""
        self.node_rows += 1
        sig = (key, scope)
        rows = self._rows
        if sig in rows:
            rows.move_to_end(sig)
            self.node_rows_reused += 1
            return rows[sig]
        built = self._build_values(key, rep if scope != UNSCOPED else None)
        rows[sig] = built
        if len(rows) > ROWS_KEPT:
            rows.popitem(last=False)
        return built

    def _build_values(self, key, rep: Optional[Pod]) -> Optional[NodeValues]:
        n_cap = self._nt.capacity
        v_cap = value_capacity(n_cap)
        at: List[int] = []
        vids: List[int] = []
        ids: Dict[str, int] = {}
        if key is ROW_INDEX:
            at = vids = [
                j for j, ni in zip(self.info_rows(), self.infos)
                if ni.node is not None and j < n_cap
            ]
        else:
            for j, ni in zip(self.info_rows(), self.infos):
                node = ni.node
                if node is None:
                    continue
                if (
                    rep is not None
                    and not pod_matches_node_selector_and_affinity(rep, ni)
                ):
                    continue  # out of the owner pods' scope: -1
                val = node.metadata.labels.get(key)
                if val is None:
                    continue  # the node lacks the key: excluded
                vid = ids.get(val)
                if vid is None:
                    if len(ids) >= v_cap:
                        return None
                    vid = ids[val] = len(ids)
                at.append(j)
                vids.append(vid)
        values = np.full(n_cap, -1, dtype=np.int32)
        values[at] = vids
        valid = np.zeros(v_cap, dtype=bool)
        valid[: len(ids)] = True
        values.flags.writeable = False
        valid.flags.writeable = False
        return NodeValues(values, valid)

    def key_incomplete(self, key: str) -> bool:
        """Whether some node lacks the label ``key``."""
        v = self._incomplete.get(key)
        if v is None:
            v = self._incomplete[key] = any(
                ni.node is not None and key not in ni.node.metadata.labels
                for ni in self.infos
            )
        return v

    # -- the score packer's rows ---------------------------------------------

    def score_zones(
        self, build: Callable[[], Optional[Tuple]]
    ) -> Optional[Tuple]:
        """The zone rows: ``build()``'s answer, asked for once while the
        node-value rows stand."""
        self.score_node_rows += 1
        if self._zones is _UNBUILT:
            self._zones = build()
        else:
            self.score_node_rows_reused += 1
        return self._zones

    def score_image_row(
        self, index: Dict, key: Tuple,
        build: Callable[[], Optional[np.ndarray]],
    ) -> Optional[np.ndarray]:
        """The row of the image list ``key``: ``build()``'s answer, asked
        for once while ``index`` (``Snapshot.image_holders()``, held here
        so that no later index can take its identity) is the snapshot's."""
        rows = self._image_rows
        if index is not self._image_index:
            self._image_index = index
            rows.clear()
        self.score_node_rows += 1
        if key in rows:
            rows.move_to_end(key)
            self.score_node_rows_reused += 1
            return rows[key]
        row = rows[key] = build()
        if len(rows) > IMAGE_ROWS_KEPT:
            rows.popitem(last=False)
        return row

    # -- the census -----------------------------------------------------------

    def _advance(self) -> None:
        """Bring the census up to the snapshot: visit the nodes its
        change log names since the last read, however many, or count
        every node from nothing where the log cannot be trusted to
        name them."""
        self._counted = True
        snapshot = self._snapshot
        names = None
        if self._cursor is None:
            cursor = snapshot.change_cursor()
        else:
            names, moved, cursor = snapshot.changes_since(self._cursor)
            if moved:
                names = None
        self._cursor = cursor
        pairs: Optional[List[Tuple[int, NodeInfo]]] = None
        if names is not None:
            pairs = []
            row_of = self._row_of
            info_map = snapshot.node_info_map
            for name in names:
                ni = info_map.get(name)
                j = row_of.get(name)
                # pods the cache holds for a name without a Node object
                # are on no row and in no count
                listed = ni is not None and ni.node is not None
                if listed != (j is not None):
                    pairs = None  # the log tells a membership move: not trusted
                    break
                if listed:
                    pairs.append((j, ni))
        if pairs is None:
            infos = self.infos
            rows = self.info_rows()
            self._row_of = {ni.node_name: j for j, ni in zip(rows, infos)}
            self._classes = {}
            self._held = {}
            self._held_terminating = set()
            self._owners = {}
            pairs = list(zip(rows, infos))
        self._visit(pairs)

    def _visit(self, pairs: List[Tuple[int, NodeInfo]]) -> None:
        """Move the count of each ``(row, NodeInfo)`` from the pods held
        for the row to the node's pods now, by the pods that differ."""
        self.nodes_recounted += len(pairs)
        held_rows = self._held
        emptied: List[_PodClass] = []
        moved = resident = 0
        for j, ni in pairs:
            pods = ni.pods
            held = held_rows.get(j)
            if held is None:
                if pods:
                    held_rows[j] = (list(pods), self._put(j, pods))
                    moved += len(pods)
                    resident += len(pods)
                continue
            was, classes = held
            resident += len(was)
            if len(pods) >= len(was) and all(map(is_, was, pods)):
                came = pods[len(was):]  # a bind appends; mostly nothing
            else:
                now = set(map(id, pods))
                stayed: List[Pod] = []
                stayed_in: List[_PodClass] = []
                for p, cls in zip(was, classes):
                    if id(p) in now:
                        stayed.append(p)
                        stayed_in.append(cls)
                    else:
                        self._drop(j, p, cls, emptied)
                        moved += 1
                came = []
                if len(stayed) < len(pods):
                    known = set(map(id, stayed))
                    came = [p for p in pods if id(p) not in known]
                if not stayed and not came:
                    del held_rows[j]
                    continue
                was[:] = stayed
                classes[:] = stayed_in
            if came:
                classes.extend(self._put(j, came))
                was.extend(came)
                moved += len(came)
                resident += len(came)
        self.census_pods_moved += moved
        self.census_pods_held += resident
        for cls in emptied:  # a class no node holds any more leaves
            by_labels = self._classes[cls.namespace]
            if not cls.pods and by_labels.get(cls.key) is cls:
                del by_labels[cls.key]

    def _put(self, j: int, pods: Sequence[Pod]) -> List[_PodClass]:
        """Count ``pods``, which came to row ``j``: the class of each."""
        classes = self._classes
        owners_kept = self._owners_kept
        out: List[_PodClass] = []
        for p in pods:
            meta = p.metadata
            by_labels = classes.get(meta.namespace)
            if by_labels is None:
                by_labels = classes[meta.namespace] = {}
            key = frozenset(meta.labels.items())
            cls = by_labels.get(key)
            if cls is None:
                cls = by_labels[key] = _PodClass(
                    meta.namespace, key, meta.labels
                )
            cls.pods[j] = cls.pods.get(j, 0) + 1
            if meta.deletion_timestamp is not None:
                cls.terminating[j] = cls.terminating.get(j, 0) + 1
                self._held_terminating.add(id(p))
            if owners_kept and p.spec.affinity is not None:
                self._own(j, p, True)
            out.append(cls)
        return out

    def _drop(
        self, j: int, pod: Pod, cls: _PodClass, emptied: List[_PodClass]
    ) -> None:
        """Take ``pod``, counted in ``cls`` on row ``j``, out again."""
        if not _one_less(cls.pods, j) and not cls.pods:
            emptied.append(cls)
        terminating = self._held_terminating
        if terminating and id(pod) in terminating:
            terminating.discard(id(pod))
            _one_less(cls.terminating, j)
        if self._owners_kept and pod.spec.affinity is not None:
            self._own(j, pod, False)

    def _own(self, j: int, pod: Pod, came: bool) -> None:
        """Put the scoring terms ``pod`` carries on row ``j``, or take
        them off it. A row leaves a term with its last owner of a kind,
        and a term leaves with its last row."""
        owners = self._owners
        for sig, term, weight, required in scoring_terms(pod):
            held = owners.get(sig)
            if came:
                if held is None:
                    held = owners[sig] = TermOwners(sig, term)
                if required:
                    held.required[j] = held.required.get(j, 0) + 1
                else:
                    held.preferred[j] = held.preferred.get(j, 0.0) + weight
                    held.preferred_owners[j] = (
                        held.preferred_owners.get(j, 0) + 1
                    )
                continue
            if required:
                _one_less(held.required, j)
            elif _one_less(held.preferred_owners, j):
                held.preferred[j] -= weight
            else:
                del held.preferred[j]
            if not held.preferred and not held.required:
                del owners[sig]

    def term_owners(self) -> List[TermOwners]:
        """Every scoring term some resident carries, with its owners'
        weight by node row, in no order a caller may rest on. The first
        call walks every node; the census keeps them from then on."""
        if not self._counted:
            self._advance()
        if not self._owners_kept:
            self._owners_kept = True
            for j, ni in zip(self.info_rows(), self.infos):
                for p in ni.pods_with_affinity:
                    self._own(j, p, True)
        return list(self._owners.values())

    def matching_in(
        self, namespace: str, sig: Tuple, matches: Callable[[Dict], bool]
    ) -> List[_PodClass]:
        """The resident classes of ``namespace`` whose labels ``matches``
        accepts; ``sig`` names the predicate, and each (predicate, class)
        pair is asked once while both live."""
        if not self._counted:
            self._advance()
        by_labels = self._classes.get(namespace)
        if not by_labels:
            return []
        memo = self._memo(sig)
        out: List[_PodClass] = []
        for cls in by_labels.values():
            hit = memo.get(cls)
            if hit is None:
                hit = memo[cls] = bool(matches(cls.labels))
            if hit:
                out.append(cls)
        return out

    def _memo(self, sig: Tuple) -> Dict[_PodClass, bool]:
        memos = self._matches
        memo = memos.get(sig)
        if memo is None:
            memo = memos[sig] = {}
            if len(memos) > SELECTORS_KEPT:
                memos.popitem(last=False)
        return memo

    def matching(self, terms: Sequence[Term]) -> List[_PodClass]:
        """The resident classes that match every one of ``terms``
        (PodMatchesTermsNamespaceAndSelector, topologies.go:40), each
        (selector, class) pair matched once while both live."""
        if not self._counted:
            self._advance()
        first = terms[0]
        out: List[_PodClass] = []
        for namespace in dict.fromkeys(first[0]):
            by_labels = self._classes.get(namespace)
            if not by_labels:
                continue
            for cls in by_labels.values():
                if all(self._matches_term(cls, t) for t in terms):
                    out.append(cls)
        return out

    def _matches_term(self, cls: _PodClass, term: Term) -> bool:
        namespaces, selector, sel_sig = term
        if cls.namespace not in namespaces:
            return False
        memo = self._memo(sel_sig)
        hit = memo.get(cls)
        if hit is None:
            hit = memo[cls] = labels_match_selector(cls.labels, selector)
        return hit

    def counts(
        self, classes: List[_PodClass], values: np.ndarray, live_only: bool
    ) -> np.ndarray:
        """Pods of ``classes`` by the value ``values`` gives their node:
        a count row ``[v_cap]`` int32. ``live_only`` leaves terminating
        pods out."""
        v_cap = value_capacity(values.shape[0])
        out = np.zeros(v_cap, dtype=np.int64)
        for cls in classes:
            pods = cls.pods
            rows = np.fromiter(pods.keys(), dtype=np.int64, count=len(pods))
            if live_only and cls.terminating:
                gone = cls.terminating
                n = np.fromiter(
                    (c - gone.get(j, 0) for j, c in pods.items()),
                    dtype=np.int64, count=len(pods),
                )
            else:
                n = np.fromiter(
                    pods.values(), dtype=np.int64, count=len(pods)
                )
            vals = values[rows]
            on = vals >= 0
            out += np.bincount(
                vals[on], weights=n[on], minlength=v_cap
            ).astype(np.int64)
        return out.astype(np.int32)

    # -- pod templates --------------------------------------------------------

    def batch_templates(
        self, pods: Sequence[Pod]
    ) -> Tuple[np.ndarray, List[Pod]]:
        """``(index [B], first pods)``: the batch's distinct templates
        in first-pod order, ``index[i]`` the template of pod i. An
        object that keeps its facts also keeps a pod's key on the pod,
        as ``host_masks._constraint_signature`` does, and hands a
        dispatch's second packer what its first one found."""
        if pods is self._tpl_pods and len(pods) == len(self._tpl[0]):
            return self._tpl
        seen: Dict[Tuple, int] = {}
        firsts: List[Pod] = []
        index: List[int] = []
        keeps = self.keeps
        known = self._template_keys
        if len(known) > TEMPLATES_KEPT:
            known.clear()  # the pods hold theirs
        for pod in pods:
            key = pod.__dict__.get("_family_memo") if keeps else None
            if key is None:
                key = template_key(pod)
                if keeps:
                    # one key object a template, not one a pod
                    key = known.setdefault(key, key)
                    pod.__dict__["_family_memo"] = key
            t = seen.get(key)
            if t is None:
                t = seen[key] = len(firsts)
                firsts.append(pod)
            index.append(t)
        self._tpl_pods = pods
        self._tpl = (np.array(index, dtype=np.int64), firsts)
        self.templates += len(firsts)
        self.nodes += len(self.infos)
        return self._tpl


def attach(
    facts: Optional[FamilyFacts], snapshot: Snapshot, nt: NodeTensor
) -> FamilyFacts:
    """``facts`` made valid for this snapshot and tensor, or, where
    nothing may be kept (no ``facts``; a snapshot no cache feeds), an
    object for this batch alone."""
    if facts is None or not snapshot.node_spec_epoch:
        facts = FamilyFacts()
        facts.keeps = False
    facts._bind(snapshot, nt)
    return facts
