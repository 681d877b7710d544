"""The plain reference for a cluster of Services whose pods carry a soft
anti-affinity term: ``reference.py``'s filters and two resource scores,
plus the two score plugins of the default provider that count pods.

Written from the two source files (kubernetes v1.18) and importing
nothing of the program. Pods of one size; every pod belongs to one
service ``k``, carries the label ``app=svc-<k>`` and one preferred pod
anti-affinity term of weight ``w`` on ``kubernetes.io/hostname`` to its
own label; a Service and a ReplicaSet select each label. What decides a
placement beside the resources is then one array, ``counts[k, n]``: the
pods of service ``k`` on node ``n``.

``DefaultPodTopologySpread`` (defaultpodtopologyspread/
default_pod_topology_spread.go), weight 1. Score: the node's count of
pods in the incoming pod's namespace that the merged selector of its
Services and ReplicaSets matches and that are not being deleted (:78,
countMatchingPods :206), here ``counts[k, n]``. NormalizeScore (:107),
over the nodes that passed the filters: ``maxCountByNodeName`` the
largest count, ``countsByZone`` the counts summed by zone,
``maxCountByZone`` the largest sum; then, all in float64 as the source
has it,

    fScore = 100 * ((maxCountByNodeName - count) / maxCountByNodeName)
    zoneScore = 100 * ((maxCountByZone - countsByZone[zone]) / maxCountByZone)
    fScore = fScore * (1.0 - zoneWeighting) + zoneWeighting * zoneScore

(each 100 where its maximum is 0; ``zoneWeighting`` = 2.0 / 3.0; every
node here has a zone) and the node's score is ``int64(fScore)``.

``InterPodAffinity`` (interpodaffinity/scoring.go), weight 1,
``hardPodAffinityWeight`` 1. PreScore (:110-200) sums, by the node's
value of each term's topology key, (a) the incoming pod's preferred
terms over every existing pod they match, weight negated for
anti-affinity, and (b) every existing pod's preferred terms over the
incoming pod where it matches them (processExistingPod :111; no pod here
has a required term). With the hostname as the key a node's sum is its
own pods': ``-w * counts[k, n]`` from (a) and ``-w * counts[k, n]`` from
(b). NormalizeScore (:246): ``maxCount`` and ``minCount`` over the nodes
that passed the filters, both seeded with 0, and
``int64(100 * ((sum - minCount) / (maxCount - minCount)))``, 0 where the
two are equal. With one term a pod the weight cancels in that quotient:
any positive ``w`` places alike.

The total is the sum of the four scores at weight 1. A pod goes to a
node that passed the filters and has the highest total; the source
breaks ties by reservoir sampling, so every node of the top class is a
right answer, and ``schedule`` takes the lowest index.

Departures from the source, each one noted where it is made: the two
resource scores in whole numbers (``reference.scores``, exact); no
``percentageOfNodesToScore`` (every node is scored, as the program's
batch path does); the nodes' zones are whole numbers, not label strings.

**These scores move with every placement**, so no lemma about counts
holds whatever the order, as ``reference.bands`` has for identical pods.
What can be held exactly is a certificate: given an order in which a
wave's pods were placed (the program solves in the order the apiserver
created them; ``checks/window_services_reference.py`` says why),
``certify`` replays it, scoring each pod against
the state the pods before it left *as they were placed*, and counts the
pods whose node is not in the top class of the rule at that instant.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference

ZONE_WEIGHTING = 2.0 / 3.0


@dataclasses.dataclass(frozen=True)
class Rule:
    """The full rule, or one of the broken ones a control schedules by.

    ``spread`` / ``affinity``: hear DefaultPodTopologySpread /
    InterPodAffinity at all. ``residents_symmetric``: whether half (b)
    sees the pods that were there before the wave (without it, only the
    pods the wave itself has placed score the incoming pod with their
    terms). ``shift``: score service ``k`` by the counts of service
    ``(k + shift) % K``. ``precision``: of the two resource scores."""

    spread: bool = True
    affinity: bool = True
    residents_symmetric: bool = True
    shift: int = 0
    precision: str = "exact"


FULL = Rule()


class State:
    """The nodes and the services' pods on them, as a wave finds them,
    moved on by ``place``."""

    def __init__(self, nodes: reference.Nodes, pod: reference.PodClass,
                 counts: np.ndarray, weight: int = 100,
                 eligible: np.ndarray = None,
                 precision: str = "exact") -> None:
        self.nodes = nodes
        self.pod = pod
        self.weight = int(weight)
        self.precision = precision
        n = nodes.cap_cpu.shape[0]
        self.eligible = (
            np.ones(n, dtype=bool) if eligible is None else eligible
        )
        self.used_cpu = nodes.used_cpu.astype(np.int64).copy()
        self.used_mem = nodes.used_mem.astype(np.int64).copy()
        self.used_pods = nodes.used_pods.astype(np.int64).copy()
        #: counts[k, n]: pods of service k on node n, residents included
        self.counts = np.asarray(counts, dtype=np.int64).copy()
        #: of them, the pods the wave itself has placed
        self.placed = np.zeros_like(self.counts)
        self.zones = int(nodes.zone.max()) + 1 if n else 0
        self.resource = reference.scores(
            nodes.cap_cpu, nodes.cap_mem, self.used_cpu + pod.cpu,
            self.used_mem + pod.mem, precision,
        )

    def feasible(self) -> np.ndarray:
        nodes, pod = self.nodes, self.pod
        return (
            (self.used_cpu + pod.cpu <= nodes.cap_cpu)
            & (self.used_mem + pod.mem <= nodes.cap_mem)
            & (self.used_pods + 1 <= nodes.cap_pods)
            & self.eligible
        )

    def place(self, k: int, node: int) -> None:
        nodes, pod = self.nodes, self.pod
        self.used_cpu[node] += pod.cpu
        self.used_mem[node] += pod.mem
        self.used_pods[node] += 1
        self.counts[k, node] += 1
        self.placed[k, node] += 1
        at = slice(node, node + 1)
        self.resource[node] = reference.scores(
            nodes.cap_cpu[at], nodes.cap_mem[at], self.used_cpu[at] + pod.cpu,
            self.used_mem[at] + pod.mem, self.precision,
        )[0]


def spread_score(count: np.ndarray, feasible: np.ndarray, zone: np.ndarray,
                 zones: int) -> np.ndarray:
    """DefaultPodTopologySpread's normalized score of every node, from
    the count of matching pods on each: float64 and ``int64()`` as
    default_pod_topology_spread.go:107-150 has them."""
    seen = np.where(feasible, count, 0)
    max_node = int(seen.max()) if seen.size else 0
    by_zone = np.bincount(zone, weights=seen, minlength=zones).astype(np.int64)
    max_zone = int(by_zone.max()) if by_zone.size else 0
    f = np.full(count.shape, 100.0)
    if max_node > 0:
        f = 100.0 * ((max_node - count).astype(np.float64) / float(max_node))
    # every node that passed the filters has a zone: haveZones
    zone_score = np.full(zones, 100.0)
    if max_zone > 0:
        zone_score = 100.0 * (
            (max_zone - by_zone).astype(np.float64) / float(max_zone)
        )
    f = f * (1.0 - ZONE_WEIGHTING) + ZONE_WEIGHTING * zone_score[zone]
    return f.astype(np.int64)  # int64(fScore): towards zero, and f >= 0


def affinity_score(total: np.ndarray, feasible: np.ndarray) -> np.ndarray:
    """InterPodAffinity's normalized score of every node from its summed
    term weights (scoring.go:246-268): the extremes over the nodes that
    passed the filters, both seeded with 0."""
    seen = np.where(feasible, total, 0)
    hi = max(int(seen.max()), 0) if seen.size else 0
    lo = min(int(seen.min()), 0) if seen.size else 0
    if hi == lo:
        return np.zeros(total.shape, dtype=np.int64)
    f = 100.0 * ((total - lo).astype(np.float64) / float(hi - lo))
    return f.astype(np.int64)


def totals(state: State, k: int, rule: Rule = FULL):
    """``(feasible [N] bool, total score [N] int64)`` of a pod of service
    ``k`` at ``state`` under ``rule``."""
    feasible = state.feasible()
    services = state.counts.shape[0]
    seen = (k + rule.shift) % services
    total = state.resource.astype(np.int64)
    if rule.precision != state.precision:
        raise ValueError("the state was built at another precision")
    if rule.spread:
        total = total + spread_score(
            state.counts[seen], feasible, state.nodes.zone, state.zones
        )
    if rule.affinity:
        # (a) the incoming pod's term over every pod it matches, and
        # (b) those pods' terms over the incoming pod
        owners = state.counts[seen] if rule.residents_symmetric else (
            state.placed[seen]
        )
        summed = -state.weight * (state.counts[seen] + owners)
        total = total + affinity_score(summed, feasible)
    return feasible, total


def schedule(state: State, arrivals, rule: Rule = FULL):
    """Place the pods of ``arrivals`` (a service each) in that order
    under ``rule``, each on the feasible node of highest total, lowest
    index. Returns the node of each pod, -1 where none was feasible;
    ``state`` is moved on."""
    out = []
    for k in arrivals:
        k = int(k)
        feasible, total = totals(state, k, rule)
        if not feasible.any():
            out.append(-1)
            continue
        node = int(np.argmax(np.where(feasible, total, -1)))
        state.place(k, node)
        out.append(node)
    return out


def certify(state: State, arrivals, nodes_placed, rule: Rule = FULL) -> int:
    """The pods of a wave, in the order they were placed, that no
    tie-break of ``rule`` explains: pod ``i`` (of service
    ``arrivals[i]``, bound to ``nodes_placed[i]``) is scored against
    ``state`` moved on by the pods before it *as they were placed*, and
    counted where its node did not pass the filters or scores below the
    best node that did. A pod bound nowhere (``-1``) is counted where a
    node was feasible for it. ``state`` is moved on."""
    unexplained = 0
    for k, node in zip(arrivals, nodes_placed):
        k, node = int(k), int(node)
        feasible, total = totals(state, k, rule)
        if node < 0:
            unexplained += bool(feasible.any())
            continue
        best = total[feasible].max() if feasible.any() else None
        if not feasible[node] or total[node] < best:
            unexplained += 1
        state.place(k, node)
    return unexplained


def zipf_shares(count: int, services: int, exponent: float, seed: int):
    """How many of ``count`` pods each of ``services`` services has:
    weights ``1 / rank**exponent`` by largest remainder, the ranks dealt
    to the services by ``seed``. Every seed of a run offers this same
    multiset."""
    weights = 1.0 / np.arange(1, services + 1, dtype=np.float64) ** exponent
    exact = count * weights / weights.sum()
    shares = np.floor(exact).astype(np.int64)
    short = count - int(shares.sum())
    shares[np.argsort(-(exact - shares), kind="stable")[:short]] += 1
    ranks = np.random.default_rng(seed).permutation(services)
    out = np.zeros(services, dtype=np.int64)
    out[ranks] = shares
    return out


def resident_nodes(shares, nodes: int, seed: int) -> list:
    """The node of every resident, service by service: each service's
    pods on distinct nodes while it has no more pods than there are
    nodes (its controller's replicas were spread by this very rule),
    dealt so that every node holds the same number of residents to
    within one. ``[K]`` arrays of node rows."""
    rng = np.random.default_rng(seed)
    total = int(np.sum(shares))
    # a round-robin deal over a shuffled node order: pod i of the whole
    # list sits on order[i % nodes], so consecutive pods (one service's)
    # are on distinct nodes
    order = rng.permutation(nodes)
    seats = order[np.arange(total) % nodes]
    out, at = [], 0
    for share in shares:
        out.append(seats[at:at + int(share)])
        at += int(share)
    return out
