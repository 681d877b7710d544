"""The plain reference for preemption: which node a pod that fits nowhere
is nominated to, and which resident pods leave for it.

Written from the published kube-scheduler (1.18,
``pkg/scheduler/core/generic_scheduler.go``) in plain Python; imports
nothing of the program.

- ``select_victims`` is ``selectVictimsOnNode``: take every pod of lower
  priority than the preemptor off the node; count the nominated pods of
  equal or higher priority on it (``addNominatedPods``); if the
  preemptor does not fit on cpu, memory and pod count even so, the node
  is no candidate. Otherwise put the removed pods back one at a time in
  ``MoreImportantPod`` order (higher priority first, then earlier start),
  those whose eviction would violate a disruption budget first
  (``filterPodsWithPDBViolation``), keeping each unless the preemptor
  stops fitting: the pods that could not be kept are the victims.
- ``pick_node`` is ``pickOneNodeForPreemption``: a node that needs no
  victim wins; then (1) fewest budget violations, (2) the lowest
  priority of the first victim (the highest-priority one), (3) the
  smallest sum of the victims' priorities, each counted as
  ``priority + MaxInt32 + 1``, (4) fewest victims, (5) the latest of the
  earliest start times among each node's highest-priority victims. It
  returns EVERY node still tied after rule 5: the published code then
  takes "the first", which is an iteration order and not the rule, so
  whoever compares an answer with this asks whether the node is among
  the ties.
- ``wave`` takes preemptors in turn (the scheduler's own order: priority
  descending), each seeing the nominations made before it.

Departures from the published text, and why:

- *No start times.* The harness creates pods without ``status.startTime``
  and the published ``GetPodStartTime`` then reads "now" for each: pods
  of equal priority tie in ``MoreImportantPod`` and rule 5 ties. ``Pod``
  carries ``start`` (default 0) so that a test can give them; equal
  starts keep the order the caller listed the pods in (a stable sort).
- *No disruption budgets in this deployment.* The steps are here
  (``budgets``) and do nothing without budgets.
- *When a victim leaves.* The published scheduler deletes victims
  through the API and goes on; whether the next preemptor still sees
  them on their node depends on how fast the deletes land. ``wave``
  takes ``evict``: False keeps every victim on its node for the whole
  wave (the program's device wave does: one snapshot a wave), True takes
  them off at once. Both are orders the rule allows, and so is any mix;
  ``unexplained`` is the comparison that holds under all of them.

``precision``: ``exact`` works in Python integers. ``float32`` and
``bfloat16`` round every fit sum and every priority key to that type
after each operation (``float32`` is what the configuration states for
the scheduler's arithmetic; ``bfloat16`` the nearest precision below).
``seen_priority`` replaces the priority read off a resident pod: the
control that has power where precision has none (PERF.md section 2).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.reference import round_bfloat16

MAX_INT32 = (1 << 31) - 1
FREE = float("-inf")  # rule 2's key of a node that needs no victim


@dataclasses.dataclass(frozen=True)
class Pod:
    name: str
    priority: int
    cpu: int  # milli
    mem: int  # bytes
    start: float = 0.0
    labels: tuple = ()  # (key, value) pairs, for a budget's selector


@dataclasses.dataclass
class Node:
    name: str
    cap_cpu: int
    cap_mem: int
    cap_pods: int
    pods: list  # resident pods, in the order the caller lists them


@dataclasses.dataclass(frozen=True)
class Budget:
    """A disruption budget: the pods it selects and how many of them may
    still be disrupted."""

    selector: tuple  # (key, value) pairs a pod's labels must all hold
    allowed: int


@dataclasses.dataclass
class Victims:
    pods: list  # budget-violating first, each part in MoreImportantPod order
    violations: int = 0


@dataclasses.dataclass
class Decision:
    pod: Pod
    node: str  # "" where no node is a candidate
    victims: list  # the pods that leave ``node`` for it
    tied: list  # every node the rule allows


def rounder(precision: str):
    if precision == "exact":
        return lambda x: x
    if precision == "float32":
        return lambda x: float(np.float32(x))
    if precision == "bfloat16":
        return lambda x: float(round_bfloat16(np.float32(x)))
    raise ValueError(f"unknown precision {precision!r}")


def more_important_first(pods: list, priority) -> list:
    """``MoreImportantPod`` order; ties keep the caller's order."""
    return sorted(pods, key=lambda p: (-priority(p), p.start))


def split_by_budgets(pods: list, budgets) -> tuple:
    """``filterPodsWithPDBViolation``: spend each budget's allowance
    over ``pods`` in order; a pod that finds a matching budget spent is
    violating."""
    allowed = [b.allowed for b in budgets]
    violating, rest = [], []
    for pod in pods:
        hit = False
        for i, budget in enumerate(budgets):
            if not pod.labels or not set(budget.selector) <= set(pod.labels):
                continue
            if allowed[i] <= 0:
                hit = True
                break
            allowed[i] -= 1
        (violating if hit else rest).append(pod)
    return violating, rest


def select_victims(node: Node, pod: Pod, nominated=(), budgets=(),
                   precision: str = "exact", seen_priority=None):
    """The victims ``pod`` needs on ``node``, or None where it does not
    fit even with every lower-priority pod gone."""
    q = rounder(precision)
    priority = seen_priority or (lambda p: p.priority)
    mine = q(pod.priority)
    lower = [p for p in node.pods if q(priority(p)) < mine]
    stays = [p for p in node.pods if q(priority(p)) >= mine]
    stays += [p for p in nominated if q(p.priority) >= mine]
    cpu = mem = 0
    for p in stays:
        cpu, mem = q(cpu + q(p.cpu)), q(mem + q(p.mem))
    count = len(stays)
    need_cpu, need_mem = q(pod.cpu), q(pod.mem)
    cap_cpu, cap_mem = q(node.cap_cpu), q(node.cap_mem)

    def fits(cpu, mem, count) -> bool:
        return (q(cpu + need_cpu) <= cap_cpu and q(mem + need_mem) <= cap_mem
                and count + 1 <= node.cap_pods)

    if not fits(cpu, mem, count):
        return None
    violating, rest = split_by_budgets(
        more_important_first(lower, priority), budgets)
    victims = Victims([])
    for part, counts in ((violating, True), (rest, False)):
        for p in part:  # the reprieve
            with_cpu, with_mem = q(cpu + q(p.cpu)), q(mem + q(p.mem))
            if fits(with_cpu, with_mem, count + 1):
                cpu, mem, count = with_cpu, with_mem, count + 1
            else:
                victims.pods.append(p)
                victims.violations += counts
    return victims


def node_key(victims: Victims, precision: str = "exact",
             seen_priority=None) -> tuple:
    """Rules 1 to 5 as one tuple, smaller is better; a node that needs
    no victim sorts before every other."""
    q = rounder(precision)
    priority = seen_priority or (lambda p: p.priority)
    if not victims.pods:
        return (0, FREE, 0, 0, 0.0)
    total = 0
    for p in victims.pods:
        total = q(total + q(priority(p) + MAX_INT32 + 1))
    top = max(q(priority(p)) for p in victims.pods)
    earliest = min(p.start for p in victims.pods if q(priority(p)) == top)
    return (victims.violations, q(priority(victims.pods[0])), total,
            len(victims.pods), -q(earliest))


def tied_best(keys: dict) -> list:
    """The names whose key is the smallest, in the dict's order."""
    best = min(keys.values(), default=None)
    return [name for name, key in keys.items() if key == best]


def pick_node(candidates: dict, precision: str = "exact",
              seen_priority=None) -> list:
    """Every node of ``candidates`` (name -> Victims) that rules 1 to 5
    leave tied, in the dict's order."""
    return tied_best({name: node_key(v, precision, seen_priority)
                      for name, v in candidates.items()})


def wave(nodes: list, preemptors: list, nominated: dict = None,
         budgets=(), precision: str = "exact", seen_priority=None,
         evict: bool = False, follow: list = None,
         eligible: list = None) -> list:
    """Preemptors in turn, each seeing the nominations before it (and,
    with ``evict``, its predecessors' victims gone). ``nominated``: node
    name -> pods nominated there before the wave. Among tied nodes a
    preemptor takes the first in ``nodes``' order, or ``follow[k]``
    where given (the node another scheduler chose, so that the two stay
    in step and each choice is held against ``tied``). ``eligible[k]``:
    the node names preemptor ``k`` may use at all (its selector), None
    for every node. Nothing of the caller's is changed."""
    nodes = [dataclasses.replace(n, pods=list(n.pods)) for n in nodes]
    by_name = {n.name: n for n in nodes}
    noms = {n.name: list((nominated or {}).get(n.name, ())) for n in nodes}
    found: dict = {}  # node -> Victims, for the preemptor at hand
    keys: dict = {}  # node -> its key, in ``nodes``' order
    stale = None  # the nodes to look at again; None for all of them
    asked = None  # what ``found`` and ``keys`` were worked out for
    out = []
    for k, pod in enumerate(preemptors):
        allowed = None if eligible is None or eligible[k] is None \
            else frozenset(eligible[k])
        if (pod.priority, pod.cpu, pod.mem, allowed) != asked:
            asked, stale = (pod.priority, pod.cpu, pod.mem, allowed), None
            found, keys = {}, {}
        for node in nodes if stale is None else stale:
            if allowed is not None and node.name not in allowed:
                continue
            victims = select_victims(
                node, pod, noms[node.name], budgets, precision, seen_priority)
            if victims is None:
                found.pop(node.name, None)
                keys.pop(node.name, None)
            else:
                found[node.name] = victims
                keys[node.name] = node_key(victims, precision, seen_priority)
        tied = tied_best(keys)
        choice = follow[k] if follow is not None else (tied[0] if tied else "")
        victims = list(found[choice].pods) if choice in found else []
        out.append(Decision(pod, choice, victims, tied))
        stale = []
        if choice in by_name:
            noms[choice].append(pod)
            if evict:
                by_name[choice].pods = without(by_name[choice].pods, victims)
            stale = [by_name[choice]]  # the one node that changed
    return out


def without(pods: list, gone: list) -> list:
    """``pods`` less one pod equal to each of ``gone``."""
    left = list(pods)
    for pod in gone:
        left.remove(pod)
    return left


# -- the comparison that holds whatever the order ---------------------------


def slots(node: Node, pod: Pod, limit: int, precision: str = "exact",
          seen_priority=None) -> list:
    """What each further pod like ``pod`` costs on ``node`` when its
    predecessors have landed there and their victims have left: a list,
    one entry a pod, of (rule 2's key, the victims). The victims of the
    first ``g`` entries together are what leaves a node that takes ``g``
    such pods, whether the victims left between them or stayed until
    the last (module text)."""
    state = dataclasses.replace(node, pods=list(node.pods))
    out = []
    while len(out) < limit:
        found = select_victims(state, pod, (), (), precision, seen_priority)
        if found is None:
            break
        out.append((node_key(found, precision, seen_priority)[1], found.pods))
        state.pods = without(state.pods, found.pods) + [pod]
    return out


def kind(pod: Pod) -> tuple:
    return (pod.priority, pod.cpu, pod.mem)


def unexplained(nodes: list, pod: Pod, landed: dict, left: dict,
                wanted: int, precision: str = "exact",
                seen_priority=None) -> dict:
    """Identical preemptors (``wanted`` pods like ``pod``) onto
    ``nodes`` as they were before: ``landed`` is node name -> how many
    of them ended there, ``left`` node name -> the resident pods that
    left it. Counted, in pods, what no order of arrival, no timing of
    the victims' deletes and no tie-break of the published rule
    explains:

    ``nodes``: preemptors on a node beyond what rule 2 allows it, or
    missing from a node rule 2 fills first: with every node's further
    pods priced by ``slots``, the ``wanted`` cheapest keys are taken,
    all below the last key taken and none above it;
    ``victims``: residents that left a node and are not the ones the
    reprieve gives up for the pods that landed there, or are and stayed
    (compared as priority and size: pods alike are interchangeable);
    ``unplaced``: preemptors that landed nowhere though a node could
    take them.

    Nodes with the same residents share one ``slots`` call."""
    by_sig: dict = {}
    priced = {}
    most = max(landed.values(), default=0) + 1
    for node in nodes:
        sig = (node.cap_cpu, node.cap_mem, node.cap_pods,
               tuple(sorted(kind(p) for p in node.pods)))
        if sig not in by_sig:
            by_sig[sig] = slots(node, pod, most, precision, seen_priority)
        priced[node.name] = by_sig[sig]
    for name, mine in priced.items():
        keys = [key for key, _ in mine]
        if keys != sorted(keys):
            raise ValueError(
                f"node {name}: a later pod costs less than an earlier one "
                f"({keys}); the wave has no order-free comparison")
    placed = sum(landed.values())
    every = sorted(key for mine in priced.values() for key, _ in mine)
    unplaced = min(wanted, len(every)) - placed
    wrong_nodes = wrong_victims = 0
    if placed:
        last = every[min(placed, len(every)) - 1]
        for name, mine in priced.items():
            got = landed.get(name, 0)
            fewest = sum(1 for key, _ in mine if key < last)
            most_here = sum(1 for key, _ in mine if key <= last)
            wrong_nodes += max(0, fewest - got) + max(0, got - most_here)
    for node in nodes:
        mine = priced[node.name]
        due: dict = {}
        for _, victims in mine[:landed.get(node.name, 0)]:
            for v in victims:
                due[kind(v)] = due.get(kind(v), 0) + 1
        went: dict = {}
        for v in left.get(node.name, ()):
            went[kind(v)] = went.get(kind(v), 0) + 1
        stayed = sum(max(0, n - went.get(k, 0)) for k, n in due.items())
        extra = sum(max(0, n - due.get(k, 0)) for k, n in went.items())
        wrong_victims += max(stayed, extra)
    return {"nodes": wrong_nodes, "victims": wrong_victims,
            "unplaced": max(0, unplaced)}


def tally(decisions: list, evict: bool = False) -> tuple:
    """``landed`` and ``left`` of a ``wave``'s decisions, for
    ``unexplained``. Where the victims stayed through the wave
    (``evict`` False) a node's later preemptor names its earlier one's
    victims again: they leave once."""
    landed: dict = {}
    left: dict = {}
    for d in decisions:
        if not d.node:
            continue
        landed[d.node] = landed.get(d.node, 0) + 1
        if evict:
            left.setdefault(d.node, []).extend(d.victims)
        else:
            more = without(d.victims, [
                v for v in left.get(d.node, ()) if v in d.victims])
            left.setdefault(d.node, []).extend(more)
    return landed, left
