"""The plain reference for bin-packing: a sequential scheduler in numpy
for pods that ask extended resources, scored by the published
``NodeResourcesMostAllocated``. Written from the reference's
``most_allocated.go`` and ``fit.go`` and importing nothing of the
program; of ``reference.py`` it takes, unchanged, the bfloat16 rounding
and the default provider's score (the control's rule).

It places identical pods one at a time, each on the feasible node of
highest score, lowest index:

- filter ``NodeResourcesFit``: cpu, memory, pod count and every extended
  resource the pod asks all fit (``fit.go``: a resource the pod does not
  ask is not looked at);
- filter ``NodeAffinity``: the pod's node selector (``eligible``);
- score ``NodeResourcesMostAllocated`` at weight 1, alone: per resource
  ``floor(requested * 100 / capacity)``, 0 when requested exceeds
  capacity or capacity is 0, over cpu and memory, then ``floor(sum /
  2)``; requested counted with the incoming pod. Extended resources are
  filtered on and not scored, as published.

``precision="exact"`` computes the score in integers; ``float32`` and
``bfloat16`` compute the same equation in float32 and round every
intermediate to the named type (``float32`` is what the configuration
states, ``bfloat16`` the nearest precision below it). ``rule="default"``
scores with the default provider's LeastAllocated + BalancedAllocation
in place of the profile's rule: a scheduler deaf to the profile.

**What a run is held to: the group multisets.** A *pool* is the nodes a
wave's pods of one kind may reach (their selector); its pods are
identical, and no other pod of the wave reaches its nodes. A *group* is
the pool's nodes that were alike before the wave: same capacity, same
use, in every column. Per-node bands (``reference.bands``) are loose
under this rule, which fills one node before it opens the next: of two
empty nodes one ends with eight pods and one with none, and which is
the tie-break's. What no tie-break can change is, for every group, the
*multiset* of what its nodes received.

**Lemma.** Let the score of a node of the pool rise strictly with every
pod of the wave it takes, for as long as the pod fits, and let nodes of
different groups that still have room score differently before the wave
(``exact_for`` checks both on the state it is given). Then under every
order of arrival, every batching and every tie-break the sequential rule
gives each group the same multiset of pods received.

*Proof.* The node chosen had the highest score among the feasible ones;
with the pod on it it scores strictly higher, so while the pod fits it
is the one strict maximum and takes the next pod too: the rule fills a
node until the pod no longer fits before it touches another. The node
it opens next is an untouched feasible node of highest score before the
wave. Untouched nodes of equal score are of one group, so a tie is
between nodes that are alike: whichever is opened receives the same
number (all that fits, or what is left of the wave), and the group's
multiset is the same. By induction over the nodes opened, the sequence
of (group, pods received) is the same under every tie-break. The order
of arrival does not matter because the pool's pods are identical and no
other pod reaches the pool's nodes; batching does not because the
program's batches replay the sequential rule on the state the batch
before left. QED.

So ``unexplained`` is one run of the sequential rule with the
lowest-index tie-break, and the distance between its group multisets
and the run's: a pod on a node of the wrong group, or one too many on a
node where one too few went to its twin, moves the sorted counts of its
groups by two in all, so half the distance is pods.
``tests/test_binpack_reference.py`` holds the lemma by brute force over
every order and tie-break on small pools, shows the multisets differing
where ``exact_for`` says the comparison is not exact, and holds the
program to it on seeded clusters.

With pods of different sizes inside one pool the rule no longer finishes
a node before it opens the next (a small pod may fit where a large one
does not), the outcomes over all orders differ, and the comparison would
have to search orders: the deployment keeps every pool to one size.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference

MAX_SCORE = 100
CPU, MEM, PODS = 0, 1, 2  # the fixed columns; extended resources follow


@dataclasses.dataclass
class Nodes:
    """Node state before a wave, in the apiserver's node order. Column 0
    is milli-cpu, 1 memory in bytes, 2 the pod count, 3 and up one
    extended resource each, in whole units."""

    cap: np.ndarray  # [N, R] int64
    used: np.ndarray  # [N, R] int64


def most_allocated(cap_cpu, cap_mem, req_cpu, req_mem,
                   precision: str = "exact"):
    """``NodeResourcesMostAllocated`` for requested totals that already
    include the incoming pod. int64 in, int64 scores out."""
    cap_cpu, cap_mem, req_cpu, req_mem = np.broadcast_arrays(
        *(np.asarray(x, dtype=np.int64)
          for x in (cap_cpu, cap_mem, req_cpu, req_mem))
    )
    pairs = ((cap_cpu, req_cpu), (cap_mem, req_mem))
    if precision == "exact":
        total = np.zeros_like(cap_cpu)
        for cap, req in pairs:
            ok = (cap > 0) & (req <= cap)
            total += np.where(ok, req * MAX_SCORE // np.maximum(cap, 1), 0)
        return total // 2
    q = reference._rounder(precision)  # float32, or rounded to bfloat16
    hundred = q(np.float32(MAX_SCORE))
    total = np.zeros(cap_cpu.shape, dtype=np.float32)
    for cap_i, req_i in pairs:
        cap, req = q(cap_i), q(req_i)
        safe = np.maximum(cap, np.float32(1))
        raw = np.floor(q(q(req * hundred) / safe))
        total = q(total + np.where((cap_i == 0) | (req > cap), 0, raw))
    return np.floor(q(total / np.float32(2))).astype(np.int64)


def scores(nodes: Nodes, used: np.ndarray, pod: np.ndarray, rule: str,
           precision: str):
    """The rule's score of every row of ``used`` with ``pod`` on it."""
    args = (nodes.cap[:, CPU], nodes.cap[:, MEM],
            used[:, CPU] + pod[CPU], used[:, MEM] + pod[MEM])
    if rule == "most":
        return most_allocated(*args, precision)
    if rule == "default":  # the default provider: a scheduler deaf to the profile
        return reference.scores(*args, precision)
    raise ValueError(f"unknown rule {rule!r}")


def fits(nodes: Nodes, used: np.ndarray, pod: np.ndarray) -> np.ndarray:
    """[N] bool: every column the pod asks fits (the three fixed ones
    are always looked at, an extended resource only where asked)."""
    asked = pod > 0
    asked[:PODS + 1] = True
    return (used[:, asked] + pod[asked] <= nodes.cap[:, asked]).all(axis=1)


def schedule(nodes: Nodes, pod, count: int, eligible: np.ndarray = None,
             rule: str = "most", precision: str = "exact"):
    """Place ``count`` pods asking ``pod`` ([R], the pod count column 1)
    in sequence. ``eligible`` [N] bool is their node selector (None:
    every node). Returns (``per_node`` [N] int64, the pods that ended on
    each node, and how many found no feasible node)."""
    pod = np.asarray(pod, dtype=np.int64)
    n = nodes.cap.shape[0]
    used = nodes.used.astype(np.int64).copy()
    per_node = np.zeros(n, dtype=np.int64)
    allowed = np.ones(n, dtype=bool) if eligible is None else eligible
    feasible = fits(nodes, used, pod) & allowed
    score = scores(nodes, used, pod, rule, precision)
    for placed in range(count):
        if not feasible.any():
            return per_node, count - placed
        # highest score, lowest index: argmax returns the first maximum
        choice = int(np.argmax(np.where(feasible, score, -1)))
        per_node[choice] += 1
        used[choice] += pod
        one = Nodes(nodes.cap[choice:choice + 1], None)
        row = used[choice:choice + 1]
        feasible[choice] = fits(one, row, pod)[0] and allowed[choice]
        score[choice] = scores(one, row, pod, rule, precision)[0]
    return per_node, 0


def groups(nodes: Nodes, eligible: np.ndarray = None) -> np.ndarray:
    """[N] int64: a label for each node, equal where capacity and use
    are equal in every column; -1 outside the pool."""
    n = nodes.cap.shape[0]
    rows = np.concatenate([nodes.cap, nodes.used], axis=1)
    _, label = np.unique(rows, axis=0, return_inverse=True)
    label = label.reshape(n).astype(np.int64)
    return label if eligible is None else np.where(eligible, label, -1)


def distance(label: np.ndarray, got: np.ndarray, want: np.ndarray) -> int:
    """Between two placements of a pool's pods, the sum over groups of
    the distance between the sorted counts their nodes received."""
    total = 0
    for g in np.unique(label[label >= 0]):
        members = label == g
        total += int(np.abs(
            np.sort(got[members]) - np.sort(want[members])
        ).sum())
    return total


def exact_for(nodes: Nodes, pod, eligible: np.ndarray = None) -> bool:
    """The lemma's two conditions on this pool and this pod, under the
    profile's rule in exact integers: a node's score rises strictly with
    every pod it takes while the pod fits, and groups that have room
    score differently before the wave."""
    pod = np.asarray(pod, dtype=np.int64)
    label = groups(nodes, eligible)
    opening = []
    for g in np.unique(label[label >= 0]):
        i = int(np.flatnonzero(label == g)[0])  # one node stands for all
        one = Nodes(nodes.cap[i:i + 1], None)
        row = nodes.used[i:i + 1].astype(np.int64).copy()
        last = None
        while fits(one, row, pod)[0]:
            s = int(scores(one, row, pod, "most", "exact")[0])
            if last is None:
                opening.append(s)
            elif s <= last:
                return False
            last = s
            row += pod
    return len(opening) == len(set(opening))


def unexplained(nodes: Nodes, pod, count: int, got: np.ndarray,
                eligible: np.ndarray = None) -> int:
    """Pods of a pool's placement ``got`` ([N], what each node received
    of ``count`` identical pods) that the profile's rule does not
    explain under any order, batching or tie-break: half the distance
    between the group multisets, a pod short of ``count`` counted
    whole."""
    want, _ = schedule(nodes, pod, count, eligible)
    got = np.asarray(got, dtype=np.int64)
    label = groups(nodes, eligible)
    stray = int(got[label < 0].sum())  # landed outside the pool
    short = max(int(want.sum()) - int(got[label >= 0].sum()), 0)
    return (distance(label, got, want) + short + 1) // 2 + stray
