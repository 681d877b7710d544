"""The plain reference: a sequential scheduler in numpy.

Written from the published kube-scheduler filter and score equations and
importing nothing of the program. It places identical pods one at a
time, each on the feasible node of highest score, lowest index:

- filter ``NodeResourcesFit``: cpu, memory and pod count all fit;
- filter ``NodeAffinity``: the pod's node selector, where it has one;
- filter ``PodTopologySpread`` (hard): a node of zone ``z`` is feasible
  when ``count[z] + 1 - min(count) <= maxSkew`` over the pods matching
  the constraint's selector (the incoming pod matches its own);
- filter ``InterPodAffinity`` (required anti-affinity on the hostname):
  a node is feasible when it holds no pod matching the term;
- score ``NodeResourcesLeastAllocated``: per resource
  ``floor((capacity - requested) * 100 / capacity)``, 0 when requested
  exceeds capacity, then ``floor(sum / 2)``;
- score ``NodeResourcesBalancedAllocation``:
  ``trunc((1 - |cpu_fraction - mem_fraction|) * 100)``, 0 when either
  fraction reaches 1;
- both at weight 1, requested counted with the incoming pod.

For identical pods the count that ends up on every node does not depend
on the order the pods arrive in or on how a batching scheduler splits
them, which is what lets a run compare a whole wave scheduled through
the API with this replay, node by node.

``precision="exact"`` computes the scores in integers. The two float
precisions compute the same equations in float32 and round every
intermediate to the named type: ``float32`` is what the program states,
``bfloat16`` is the control (the nearest precision below it), which the
check must tell from the exact answer.
"""

from __future__ import annotations

import dataclasses

import numpy as np

MAX_SCORE = 100


@dataclasses.dataclass
class Nodes:
    """Node state before a wave, in the apiserver's node order."""

    cap_cpu: np.ndarray  # [N] int64 milli-cpu
    cap_mem: np.ndarray  # [N] int64 bytes
    cap_pods: np.ndarray  # [N] int64
    used_cpu: np.ndarray
    used_mem: np.ndarray
    used_pods: np.ndarray
    zone: np.ndarray  # [N] int64 zone index


@dataclasses.dataclass(frozen=True)
class PodClass:
    """One class of identical pods. The constraints select the wave's
    own pods only (a fresh app label), so their counts start at 0."""

    cpu: int  # milli
    mem: int  # bytes
    spread_max_skew: int = 0  # > 0: hard zone spread over its own app
    anti_hostname: bool = False  # required anti-affinity to its own app


def round_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even),
    returned as float32."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32)
    bias = ((bits >> 16) & 1) + np.uint32(0x7FFF)
    return ((bits + bias) & np.uint32(0xFFFF0000)).view(np.float32)


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x, dtype=np.float32)
    if precision == "bfloat16":
        return round_bfloat16
    raise ValueError(f"unknown precision {precision!r}")


def scores(cap_cpu, cap_mem, req_cpu, req_mem, precision: str = "exact"):
    """LeastAllocated + BalancedAllocation for requested totals that
    already include the incoming pod. int64 in, int64 scores out."""
    cap_cpu, cap_mem, req_cpu, req_mem = np.broadcast_arrays(
        *(np.asarray(x, dtype=np.int64)
          for x in (cap_cpu, cap_mem, req_cpu, req_mem))
    )
    if precision == "exact":
        least = np.zeros_like(cap_cpu)
        for cap, req in ((cap_cpu, req_cpu), (cap_mem, req_mem)):
            ok = (cap > 0) & (req <= cap)
            least += np.where(
                ok, (cap - req) * MAX_SCORE // np.maximum(cap, 1), 0
            )
        least //= 2
        # |req_cpu/cap_cpu - req_mem/cap_mem| over one denominator, so
        # the truncation is of an exact quotient. The memory side is
        # counted in KiB to keep the products inside int64.
        cap_k, req_k = cap_mem >> 10, req_mem >> 10
        den = np.maximum(cap_cpu * cap_k, 1)
        num = np.abs(req_cpu * cap_k - req_k * cap_cpu)
        balanced = (den - num) * MAX_SCORE // den
        full = (
            (cap_cpu == 0) | (cap_mem == 0)
            | (req_cpu >= cap_cpu) | (req_mem >= cap_mem)
        )
        return least + np.where(full, 0, balanced)
    q = _rounder(precision)
    hundred = q(np.float32(MAX_SCORE))
    total = np.zeros(cap_cpu.shape, dtype=np.float32)
    fracs = []
    for cap_i, req_i in ((cap_cpu, req_cpu), (cap_mem, req_mem)):
        cap, req = q(cap_i), q(req_i)
        safe = np.maximum(cap, np.float32(1))
        raw = np.floor(q(q(q(cap - req) * hundred) / safe))
        total = q(total + np.where((cap_i == 0) | (req > cap), 0, raw))
        fracs.append(np.where(cap_i == 0, np.float32(1), q(req / safe)))
    least = np.floor(q(total / np.float32(2)))
    diff = q(np.abs(q(fracs[0] - fracs[1])))
    balanced = np.trunc(q(q(np.float32(1) - diff) * hundred))
    balanced = np.where((fracs[0] >= 1) | (fracs[1] >= 1), 0, balanced)
    return (least + balanced).astype(np.int64)


def schedule(
    nodes: Nodes, pod: PodClass, count: int, precision: str = "exact",
    eligible: np.ndarray = None,
):
    """Place ``count`` pods of one class in sequence. ``eligible`` [N]
    bool is the pods' node selector (None: every node). Returns
    (``per_node`` [N] int64, the pods that ended on each node, and
    ``unplaced``, how many found no feasible node)."""
    n = nodes.cap_cpu.shape[0]
    used_cpu = nodes.used_cpu.astype(np.int64).copy()
    used_mem = nodes.used_mem.astype(np.int64).copy()
    used_pods = nodes.used_pods.astype(np.int64).copy()
    per_node = np.zeros(n, dtype=np.int64)
    zones = int(nodes.zone.max()) + 1 if n else 0
    zone_count = np.zeros(zones, dtype=np.int64)
    score = scores(
        nodes.cap_cpu, nodes.cap_mem, used_cpu + pod.cpu,
        used_mem + pod.mem, precision,
    )
    for placed in range(count):
        feasible = (
            (used_cpu + pod.cpu <= nodes.cap_cpu)
            & (used_mem + pod.mem <= nodes.cap_mem)
            & (used_pods + 1 <= nodes.cap_pods)
        )
        if eligible is not None:
            feasible &= eligible
        if pod.spread_max_skew:
            skew = zone_count + 1 - zone_count.min()
            feasible &= (skew <= pod.spread_max_skew)[nodes.zone]
        if pod.anti_hostname:
            feasible &= per_node == 0
        if not feasible.any():
            return per_node, count - placed
        # highest score, lowest index: argmax returns the first maximum
        choice = int(np.argmax(np.where(feasible, score, -1)))
        per_node[choice] += 1
        used_cpu[choice] += pod.cpu
        used_mem[choice] += pod.mem
        used_pods[choice] += 1
        zone_count[nodes.zone[choice]] += 1
        score[choice] = scores(
            nodes.cap_cpu[choice:choice + 1], nodes.cap_mem[choice:choice + 1],
            used_cpu[choice:choice + 1] + pod.cpu,
            used_mem[choice:choice + 1] + pod.mem, precision,
        )[0]
    return per_node, 0


def bands(
    nodes: Nodes, pod: PodClass, quota, precision: str = "exact",
    eligible: np.ndarray = None,
):
    """The same greedy rule without its tie-break: for every node the
    fewest (``lo``) and the most (``hi``) pods of the wave it can hold
    under ANY order of breaking ties between equal scores.

    A node's score depends only on its own load, so the k-th pod a node
    receives is taken when the best score anywhere has sunk to
    ``m(k) = min(score(1..k))``; the wave takes the ``quota`` largest
    ``m`` of its group, and only the entries equal to the last one taken
    are a matter of tie-break. A group is the whole cluster, or one zone
    for a hard zone spread at ``maxSkew`` 1, which fills the zones in
    rounds; ``quota`` is then the [zones] count each zone received
    (``zone_quota_error`` holds those counts to the rounds)."""
    n = nodes.cap_cpu.shape[0]
    free = [
        (nodes.cap_cpu - nodes.used_cpu) // max(pod.cpu, 1),
        (nodes.cap_mem - nodes.used_mem) // max(pod.mem, 1),
        nodes.cap_pods - nodes.used_pods,
    ]
    room = np.clip(np.minimum.reduce(free), 0, None)
    if pod.anti_hostname:
        room = np.minimum(room, 1)
    if eligible is not None:
        room = np.where(eligible, room, 0)
    total = int(np.sum(quota))
    depth = int(min(room.max() if n else 0, total))
    k = np.arange(1, depth + 1, dtype=np.int64)[None, :]
    s = scores(
        nodes.cap_cpu[:, None], nodes.cap_mem[:, None],
        nodes.used_cpu[:, None] + k * pod.cpu,
        nodes.used_mem[:, None] + k * pod.mem, precision,
    )
    m = np.minimum.accumulate(s, axis=1)
    m = np.where(k <= room[:, None], m, -1)
    lo = np.zeros(n, dtype=np.int64)
    hi = np.zeros(n, dtype=np.int64)
    if pod.spread_max_skew:
        groups = [(nodes.zone == z, int(q)) for z, q in enumerate(quota)]
    else:
        groups = [(np.ones(n, dtype=bool), total)]
    for members, q in groups:
        values = np.sort(m[members].ravel())[::-1]
        values = values[values >= 0]
        if q <= 0 or values.size == 0:
            continue
        tau = values[min(q, values.size) - 1]
        lo[members] = (m[members] > tau).sum(axis=1)
        hi[members] = (m[members] >= tau).sum(axis=1)
    return lo, hi


def outside(per_node: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> int:
    """Pods of a placement that no tie-break of the rule explains: those
    short of a node's ``lo`` plus those beyond its ``hi``."""
    got = np.asarray(per_node, dtype=np.int64)
    return int(np.clip(lo - got, 0, None).sum() + np.clip(got - hi, 0, None).sum())


def zone_quota_error(per_zone: np.ndarray, count: int) -> int:
    """Pods by which the zones' counts depart from filling in rounds:
    every zone holds ``count // zones`` or one more."""
    per_zone = np.asarray(per_zone, dtype=np.int64)
    base = count // per_zone.shape[0]
    return int(
        np.clip(base - per_zone, 0, None).sum()
        + np.clip(per_zone - base - 1, 0, None).sum()
    )
