"""The plain reference for gangs: all-or-nothing admission, one gang at a
time, written from the Coscheduling plugin's contract (a PodGroup's pods
are bound together or not at all) over the sequential scheduler of
``reference.py``. Imports nothing of the program.

``admit`` takes the gangs in a given order. A gang's members are placed
one by one on a copy of the node state, each on the feasible node the
published score rule ranks first; if every member found a node the copy
is kept, else it is thrown away and the gang holds nothing.

The order is not the program's to be held to: it batches, solves a
batch's gangs side by side and masks. What a run is held to is that its
outcome is one ``admit`` gives under SOME order (``admissible``), and
the lemma below makes that exact whatever the order, the batching and
the tie-break.

**Lemma** (pods of one size). The outcomes of ``admit`` over all orders
are exactly the all-or-nothing packings that are *maximal*: sets of
gangs that fit together and leave no slot count that an unadmitted gang
fits in.

- *Every maximal packing is an outcome.* Take its gangs first, in any
  order, then the rest. With pods of one size a gang fits when its size
  is at most the free slots (a slot is room for one pod on a node; the
  members are placed one by one, and any feasible node takes one), so
  each gang of the packing is admitted, and each of the rest finds fewer
  slots than its size, because the packing is maximal.
- *Every outcome is a maximal packing.* The admitted gangs were placed
  on one node state, so they fit together. A gang that was skipped was
  larger than the slots free when its turn came, and the slots free at
  the end are no more than that.

So ``admissible(outcome)`` is ``admit(outcome's gangs first, then the
rest) == outcome``, one run of the sequential rule. With pods of
different sizes a slot count no longer says what fits, the outcomes over
all orders are no longer the maximal packings, and the comparison would
have to search orders: the deployment keeps to one size
(``tests/test_gang_reference.py`` holds both directions by brute force).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference


def slots(nodes: reference.Nodes, pod: reference.PodClass) -> int:
    """Pods of class ``pod`` that the free room of ``nodes`` holds."""
    room = np.minimum.reduce([
        (nodes.cap_cpu - nodes.used_cpu) // max(pod.cpu, 1),
        (nodes.cap_mem - nodes.used_mem) // max(pod.mem, 1),
        nodes.cap_pods - nodes.used_pods,
    ])
    return int(np.clip(room, 0, None).sum())


def admit(nodes: reference.Nodes, pod: reference.PodClass, gangs: dict,
          order, precision: str = "exact") -> tuple:
    """Gangs (name -> size) taken one at a time in ``order``. Returns
    (the names admitted, in that order; ``per_node`` [N], the pods every
    node received)."""
    state = dataclasses.replace(
        nodes, used_cpu=nodes.used_cpu.copy(), used_mem=nodes.used_mem.copy(),
        used_pods=nodes.used_pods.copy(),
    )
    admitted = []
    total = np.zeros(nodes.cap_cpu.shape[0], dtype=np.int64)
    for name in order:
        per_node, unplaced = reference.schedule(
            state, pod, int(gangs[name]), precision
        )
        if unplaced:
            continue  # the copy is thrown away: the gang holds nothing
        state.used_cpu += per_node * pod.cpu
        state.used_mem += per_node * pod.mem
        state.used_pods += per_node
        total += per_node
        admitted.append(name)
    return admitted, total


def otherwise(nodes: reference.Nodes, pod: reference.PodClass, gangs: dict,
              outcome, rest) -> int:
    """Gangs that the reference decides otherwise than ``outcome`` when
    it takes ``outcome``'s gangs first and then ``rest`` (the gangs left
    out, in any order): those of ``outcome`` it cannot place beside the
    ones before them, and those of ``rest`` it admits into what is
    left. 0 exactly when ``outcome`` is a maximal packing."""
    outcome = list(outcome)
    got, _ = admit(nodes, pod, gangs, outcome + list(rest))
    return len(set(got) ^ set(outcome))


def admissible(nodes: reference.Nodes, pod: reference.PodClass, gangs: dict,
               outcome) -> bool:
    """``outcome`` (names of the gangs bound whole) is what ``admit``
    gives under some order."""
    taken = set(outcome)
    rest = [name for name in gangs if name not in taken]
    return otherwise(nodes, pod, gangs, outcome, rest) == 0


def ignoring_groups(nodes: reference.Nodes, pod: reference.PodClass,
                    gangs: dict, order) -> dict:
    """The control: the reference reading no pod groups. Every pod is a
    gang of one, taken in the order its gang was created. Returns name
    -> members bound (the first pods created fill the slots, whatever
    gang they belong to)."""
    offered = sum(int(gangs[name]) for name in order)
    _, unplaced = reference.schedule(nodes, pod, offered)
    left = offered - unplaced
    bound = {}
    for name in order:
        bound[name] = min(int(gangs[name]), left)
        left -= bound[name]
    return bound
