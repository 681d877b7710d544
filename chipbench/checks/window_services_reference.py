"""The window's placements against the plain reference that knows the
Services and the pods' soft anti-affinity (``chipbench/services_reference.py``):
for every wave of the window, at its own size, the pods that no
tie-break of the published rule explains.

Selector spread and preferred inter-pod affinity count pods, so a
score moves with every placement and no lemma about a node's count holds
whatever the order (as ``reference.bands`` has for identical pods, and
``image_reference.unexplained`` for static rows). What is held instead
is a **certificate**: an order in which the rule could have placed the
wave's pods as they were placed, replayed pod by pod. The order of the
binds is none: the program binds a batch in one transaction with its
pods grouped by target node (the committer sorts them so that the cache
takes them as per-node runs), so the watch sees node order, not the
order the batch was solved in. The order the program does solve in is
its queue's: priority, then the instant a pod was enqueued, which is
the order its informer delivered the creates in, which is the order the
apiserver made them in; every batch takes the oldest pods first and a
batch whose scores count pods is packed only after the batches before it
have committed, so over a wave of one priority the solve order is the
creation order whatever the batching. The apiserver lists pods in the
order it created them, so the wave's snapshot (a dict built from one
list) holds it. Each pod is scored by the reference against the node state
the apiserver showed before the wave (the wave's snapshot, less its own
pods: residents, ballast, init pods) plus the wave's pods bound before
it in that order *as the program placed them*; its node must have passed the filters
and be in the top class of the total. The number compared is the pods of
the worst wave for which that fails, plus its pods left unbound; the
limit is the configuration's (``window_services_reference``).

``control``: the reference itself placing the first window wave's pods
in the same order of services, (a) deaf to selector spread, (b) deaf to
preferred affinity, (c) deaf to the residents' half of the symmetric
terms alone (the pods the wave placed still score with theirs), (d) each
service scored by the next one's counts, (e) under the full rule with
the resource scores in float32 and (f) in bfloat16, each held to the
same certificate under the full rule. (a)-(d) have to read far above the
limit; (e) has to read what the program reads: it states float32.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference, services_reference
from chipbench.check import MIB, compare, nodes_before, wave_size

CONTROLS = (
    ("deaf to selector spread",
     services_reference.Rule(spread=False)),
    ("deaf to preferred affinity",
     services_reference.Rule(affinity=False)),
    ("deaf to the residents' symmetric half",
     services_reference.Rule(residents_symmetric=False)),
    ("each service scored by the next one's counts",
     services_reference.Rule(shift=1)),
    ("the full rule, resource scores in float32",
     services_reference.Rule(precision="float32")),
    ("the full rule, resource scores in bfloat16",
     services_reference.Rule(precision="bfloat16")),
)


def creation_order(snapshot: dict) -> dict:
    """pod name -> its place in the order the apiserver created the
    pods in: a snapshot is built from one list of the apiserver's, which
    lists in that order."""
    return {name: i for i, name in enumerate(snapshot)}


def wave_state(run, names: list, snapshot: dict, services: int,
               eligible=None):
    """What a wave's certificate needs: ``make`` builds the state before
    the wave at a precision; the services of its pods in the order they
    were created, with the unbound ones last; the row of each pod's node
    (-1: bound nowhere)."""
    mine = set(names)
    order = creation_order(snapshot)
    before = nodes_before(run, {
        name: node for name, node in snapshot.items() if name not in mine
    })
    size = wave_size(run, names)
    if size is None:
        raise ValueError("pods of different sizes: one class a wave")
    pod = reference.PodClass(cpu=size[0], mem=size[1] * MIB)
    service_of = run.service_of
    rows = run.node_rows
    counts = np.zeros((services, len(rows)), dtype=np.int64)
    for name, node in snapshot.items():
        if name not in mine and name in service_of:
            counts[service_of[name], rows[node]] += 1
    bound = sorted((n for n in names if n in order),
                   key=order.__getitem__)
    left = [n for n in names if n not in snapshot]
    arrivals = [service_of[n] for n in bound + left]
    placed = [rows[snapshot[n]] for n in bound] + [-1] * len(left)
    weight = int(run.config["services"]["term_weight"])

    def make(precision: str = "exact") -> services_reference.State:
        return services_reference.State(
            before, pod, counts, weight, eligible, precision
        )

    return make, arrivals, placed, len(left)


def read_controls(make, arrivals, what: str, limit: int) -> None:
    for name, rule in CONTROLS:
        other = services_reference.schedule(
            make(rule.precision), arrivals, rule
        )
        outside = services_reference.certify(make(), arrivals, other)
        print(f"control {what}: the reference placing the wave's "
              f"{len(arrivals)} pods {name} leaves {outside} that the full "
              f"rule does not explain (limit {limit})", flush=True)


def run(run, control: bool) -> bool:
    spec = run.config["window_services_reference"]
    limit = int(spec["limit_pods"])
    services = int(run.config["services"]["count"])
    waves = [w for w in run.waves if w["in_window"]]
    worst, total, unbound, pods = 0, 0, 0, 0
    for k, wave in enumerate(waves):
        make, arrivals, placed, left = wave_state(
            run, wave["names"], wave["snapshot"], services
        )
        outside = services_reference.certify(make(), arrivals, placed)
        worst = max(worst, outside)
        total += outside
        unbound += left
        pods += len(arrivals)
        if control and k == 0:
            read_controls(make, arrivals, "window", limit)
    return compare(
        "window against the reference that knows the Services and the "
        "pods' soft anti-affinity: pods of the worst wave that no "
        "tie-break of the default provider's rule explains, replayed in "
        f"the order they were created ({len(waves)} waves, {pods} "
        f"pods of {services} services, {total} such pods in all, {unbound} "
        "unbound)",
        worst, limit,
    ) and bool(waves)
