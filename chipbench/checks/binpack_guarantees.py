"""What a deployment of extended resources and accelerator pools
guarantees beyond the replay's cpu, memory and pod count, counted over
every snapshot of the apiserver the run took (a wave's, once it had
drained). The limits are the configuration's (``binpack_guarantees``),
both 0:

- *nodes over an extended resource*: a node whose bound pods ask more
  of a resource of the node shape's ``scalars`` than the node has (a
  fit that leaves the column out puts a ninth GPU pod on an eight-GPU
  node, which cpu and memory allow);
- *pods outside their pool*: a pod the generator gave a node selector
  on the zone, bound to a node of another zone.

``node_state`` gives the comparisons the nodes in
``binpack_reference``'s columns: cpu, memory, pod count, then the node
shape's ``scalars`` in the file's order.
"""

from __future__ import annotations

import numpy as np

from chipbench import binpack_reference
from chipbench.check import MIB, compare, parse_cpu_milli, parse_memory_bytes


def columns(run) -> list:
    return list(run.config["cluster"]["node"].get("scalars", {}))


def request(run, cls_name: str) -> np.ndarray:
    """What one pod of the class asks, in ``node_state``'s columns."""
    cls = run.config["pod_classes"][cls_name]
    asked = cls.get("scalars", {})
    return np.array(
        [cls["cpu_milli"], cls["memory_mib"] * MIB, 1]
        + [int(asked.get(name, 0)) for name in columns(run)],
        dtype=np.int64,
    )


def node_state(run, snapshot: dict) -> binpack_reference.Nodes:
    """Every row of ``run.node_rows`` at the configuration's one shape,
    holding what ``snapshot`` puts there."""
    shape = run.config["cluster"]["node"]
    rows = run.node_rows
    cap = np.array(
        [parse_cpu_milli(shape["cpu"]), parse_memory_bytes(shape["memory"]),
         shape["pods"]] + [int(shape["scalars"][c]) for c in columns(run)],
        dtype=np.int64,
    )
    asks = {name: request(run, name) for name in run.config["pod_classes"]}
    used = np.zeros((len(rows), cap.shape[0]), dtype=np.int64)
    for name, node in snapshot.items():
        used[rows[node]] += asks[run.created[name]]
    return binpack_reference.Nodes(np.tile(cap, (len(rows), 1)), used)


def run(run, control: bool) -> bool:
    spec = run.config["binpack_guarantees"]
    zones = run.config["cluster"]["zones"]
    pod_zone = run.binpack["pod_zone"]
    rows = run.node_rows
    over = outside = seen = 0
    for snap in run.snapshots:
        state = node_state(run, snap)
        over = max(over, int(
            (state.used[:, 3:] > state.cap[:, 3:]).any(axis=1).sum()
        ))
        outside = max(outside, sum(
            1 for name, node in snap.items()
            if name in pod_zone and rows[node] % zones != pod_zone[name]
        ))
        seen += len(snap)
    ok = compare(
        f"binpack guarantees over {len(run.snapshots)} snapshot(s), {seen} "
        f"placements: nodes whose bound pods ask more {'/'.join(columns(run))} "
        "than the node has, worst snapshot", over, int(spec["limit_nodes_over"]),
    )
    ok &= compare(
        "binpack guarantees: pods bound outside the pool their node "
        "selector names, worst snapshot", outside,
        int(spec["limit_pods_outside_pool"]),
    )
    return bool(ok)
