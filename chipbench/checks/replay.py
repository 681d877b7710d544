"""The replay: the guarantees the configuration states, over every
snapshot of the apiserver the window took (or, where nothing is deleted,
the whole window)."""

from __future__ import annotations

import numpy as np

from chipbench.check import (
    compare, node_index, parse_cpu_milli, parse_memory_bytes, usage,
)


def replay(run, snapshot: dict) -> dict:
    """The guarantees over one snapshot of the apiserver: how far any
    node is over its allocatable, the worst zone skew of a ``spread``
    app, hosts shared inside an ``anti`` app, and pods whose node in the
    apiserver is not the one the watch reported."""
    config = run.config
    cluster = config["cluster"]
    shape = cluster["node"]
    created = run.created
    cpu, mem, pods = usage(config, created, snapshot)
    over = int(
        (cpu > parse_cpu_milli(shape["cpu"])).sum()
        + (mem > parse_memory_bytes(shape["memory"])).sum()
        + (pods > shape["pods"]).sum()
    )
    by_app: dict = {}
    for name, node in snapshot.items():
        by_app.setdefault(name.rsplit("-", 1)[0], []).append((name, node))
    skew = 0
    shared = 0
    classes = config["pod_classes"]
    for app, members in by_app.items():
        cls = classes[created[members[0][0]]]
        if "spread" in cls:
            zones = np.zeros(cluster["zones"], dtype=np.int64)
            for _, node in members:
                zones[node_index(node) % cluster["zones"]] += 1
            over_skew = int(zones.max() - zones.min()) - cls["spread"]["max_skew"]
            skew = max(skew, over_skew)
        if "anti_affinity" in cls:
            hosts = [node for _, node in members]
            shared += len(hosts) - len(set(hosts))
    watched = run.watcher.bind_node
    mismatch = sum(1 for name, node in snapshot.items()
                   if name not in run.prebound and watched.get(name) != node)
    return {"over": over, "skew": skew, "shared": shared,
            "mismatch": mismatch}


def run(run, control: bool) -> bool:
    ok = True
    if not run.snapshots:
        run.snapshot()  # a cell that deletes nothing is replayed whole
    worst = {"over": 0, "skew": 0, "shared": 0, "mismatch": 0}
    seen = 0
    for snap in run.snapshots:
        found = replay(run, snap)
        seen += len(snap)
        for key in worst:
            worst[key] = max(worst[key], found[key])
    ok &= compare(f"replay of {len(run.snapshots)} snapshot(s), {seen} "
                  "placements: nodes over allocatable", worst["over"], 0)
    ok &= compare("replay: zone skew beyond maxSkew, worst spread app",
                  worst["skew"], 0)
    ok &= compare("replay: pods sharing a host inside an anti app",
                  worst["shared"], 0)
    ok &= compare("replay: pods whose node in the apiserver differs from "
                  "the watch's", worst["mismatch"], 0)
    ok &= compare("watch history: pods bound more than once",
                  len(run.watcher.rebinds), 0)
    return bool(ok)
