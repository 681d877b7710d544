"""The check wave: one untimed wave after the window, sent by node
selector to the ballast pool."""

from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.check import MIB, compare, node_index, nodes_before

#: what this comparison reads of a pod class beside ``make_pods``'s keys
POD_CLASS_KEYS = ("check",)


def eligible_nodes(run, half) -> np.ndarray:
    """The ballast pool, or one half of it."""
    return np.array([
        run.in_ballast_pool(i) and (half is None or run.pool_half(i) == half)
        for i in range(run.config["cluster"]["nodes"])
    ], dtype=bool)


def check_wave(run, class_names: list, control: bool) -> bool:
    """One untimed wave through the API, after the window: for each
    class, identical pods under a fresh app label, sent by node selector
    to the ballast pool, where the nodes differ in score. Classes of one
    wave go to disjoint halves of the pool, so that a batch holds them
    mixed as the window's batches do while their placements stay
    independent. The reference works out from the node state the
    apiserver showed before the wave how many pods of a class every node
    may hold (``reference.bands``); the number compared is how many no
    tie-break of the published rule explains."""
    from chipbench.harness import HALF_KEY, POOL_KEY, compile_events

    classes = run.config["pod_classes"]
    zones = run.config["cluster"]["zones"]
    n = run.config["cluster"]["nodes"]
    before = nodes_before(run, run.snapshot())
    run.snapshots.pop()
    pods, names = [], {}
    for cls_name in class_names:
        half = classes[cls_name]["check"].get("half")
        selector = {POOL_KEY: "ballast"}
        if half is not None:
            selector[HALF_KEY] = str(half)
        made = run.make_pods(
            cls_name, int(classes[cls_name]["check"]["count"]),
            f"check{cls_name}", selector=selector,
        )
        names[cls_name] = [p.metadata.name for p in made]
        pods += made
    pods = [pods[int(k)] for k in run.rng.permutation(len(pods))]
    compiles = compile_events()
    started = run.now()
    run.create(pods, threads=int(run.mix["params"].get("creators", 1)))
    timeout = max(float(classes[c]["check"]["timeout_s"]) for c in class_names)
    run.wait_bound([p.metadata.name for p in pods], timeout)
    run.sched.wait_for_inflight_binds(timeout=30)
    print(f"check wave: {len(pods)} pods of {class_names} took "
          f"{run.now() - started:.2f}s, compile events "
          f"{compile_events() - compiles}", flush=True)
    where = {
        p.metadata.name: p.spec.node_name
        for p in run.client.list_pods()[0] if p.spec.node_name
    }
    ok = True
    for cls_name in class_names:
        cls = classes[cls_name]
        spec = cls["check"]
        count = int(spec["count"])
        got = np.zeros(n, dtype=np.int64)
        for name in names[cls_name]:
            if name in where:
                got[node_index(where[name])] += 1
        pod = reference.PodClass(
            cpu=cls["cpu_milli"], mem=cls["memory_mib"] * MIB,
            spread_max_skew=cls.get("spread", {}).get("max_skew", 0),
            anti_hostname="anti_affinity" in cls,
        )
        eligible = eligible_nodes(run, spec.get("half"))

        def unexplained(per_node: np.ndarray) -> int:
            quota, rounds = count, 0
            if pod.spread_max_skew:
                quota = np.bincount(
                    before.zone, weights=per_node, minlength=zones
                )
                rounds = reference.zone_quota_error(quota, count)
            lo, hi = reference.bands(before, pod, quota, "exact", eligible)
            return (reference.outside(per_node, lo, hi) + rounds
                    + abs(count - int(per_node.sum())))

        ok &= compare(
            f"check wave {cls_name}: pods no tie-break of the reference "
            f"explains ({count} pods, {int(got.sum())} bound, node "
            "selector to the ballast pool)",
            unexplained(got), int(spec["limit_pods"]),
        )
        if control:
            for precision in ("float32", "bfloat16"):
                other, _ = reference.schedule(
                    before, pod, count, precision, eligible
                )
                print(f"control {cls_name}: the reference scheduling in "
                      f"{precision} leaves {unexplained(other)} pods "
                      f"unexplained (limit {spec['limit_pods']})", flush=True)
    return bool(ok)


def run(run, control: bool) -> bool:
    return check_wave(run, run.mix["params"]["check_classes"], control)
