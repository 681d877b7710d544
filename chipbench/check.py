"""What decides ``correct``. Every number compared is printed beside its
limit.

(a) A replay of every placement of the window against the guarantees the
    configuration states.
(b) The window's own placements against the plain reference: for every
    wave of the window (the whole window where nothing is deleted), the
    pods each node received against the fewest and the most the
    published scoring rule lets it receive from the node state before
    the wave. This is the timed path itself, at its own sizes: pods
    without a node selector, scored over the whole cluster.
(c) A check wave after the window, scheduled through the API like any
    wave, whose pods go by node selector to a pool of nodes that differ
    in score, compared with the reference in the same way. This is what
    tells float32 scoring from bfloat16 (the control) in every cell; (b)
    does so in a burst's wave and not in the open-loop window, since on
    nodes that fill alike water-filling ends the same whatever the
    score's last bits.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference

MIB = 1 << 20


def parse_cpu_milli(text: str) -> int:
    return int(text[:-1]) if text.endswith("m") else int(float(text) * 1000)


def parse_memory_bytes(text: str) -> int:
    for suffix, shift in (("Ki", 10), ("Mi", 20), ("Gi", 30)):
        if text.endswith(suffix):
            return int(text[:-2]) << shift
    return int(text)


def compare(what: str, value, limit) -> bool:
    ok = value <= limit
    print(f"compare {what}: {value} (limit {limit}) -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def node_index(name: str) -> int:
    return int(name.rsplit("-", 1)[1])


def usage(config: dict, created: dict, snapshot: dict):
    """Per-node cpu, memory and pod count of a snapshot, from the
    classes this harness created the pods with."""
    n = config["cluster"]["nodes"]
    classes = config["pod_classes"]
    cpu = np.zeros(n, dtype=np.int64)
    mem = np.zeros(n, dtype=np.int64)
    pods = np.zeros(n, dtype=np.int64)
    for name, node in snapshot.items():
        cls = classes[created[name]]
        i = node_index(node)
        cpu[i] += cls["cpu_milli"]
        mem[i] += cls["memory_mib"] * MIB
        pods[i] += 1
    return cpu, mem, pods


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


def nodes_before(run, snapshot: dict) -> reference.Nodes:
    config = run.config
    cluster = config["cluster"]
    shape = cluster["node"]
    n = cluster["nodes"]
    cpu, mem, pods = usage(config, run.created, snapshot)
    return reference.Nodes(
        cap_cpu=np.full(n, parse_cpu_milli(shape["cpu"]), dtype=np.int64),
        cap_mem=np.full(n, parse_memory_bytes(shape["memory"]), dtype=np.int64),
        cap_pods=np.full(n, shape["pods"], dtype=np.int64),
        used_cpu=cpu, used_mem=mem, used_pods=pods,
        zone=np.arange(n, dtype=np.int64) % cluster["zones"],
    )


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


def wave_size(run, names) -> tuple:
    """(cpu_milli, memory_mib) where every pod of ``names`` asks for the
    same, else None."""
    classes = run.config["pod_classes"]
    sizes = {
        (classes[c]["cpu_milli"], classes[c]["memory_mib"])
        for c in {run.created[n] for n in names}
    }
    return sizes.pop() if len(sizes) == 1 else None


def window_against_reference(run, control: bool) -> bool:
    """(b) of the module's text. A wave's pods all ask for the same, so
    a node's score depends on how many of them it holds and nothing
    else, and ``reference.bands`` gives for every node the fewest and
    the most the rule lets it receive under any order of arrival,
    batching or tie-break. The pods' own constraints (spread, anti) are
    left to the replay; the bands are those of the scoring rule alone,
    which the mix's ``window_check`` holds every wave to within
    ``limit_pods`` pods; the number compared is the worst wave's."""
    spec = run.mix.get("window_check")
    if spec is None:  # e.g. a mix whose waves hold pods of different sizes
        print("window against the reference: not compared, the mix has "
              "no window_check", flush=True)
        return True
    groups = [
        (w["names"], w["snapshot"]) for w in run.waves
        if w["in_window"] and "snapshot" in w
    ]
    if not groups:  # nothing was deleted: the whole window is one wave
        groups = [(run.window_names, run.snapshots[-1])]
    worst = 0
    total = 0
    seen = 0
    control_read = False
    for names, snapshot in groups:
        size = wave_size(run, names)
        if size is None:
            raise ValueError("a wave of pods of different sizes has no bands")
        mine = set(names)
        before = nodes_before(run, {
            name: node for name, node in snapshot.items() if name not in mine
        })
        got = np.zeros(run.config["cluster"]["nodes"], dtype=np.int64)
        for name in names:
            if name in snapshot:
                got[node_index(snapshot[name])] += 1
        pod = reference.PodClass(cpu=size[0], mem=size[1] * MIB)
        lo, hi = reference.bands(before, pod, len(names))
        outside = reference.outside(got, lo, hi) + len(names) - int(got.sum())
        worst = max(worst, outside)
        total += outside
        seen += len(names)
        if control and not control_read:
            for precision in ("float32", "bfloat16"):
                other, _ = reference.schedule(
                    before, pod, len(names), precision
                )
                print(f"control window: the reference scheduling the first "
                      f"wave's {len(names)} pods in {precision} leaves "
                      f"{reference.outside(other, lo, hi)} outside the "
                      "bands", flush=True)
            control_read = True
    return compare(
        f"window against the reference: pods of the worst wave outside "
        f"what the scoring rule allows their node ({len(groups)} wave(s), "
        f"{seen} pods, {total} outside in all, no node selector)",
        worst, int(spec["limit_pods"]),
    )


def run_checks(run, control: bool) -> bool:
    """Everything that decides ``correct``, after the window closed."""
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
    ok &= window_against_reference(run, control)
    ok &= check_wave(run, run.mix["params"]["check_classes"], control)
    return bool(ok)
