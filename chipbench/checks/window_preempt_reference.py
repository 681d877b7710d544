"""The window's evictions against the plain reference
(``chipbench/preempt_reference.py``): for every wave of the window, from
the pods the apiserver showed on every node before it, which nodes the
wave's preemptors ended on and which residents left for them, held to
what the published rule allows whatever the order of arrival, the
timing of the victims' deletes and the tie-break
(``preempt_reference.unexplained``).

The cluster before a wave is put together from what the harness has:
the snapshot the generator took once the wave was bound, less the
wave's own pods, plus the wave's victims (``wave["victims"]``: what
left during it and the harness did not delete) on the nodes they were
bound to. The number compared is the worst wave's, in preemptors; the
limit is the configuration's (``window_preempt_reference``).

``control``: the reference's own ``wave`` put through the same
comparison in the program's place, on the window's first wave: in
float32 (what the configuration states: 0), in bfloat16 (0 too at this
deployment: every sum and key of it is a bfloat16 number or rounds to
the same side, PERF.md section 2), and reading every resident's
priority as 0, which is the control that has to fail."""

from __future__ import annotations

from chipbench import preempt_reference as ref
from chipbench.check import (
    MIB, compare, parse_cpu_milli, parse_memory_bytes,
)


def pod_of(run, cls_name: str) -> ref.Pod:
    cls = run.config["pod_classes"][cls_name]
    return ref.Pod(cls_name, int(cls.get("priority", 0)),
                   cls["cpu_milli"], cls["memory_mib"] * MIB)


def cluster_before(run, wave: dict, where: dict) -> tuple:
    """(nodes, left): the reference's nodes as the wave found them, and
    node name -> the residents that left during it. ``where`` is pod
    name -> node from every earlier snapshot and the watch."""
    shape = run.config["cluster"]["node"]
    cap = (parse_cpu_milli(shape["cpu"]), parse_memory_bytes(shape["memory"]),
           shape["pods"])
    kinds = {c: pod_of(run, c) for c in run.config["pod_classes"]}
    mine = set(wave["names"])
    pods: dict = {
        f"node-{i}": [] for i in range(run.config["cluster"]["nodes"])
    }
    for name, node in wave["snapshot"].items():
        if name not in mine:
            pods[node].append(kinds[run.created[name]])
    left: dict = {}
    for name in wave["victims"]:
        kind = kinds[run.created[name]]
        pods[where[name]].append(kind)
        left.setdefault(where[name], []).append(kind)
    nodes = [ref.Node(name, *cap, mine) for name, mine in pods.items()]
    return nodes, left


def count(found: dict) -> int:
    return max(found["nodes"], found["victims"]) + found["unplaced"]


def run(run, control: bool) -> bool:
    spec = run.config["window_preempt_reference"]
    preemptor = pod_of(run, run.mix["params"]["preemptors"]["class"])
    where = dict(run.watcher.bind_node)
    for snap in run.snapshots:
        for name, node in snap.items():
            where.setdefault(name, node)  # what was created bound
    worst = total = seen = victims = 0
    waves = [w for w in run.waves if w["in_window"] and "snapshot" in w]
    for k, wave in enumerate(waves):
        nodes, left = cluster_before(run, wave, where)
        landed: dict = {}
        for name in wave["names"]:
            node = wave["snapshot"].get(name)
            if node is not None:
                landed[node] = landed.get(node, 0) + 1
        found = ref.unexplained(
            nodes, preemptor, landed, left, len(wave["names"]))
        worst = max(worst, count(found))
        total += count(found)
        seen += len(wave["names"])
        victims += len(wave["victims"])
        if count(found):
            print(f"wave {k}: {found}", flush=True)
        if control and k == 0:
            wanted = [preemptor] * len(wave["names"])
            for what, how in (
                ("in float32", {"precision": "float32"}),
                ("in bfloat16", {"precision": "bfloat16"}),
                ("reading every resident's priority as 0",
                 {"seen_priority": lambda p: 0}),
            ):
                other = ref.tally(ref.wave(nodes, wanted, **how))
                print(f"control window: the reference's wave of "
                      f"{len(wanted)} preemptors {what} leaves "
                      f"{count(ref.unexplained(nodes, preemptor, *other, len(wanted)))}"
                      f" unexplained (limit {spec['limit_preemptors']})",
                      flush=True)
    return compare(
        "window against the reference: preemptors of the worst wave whose "
        "node or victim no order and no tie-break of the published rule "
        f"explains ({len(waves)} wave(s), {seen} preemptors, {victims} "
        f"victims, {total} unexplained in all)",
        worst, int(spec["limit_preemptors"]),
    )
