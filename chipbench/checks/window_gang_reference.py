"""The window's gangs against the plain reference
(``chipbench/gang_reference.py``): for every wave of the window, from
the node state the apiserver showed before it (the snapshot the
generator took once the wave had settled, less the wave's own pods),
``admit`` taking the gangs in the order "those the program bound, by
their last bind, then the rest as created" must admit exactly the gangs
the program bound and none of the rest: the program's outcome is one
the reference gives under some order, which with pods of one size is
every maximal all-or-nothing packing and nothing else (the module's
lemma), whatever the order of arrival, the batching and the tie-break.
The number compared is the worst wave's, in gangs; the limit is the
configuration's (``window_gang_reference``).

``control``: ``admit`` given the outcome of the reference reading no pod
groups, on every wave of the window, the worst wave's number
(``gang_guarantees`` says why not the first wave's alone)."""

from __future__ import annotations

from chipbench import gang_reference
from chipbench.check import compare
from chipbench.checks.gang_guarantees import before, worker


def run(run, control: bool) -> bool:
    spec = run.config["window_gang_reference"]
    pod = worker(run)
    bind = run.watcher.bind_time
    waves = [w for w in run.waves if w["in_window"] and "snapshot" in w]
    worst = total = gangs_seen = control_worst = 0
    for k, wave in enumerate(waves):
        sizes = {g: len(m) for g, m in wave["gangs"].items()}
        snap = wave["snapshot"]
        whole = [g for g, m in wave["gangs"].items()
                 if all(n in snap for n in m)]
        whole.sort(key=lambda g: max(
            bind.get(n, float("inf")) for n in wave["gangs"][g]))
        bound = set(whole)
        part = [g for g, m in wave["gangs"].items()
                if g not in bound and any(n in snap for n in m)]
        rest = [g for g in wave["order"] if g not in bound]
        nodes = before(run, wave)
        # a gang bound in part is no gang of the outcome, and counts
        found = gang_reference.otherwise(
            nodes, pod, sizes, whole, rest
        ) + len(part)
        worst = max(worst, found)
        total += found
        gangs_seen += len(sizes)
        if found:
            print(f"wave {k}: the reference decides {found} gangs otherwise "
                  f"({len(whole)} bound whole, {len(part)} in part)",
                  flush=True)
        if control:
            other = gang_reference.ignoring_groups(
                nodes, pod, sizes, wave["order"]
            )
            took = [g for g in wave["order"] if other[g] == sizes[g]]
            left_out = [g for g in wave["order"] if other[g] < sizes[g]]
            control_worst = max(control_worst, gang_reference.otherwise(
                nodes, pod, sizes, took, left_out
            ) + sum(1 for g in left_out if other[g]))
    if control:
        print(f"control window: the reference decides {control_worst} gangs "
              "otherwise than the reference reading no pod groups in the "
              f"worst of {len(waves)} wave(s) (limit {spec['limit_gangs']})",
              flush=True)
    return compare(
        "window against the reference: gangs the reference admits "
        f"otherwise, worst wave ({len(waves)} wave(s), {gangs_seen} gangs, "
        f"{total} otherwise in all)", worst, int(spec["limit_gangs"]),
    )
