"""What gang scheduling guarantees, counted from the client's side over
every wave of the window (the snapshot of the apiserver the generator
took once the wave had settled), and then probed once after it. The
limits are the configuration's (``gang_guarantees``), all 0:

- *workers of gangs bound in part*: a PodGroup's pods are bound together
  or not at all;
- *unbound gangs the leftover holds*: a job that fits the free capacity
  starts. The leftover is counted on the nodes (the slots free before
  the wave, from the snapshot less the wave's own pods, less the wave's
  bound workers), so a gang is held to have fitted only where the nodes
  had the room;
- *the probe*: a further wave, settled and not deleted; then as many
  plain pods of the workers' class as that wave left slots, which must
  all bind within ``probe_timeout_s`` (a gang left unbound holds
  nothing, at Permit or in the scheduler's cache); then one more, which
  must still be pending after ``probe_hold_s`` (nothing is
  overcommitted; the replay counts the nodes themselves).

``control``: the reference reading no pod groups
(``gang_reference.ignoring_groups``) in the program's place on every
wave of the window: the first pods created fill the slots whatever gang
they belong to, and the gang that the last slot falls in is bound in
part. Every size is a multiple of the smallest, so in about a third of
the shuffles the slots end where a gang ends and that wave's outcome is
a sound one by luck; the number is the worst wave's, as the
comparison's is, and over a window's waves it has to come out above
the limit."""

from __future__ import annotations

import time

from chipbench import gang_reference, reference
from chipbench.check import MIB, compare, nodes_before


def worker(run) -> reference.PodClass:
    cls = run.config["pod_classes"][run.mix["params"]["class"]]
    return reference.PodClass(cls["cpu_milli"], cls["memory_mib"] * MIB)


def before(run, wave: dict) -> reference.Nodes:
    """The nodes as the wave found them."""
    mine = {n for members in wave["gangs"].values() for n in members}
    return nodes_before(run, {
        name: node for name, node in wave["snapshot"].items()
        if name not in mine
    })


def count(wave: dict, bound: dict, free: int) -> tuple:
    """(workers of gangs bound in part, unbound gangs that the slots
    left would hold) where ``bound`` is gang -> members bound."""
    sizes = {g: len(m) for g, m in wave["gangs"].items()}
    left = free - sum(bound.values())
    part = sum(n for g, n in bound.items() if 0 < n < sizes[g])
    fits = sum(1 for g, n in bound.items() if n == 0 and sizes[g] <= left)
    return part, fits


def probe(run, spec: dict) -> tuple:
    """(plain pods that did not bind into the slots a settled wave left,
    pods that bound beyond them)."""
    from chipbench.generators import gang_waves

    params = run.mix["params"]
    wave = gang_waves.offer(run, params)
    left = wave["state"]["left"]
    fill = run.make_pods(params["class"], left, "probe")
    run.create(fill, threads=params["creators"], chunk=params["chunk"],
               timed=False)
    names = [p.metadata.name for p in fill]
    run.wait_bound(names, float(spec["probe_timeout_s"]))
    unbound = sum(1 for n in names if n not in run.watcher.bind_time)
    extra = run.make_pods(params["class"], 1, "probeover")
    run.create(extra, timed=False)
    time.sleep(float(spec["probe_hold_s"]))
    over = sum(1 for p in extra if p.metadata.name in run.watcher.bind_time)
    print(f"probe: a further wave settled {wave['settled']} with {left} "
          f"slots left; {len(names) - unbound} of {len(names)} plain pods "
          f"bound into them, {over} beyond them", flush=True)
    return unbound + (0 if wave["settled"] else 1), over


def run(run, control: bool) -> bool:
    spec = run.config["gang_guarantees"]
    pod = worker(run)
    waves = [w for w in run.waves if w["in_window"] and "snapshot" in w]
    part = fits = control_part = 0
    for k, wave in enumerate(waves):
        nodes = before(run, wave)
        free = gang_reference.slots(nodes, pod)
        bound = {
            g: sum(1 for n in members if n in wave["snapshot"])
            for g, members in wave["gangs"].items()
        }
        found = count(wave, bound, free)
        if any(found):
            print(f"wave {k}: {found[0]} workers of gangs bound in part, "
                  f"{found[1]} unbound gangs the leftover holds "
                  f"({free} slots before it)", flush=True)
        part = max(part, found[0])
        fits = max(fits, found[1])
        if control:
            sizes = {g: len(m) for g, m in wave["gangs"].items()}
            other = gang_reference.ignoring_groups(
                nodes, pod, sizes, wave["order"]
            )
            control_part = max(control_part, count(wave, other, free)[0])
    if control:
        print("control gangs: the reference reading no pod groups leaves "
              f"{control_part} workers of gangs bound in part in the worst "
              f"of {len(waves)} wave(s) (limit "
              f"{spec['limit_bound_in_part']})", flush=True)
    ok = compare(
        f"gangs: workers of gangs bound in part, worst wave ({len(waves)} "
        "wave(s))", part, int(spec["limit_bound_in_part"]),
    )
    ok &= compare(
        "gangs: unbound gangs that the slots left would hold, worst wave",
        fits, int(spec["limit_unbound_that_fit"]),
    )
    unbound, over = probe(run, spec)
    ok &= compare(
        "gangs: plain pods that did not bind into the slots a settled wave "
        "left (a gang left unbound holds nothing)", unbound,
        int(spec["limit_probe_unbound"]),
    )
    ok &= compare(
        "gangs: plain pods bound beyond the slots left", over,
        int(spec["limit_probe_overcommitted"]),
    )
    return bool(ok)
