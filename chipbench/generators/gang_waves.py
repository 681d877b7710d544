"""Closed waves of PodGroup gangs onto a cluster that holds about half of
what a wave offers: create the wave's PodGroups, then its pods (a gang's
members together, as a job controller sends them; the seed shuffles the
gangs), wait until the wave has *settled*, read the apiserver, delete
every pod of the wave, bound or pending, and its PodGroups, repeat.

Settled, from the client's side (the watch's bind events): no gang is
bound in part, and every unbound gang is larger than the free slots
left, which are the slots the cluster showed free before the wave
(``Run.gang_free_slots``, read once after set-up: every wave is deleted
whole) less the wave's bound workers. Half of a wave's pods are
*rightly* never bound, so the harness's own count of a window's pods
(every pod created) cannot be used: a wave is created ``timed=False``,
which still records each pod's ``due`` and ``issued``, and when it has
settled the window gains the pods the rule admits: the members of every
gang bound whole, every member of a gang bound in part, and every member
of an unbound gang that the leftover would have held (taken smallest
first). So a pod the program should have bound and did not counts
``failed``, and a pod it rightly left does not. A pod of the window has
``deadline_s`` to be bound; the generator itself waits
``wave_timeout_s`` for a wave to settle, and a wave that has not by then
ends as it stands (a warm-up wave that does not settle ends the run).

A wave's record keeps ``gangs`` (name -> member names), ``order`` (the
gangs as created) and ``free_before`` for the comparisons.
"""

from __future__ import annotations

import gc
import time

from chipbench.harness import BenchError

POLL_S = 0.02


def sizes_of(params: dict) -> list:
    return [
        int(part["size"]) for part in params["gangs"]
        for _ in range(int(part["count"]))
    ]


def free_slots(run, params: dict) -> int:
    """Workers the cluster's free room holds, from the apiserver."""
    from chipbench import check, gang_reference, reference

    cls = run.config["pod_classes"][params["class"]]
    snap = {
        p.metadata.name: p.spec.node_name
        for p in run.client.list_pods()[0] if p.spec.node_name
    }
    return gang_reference.slots(
        check.nodes_before(run, snap),
        reference.PodClass(cls["cpu_milli"], cls["memory_mib"] * check.MIB),
    )


def build(run, params: dict) -> tuple:
    """(gangs, order, pods): the wave's PodGroups are created here, its
    pods are returned in the order they will be."""
    from kubernetes_tpu.api.types import (
        POD_GROUP_LABEL, ObjectMeta, PodGroup,
    )

    sizes = sizes_of(params)
    sizes = [sizes[int(k)] for k in run.rng.permutation(len(sizes))]
    gangs, order, pods = {}, [], []
    for g, size in enumerate(sizes):
        made = run.make_pods(params["class"], size, f"job{g}")
        # make_pods names a pod <app>-<serial>-<i>: the gang is its app
        # and serial, which the replay reads back as one app
        group = made[0].metadata.name.rsplit("-", 1)[0]
        for pod in made:
            pod.metadata.labels[POD_GROUP_LABEL] = group
        run.client.create_pod_group(PodGroup(
            metadata=ObjectMeta(name=group, namespace="default"),
            min_member=size,
            schedule_timeout_seconds=int(params["schedule_timeout_seconds"]),
        ))
        gangs[group] = [p.metadata.name for p in made]
        order.append(group)
        pods += made
    # a job controller creates the PodGroup and, once it is served, the
    # pods: wait until the watch has delivered the last one
    lister = run.informers.pod_groups()
    deadline = run.now() + 30
    while lister.get("default", order[-1]) is None:
        if run.now() > deadline:
            raise BenchError("the wave's PodGroups were not served in 30s")
        time.sleep(0.002)
    return gangs, order, pods


def tally(run, gangs: dict, free: int, before: dict = None) -> dict:
    """Where a wave stands, from the watch: gangs bound whole, gangs
    bound in part, gangs unbound, and the slots left. ``before`` is an
    earlier tally of the same wave: a gang it found whole is not read
    again."""
    bound = run.watcher.bind_time
    whole = list(before["whole"]) if before else []
    known = set(whole)
    part, unbound = [], []
    used = sum(len(gangs[g]) for g in whole)
    for group, names in gangs.items():
        if group in known:
            continue
        n = sum(1 for name in names if name in bound)
        used += n
        (whole if n == len(names) else part if n else unbound).append(group)
    return {"whole": whole, "part": part, "unbound": unbound,
            "left": free - used}


def settled(state: dict, gangs: dict) -> bool:
    return not state["part"] and all(
        len(gangs[g]) > state["left"] for g in state["unbound"]
    )


def admitted(state: dict, gangs: dict) -> list:
    """The pods the rule admits (the module's text)."""
    names = [n for g in state["whole"] + state["part"] for n in gangs[g]]
    left = state["left"]
    for g in sorted(state["unbound"], key=lambda g: len(gangs[g])):
        if len(gangs[g]) > left:
            break
        left -= len(gangs[g])
        names += gangs[g]  # the leftover would have held it
    return names


def offer(run, params: dict) -> dict:
    """One wave, created and waited for until it has settled or
    ``wave_timeout_s`` has passed; not recorded, not deleted."""
    free = run.gang_free_slots
    with run.phase("wave_build"):
        gangs, order, pods = build(run, params)
    with run.phase("wave_create"):
        start = run.now()
        run.create(
            pods, due=start, threads=params["creators"],
            chunk=params["chunk"], timed=False,
        )
    with run.phase("wave_drain"):
        state = tally(run, gangs, free)
        while not settled(state, gangs) and (
            run.now() - start <= params["wave_timeout_s"]
        ):
            time.sleep(POLL_S)
            state = tally(run, gangs, free, state)
    return {"start": start, "gangs": gangs, "order": order,
            "free_before": free, "state": state,
            "settled": settled(state, gangs)}


def one_wave(run, params: dict, warm: bool = False) -> None:
    offered = offer(run, params)
    gangs, state = offered["gangs"], offered["state"]
    if warm and not offered["settled"]:
        raise BenchError(
            f"a warm-up wave had not settled in {params['wave_timeout_s']}s: "
            f"{len(state['whole'])} gangs bound whole, {len(state['part'])} "
            f"in part, {state['left']} slots left"
        )
    names = admitted(state, gangs)
    if not any(n in run.watcher.bind_time for n in names):
        raise BenchError(
            f"a wave bound nothing in {params['wave_timeout_s']}s"
        )
    if run.in_window:
        run.window_names.extend(names)
    wave = run.record_wave(offered["start"], names)
    wave.update(gangs=gangs, order=offered["order"],
                free_before=offered["free_before"], left=state["left"],
                settled=offered["settled"])
    with run.phase("gap_delete"):
        wave["snapshot"] = snap = run.snapshot()
        everyone = [n for members in gangs.values() for n in members]
        pending = [n for n in everyone if n not in snap]
        # the pending ones first, by hand: Run.delete waits until the
        # scheduler's cache has dropped as many pods as were deleted,
        # and the cache never held these
        run.harness_deleted.update(pending)
        keys = [("default", n) for n in pending]
        for i in range(0, len(keys), 1024):
            run.client.delete_pods_bulk(keys[i:i + 1024])
        run.watcher.wait_deleted(
            pending, run.now() + params["delete_timeout_s"]
        )
        run.delete([n for n in everyone if n in snap],
                   params["delete_timeout_s"])
        run.server.delete_bulk(
            "PodGroup", [("default", g) for g in gangs]
        )
        gc.collect()  # as waves.py: the harness's own garbage, in the gap


def warmup(run, params: dict) -> None:
    run.gang_free_slots = free_slots(run, params)
    if run.gang_free_slots != int(params["expect_free_slots"]):
        raise BenchError(
            f"the residents leave {run.gang_free_slots} free slots, the mix "
            f"is sized for {params['expect_free_slots']}"
        )
    print(f"gang waves: {run.gang_free_slots} free slots before the first "
          f"wave, {sum(sizes_of(params))} workers a wave in "
          f"{len(sizes_of(params))} gangs", flush=True)
    for _ in range(params["warmup_waves"]):
        one_wave(run, params, warm=True)


def prepare(run, params: dict, seconds: float):
    return None


def window(run, params: dict, prepared, seconds: float) -> None:
    start = run.now()
    while run.now() - start < seconds:
        one_wave(run, params)
    waves = [w for w in run.waves if w["in_window"]]
    by_size: dict = {}
    for w in waves:
        bound = set(w["snapshot"])
        for members in w["gangs"].values():
            if all(n in bound for n in members):
                by_size[len(members)] = by_size.get(len(members), 0) + 1
    limit = float(params["deadline_s"])
    print("gangs admitted in the window by size: " + ", ".join(
        f"{count} of {size}" for size, count in sorted(by_size.items())
    ) + f"; slots left a wave: {' '.join(str(w['left']) for w in waves)}; "
        f"waves not settled: {sum(not w['settled'] for w in waves)}; waves "
        f"that took longer than deadline_s: "
        f"{sum(w['drain_s'] > limit for w in waves)}", flush=True)
    stages = run.sched.stage_seconds
    print("gang stages, seconds since the process began: " + ", ".join(
        f"{k} {stages[k]:.3f}" for k in sorted(stages)
        if k.startswith(("gang_fixup", "commit.permit"))
    ), flush=True)
