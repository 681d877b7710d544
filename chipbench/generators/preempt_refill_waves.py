"""Closed preemption waves on a full cluster: create a wave of
high-priority pods that fit nowhere, wait until every one is bound (each
after the eviction it needs), read the apiserver, delete the wave, put
back a pod of its class for every resident that was evicted (through
the scheduler, untimed: nobody waits for them), wait until those are
bound, repeat. Every wave is the same; the seed changes nothing but pod
order inside the scheduler.

A wave's record keeps ``victims``: the names of the pods that left
during it and that the harness did not delete, from the watch
(``Run.evicted``), for the comparison with the reference. A resident
created bound (the tier pool's) that was evicted comes back through the
scheduler like any other, so the cluster stays full; where it lands is
then the scheduler's choice, and the comparisons read every wave from
the snapshot before it.

A pod of the window has ``deadline_s`` to be bound; one bound later
counts as failed (``harness.end_to_end`` reads the same key). The
generator itself waits ``wave_timeout_s`` for a wave, so that a wave
ends whole: every preemptor bound before it is deleted, its victims
counted, the cluster full again. The first wave of a process compiles
the preemption kernel, which none of its pods outlasts bound, and with
an empty compile cache that takes longer than a pod of the window may
(19 s on the chip, PERF.md section 6): warm-up is set-up, and a warm-up
wave that is not bound by ``wave_timeout_s`` ends the run."""

from __future__ import annotations

import gc

from chipbench.harness import BenchError


def by_class(run, names) -> dict:
    counts: dict = {}
    for name in names:
        counts[run.created[name]] = counts.get(run.created[name], 0) + 1
    return counts


def one_wave(run, params: dict, warm: bool = False) -> None:
    spec = params["preemptors"]
    with run.phase("wave_build"):
        pods = run.make_pods(spec["class"], spec["count"], spec["class"])
        names = [p.metadata.name for p in pods]
    with run.phase("wave_create"):
        start = run.now()
        run.create(
            pods, due=start, threads=params["creators"],
            chunk=params["chunk"],
        )
    with run.phase("wave_drain"):
        left = params["wave_timeout_s"] - (run.now() - start)
        if not run.wait_bound(names, left) and warm:
            raise BenchError(
                f"a warm-up wave of {len(names)} preemptors was not bound "
                f"in {params['wave_timeout_s']}s"
            )
    wave = run.record_wave(start, names)
    with run.phase("gap_delete"):
        wave["snapshot"] = run.snapshot()
        run.delete(names, params["delete_timeout_s"])
    with run.phase("gap_refill"):
        # what left since the last refill, by name, from the watch, which
        # keeps them in the order it saw them go; once it has shown the
        # wave's own deletes it has shown every eviction before them
        known = sum(len(w.get("victims", ())) for w in run.waves)
        wave["victims"] = list(run.evicted())[known:]
        refill = []
        for cls, count in sorted(by_class(run, wave["victims"]).items()):
            refill += run.make_pods(cls, count, "refill")
        run.create(refill, threads=params["creators"],
                   chunk=params["chunk"], timed=False)
        if not run.wait_bound([p.metadata.name for p in refill],
                              params["refill_timeout_s"]):
            print(f"refill: not all of {len(refill)} pods bound in "
                  f"{params['refill_timeout_s']}s", flush=True)
        gc.collect()  # as waves.py: the harness's own garbage, in the gap


def warmup(run, params: dict) -> None:
    for _ in range(params["warmup_waves"]):
        one_wave(run, params, warm=True)


def prepare(run, params: dict, seconds: float):
    # the preemptor's own account of why it searched a preemptor again,
    # as the window opens (a program from before the counter has none)
    preemptor = run.sched.preemptor
    return dict(getattr(preemptor, "searched_again", {}))


def window(run, params: dict, prepared, seconds: float) -> None:
    start = run.now()
    while run.now() - start < seconds:
        one_wave(run, params)
    stages = run.sched.stage_seconds
    print("preemption stages, seconds since the process began: "
          + ", ".join(f"{k} {stages[k]:.3f}" for k in sorted(stages)
                      if k.startswith(("preempt", "victim"))), flush=True)
    again = getattr(run.sched.preemptor, "searched_again", None)
    if again is not None:
        print("preemptors searched again in the window, by the "
              "preemptor's own reason: " + (", ".join(
                  f"{k} {v - prepared.get(k, 0)}"
                  for k, v in sorted(again.items())) or "none"), flush=True)
    print("victims a wave of the window, by class: " + " ".join(
        "+".join(
            f"{count} {cls}" for cls, count in sorted(by_class(
                run, w["victims"]).items())
        ) or "none" for w in run.waves if w["in_window"]), flush=True)
