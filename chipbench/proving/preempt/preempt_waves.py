"""Closed preemption waves on a full cluster: create a wave of
high-priority pods that fit nowhere, wait until every one is bound (each
after the eviction it needs), delete the wave, put back as many
low-priority pods as were evicted, wait until those are bound, repeat.
Every wave is the same; the seed changes nothing but pod order inside
the scheduler."""

from __future__ import annotations

import gc


def one_wave(run, params: dict) -> None:
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
        left = params["deadline_s"] - (run.now() - start)
        run.wait_bound(names, left)
    wave = run.record_wave(start, names)
    with run.phase("gap_delete"):
        wave["snapshot"] = run.snapshot()
        run.delete(names, params["delete_timeout_s"])
    with run.phase("gap_refill"):
        # as many fillers as left since the last refill, counted from
        # the watch: they are not of the window's pods
        put_back = sum(w.get("refilled", 0) for w in run.waves)
        wave["refilled"] = len(run.evicted()) - put_back
        fillers = run.make_pods(
            params["refill_class"], wave["refilled"], "refill"
        )
        run.create(fillers, threads=params["creators"],
                   chunk=params["chunk"], timed=False)
        if not run.wait_bound([p.metadata.name for p in fillers],
                              params["refill_timeout_s"]):
            print(f"refill: not all of {len(fillers)} fillers bound in "
                  f"{params['refill_timeout_s']}s", flush=True)
        gc.collect()  # as waves.py: the harness's own garbage, in the gap


def warmup(run, params: dict) -> None:
    for _ in range(params["warmup_waves"]):
        one_wave(run, params)


def prepare(run, params: dict, seconds: float):
    return None


def window(run, params: dict, prepared, seconds: float) -> None:
    start = run.now()
    while run.now() - start < seconds:
        one_wave(run, params)
    stages = run.sched.stage_seconds
    print("preemption stages, seconds since the process began: "
          + ", ".join(f"{k} {stages[k]:.3f}" for k in sorted(stages)
                      if k.startswith(("preempt", "victim"))), flush=True)
    print("waves (pods refilled): "
          + " ".join(str(w["refilled"]) for w in run.waves), flush=True)
