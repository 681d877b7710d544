"""The check wave of a cluster of Services: after the window, one wave
sent by node selector to the ballast pool, where the nodes differ in
resource score, and held to the same certificate as the window's waves
(``checks/window_services_reference.py``): replayed in the order they
were created, each pod's node has to be in the top class of the full
rule over the pool at that instant.

Half of its pods are of one FRESH service (a Service and a ReplicaSet
created for it; the label, the owner reference and the soft
anti-affinity term as every replica has them), half of the LARGEST
resident service, mixed by the run's seed. No resident is of the fresh
service, so its counts start at 0 and what ranks the pool's nodes at
first is the two resource scores, which is what tells float32 from
bfloat16 here as ``check_wave`` does in the other cells. The pool is
small enough for the wave to stack some six pods a node, so a step of
the count is worth about as much as the nodes differ in resource score:
that is where a scheduler deaf to preferred affinity places otherwise
(in the window, one pod a node at most, selector spread's node term
ranks the nodes as the affinity term does and such a scheduler places
alike). The largest service has a resident on nine nodes of ten, none
on the tenth: that is where a scheduler that forgets the residents'
half of the symmetric terms places otherwise.
"""

from __future__ import annotations

from chipbench import services_reference
from chipbench.check import compare
from chipbench.checks.check_wave import eligible_nodes
from chipbench.checks.window_services_reference import (
    read_controls, wave_state,
)

#: what this comparison reads of a pod class beside ``make_pods``'s keys
POD_CLASS_KEYS = ("check",)


def run(run, control: bool) -> bool:
    from chipbench.generators import rollout_waves
    from chipbench.harness import POOL_KEY, compile_events

    ok = True
    for cls_name in run.mix["params"]["check_classes"]:
        spec = run.config["pod_classes"][cls_name]["check"]
        count = int(spec["count"])
        fresh = run.services.count  # the next service number
        run.services.create_objects(run, first=fresh, count=1)
        largest = int(run.services.shares(run.services.residents).argmax())
        services = [fresh] * (count // 2) + [largest] * (count - count // 2)
        services = [services[int(i)] for i in run.rng.permutation(count)]
        pods = rollout_waves.make_pods(
            run, cls_name, services, f"check{cls_name}",
            selector={POOL_KEY: "ballast"},
        )
        names = [p.metadata.name for p in pods]
        compiles = compile_events()
        started = run.now()
        run.create(pods, threads=int(run.mix["params"].get("creators", 1)))
        run.wait_bound(names, float(spec["timeout_s"]))
        run.sched.wait_for_inflight_binds(timeout=30)
        print(f"check wave: {count} pods, half of the fresh service "
              f"svc-{fresh} and half of the largest, svc-{largest}, took "
              f"{run.now() - started:.2f}s, compile events "
              f"{compile_events() - compiles}", flush=True)
        after = run.snapshot()
        run.snapshots.pop()  # not the window's: the replay has made its own
        make, arrivals, placed, left = wave_state(
            run, names, after, fresh + 1, eligible_nodes(run, None),
        )
        outside = services_reference.certify(make(), arrivals, placed)
        ok &= compare(
            f"check wave {cls_name}: pods of one fresh service and of the "
            "largest that no tie-break of the rule explains, replayed in "
            f"the order they were created ({count} pods, {count - left} bound, "
            "node selector to the ballast pool)",
            outside, int(spec["limit_pods"]),
        )
        if control:
            read_controls(make, arrivals, f"check wave {cls_name}",
                          int(spec["limit_pods"]))
    return bool(ok)
