"""What preemption guarantees, counted from the client's side: the pods
that left and that the harness did not delete (``Run.evicted``) against
the pods of a class with a ``priority`` that the harness created
(preemptors), over the whole run. The limits are the configuration's
(``evictions``): no victim of a priority equal to or higher than the
lowest preemptor's; no more victims than ``victims_per_preemptor`` a
preemptor; every preemptor bound."""

from __future__ import annotations

from chipbench.check import compare


def run(run, control: bool) -> bool:
    spec = run.config["evictions"]
    classes = run.config["pod_classes"]

    def priority(name: str) -> int:
        return int(classes[run.created[name]].get("priority", 0))

    preemptors = [n for n in run.created if priority(n) > 0]
    lowest = min((priority(n) for n in preemptors), default=0)
    victims = run.evicted()
    high = sum(1 for n in victims if priority(n) >= lowest)
    allowed = len(preemptors) * int(spec["victims_per_preemptor"])
    unbound = sum(1 for n in preemptors if n not in run.watcher.bind_time)
    ok = compare(
        f"evictions: victims of priority >= {lowest} ({len(victims)} pods "
        f"left that the harness did not delete, {len(preemptors)} "
        "preemptors created)", high,
        int(spec["limit_equal_or_higher_priority"]),
    )
    ok &= compare(
        f"evictions: victims beyond {spec['victims_per_preemptor']} a "
        f"preemptor ({len(victims)} victims, {len(preemptors)} preemptors)",
        max(0, len(victims) - allowed), int(spec["limit_beyond_the_rule"]),
    )
    ok &= compare("evictions: preemptors never bound", unbound,
                  int(spec["limit_unbound"]))
    return bool(ok)
