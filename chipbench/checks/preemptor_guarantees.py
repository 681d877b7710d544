"""What preemption guarantees, counted from the client's side: the pods
that left and that the harness did not delete (``Run.evicted``) against
the preemptors, which are the pods of the class the mix's
``preemptors`` names (a resident pod may carry a priority too: the tier
pool's do, and they preempt nobody). Over the whole run, warm-up
included. The limits are the configuration's (``preemptor_guarantees``):
no victim of a priority equal to or higher than the preemptors'; no more
victims than ``victims_per_preemptor`` a preemptor; every preemptor
bound."""

from __future__ import annotations

from chipbench.check import compare


def run(run, control: bool) -> bool:
    spec = run.config["preemptor_guarantees"]
    classes = run.config["pod_classes"]
    cls = run.mix["params"]["preemptors"]["class"]
    mine = int(classes[cls]["priority"])
    preemptors = [n for n, c in run.created.items() if c == cls]
    victims = run.evicted()
    high = sum(
        1 for n in victims
        if int(classes[run.created[n]].get("priority", 0)) >= mine
    )
    allowed = len(preemptors) * int(spec["victims_per_preemptor"])
    unbound = sum(1 for n in preemptors if n not in run.watcher.bind_time)
    ok = compare(
        f"preemptors: victims of priority >= {mine} ({len(victims)} pods "
        f"left that the harness did not delete, {len(preemptors)} pods of "
        f"class {cls!r} created)", high,
        int(spec["limit_equal_or_higher_priority"]),
    )
    ok &= compare(
        f"preemptors: victims beyond {spec['victims_per_preemptor']} a "
        f"preemptor ({len(victims)} victims, {len(preemptors)} preemptors)",
        max(0, len(victims) - allowed), int(spec["limit_beyond_the_rule"]),
    )
    ok &= compare("preemptors: never bound", unbound,
                  int(spec["limit_unbound"]))
    return bool(ok)
