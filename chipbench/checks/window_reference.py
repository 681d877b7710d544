"""The window's own placements against the plain reference."""

from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.check import (
    MIB, compare, node_index, nodes_before, wave_size,
)


def run(run, control: bool) -> bool:
    """``window_reference`` of ``check.py``'s text. A wave's pods all
    ask for the same, so a node's score depends on how many of them it holds and nothing
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
