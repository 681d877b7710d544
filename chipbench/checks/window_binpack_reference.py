"""The window's placements against the plain reference for bin-packing
(``chipbench/binpack_reference.py``): for every wave of the window, from
the node state the apiserver showed before it (the snapshot taken once
the wave had drained, less the wave's own pods), and for every pool of
the wave (a class's pods under one node selector: identical pods on
nodes no other pod of the wave reaches), the multiset of pods that each
group of nodes alike before the wave received must be the one the
profile's rule gives, which the module's lemma makes exact whatever the
order of arrival, the batching and the tie-break. The number compared is
the worst wave's, in pods (half the distance between the multisets,
summed over pools and groups); the limit is the configuration's
(``window_binpack_reference``). The residents are held to it once, as
set-up placed them from an empty cluster, on a line of their own.

``control``: beside the first wave, the reference scheduling it under
the default provider's rule (LeastAllocated + BalancedAllocation: a
scheduler deaf to the profile's score plugins) and in float32 and
bfloat16 under the profile's.
"""

from __future__ import annotations

import numpy as np

from chipbench import binpack_reference
from chipbench.check import compare
from chipbench.checks.binpack_guarantees import node_state, request


def received(run, snapshot: dict, names) -> np.ndarray:
    """[N] the pods of ``names`` each node holds in ``snapshot``."""
    got = np.zeros(len(run.node_rows), dtype=np.int64)
    for name in names:
        if name in snapshot:
            got[run.node_rows[snapshot[name]]] += 1
    return got


def wave_pools(run, wave: dict):
    """(nodes before the wave, then per pool: the pod's request, its
    names, the [N] bool of its nodes)."""
    zones = run.config["cluster"]["zones"]
    zone = np.arange(len(run.node_rows), dtype=np.int64) % zones
    mine = set(wave["names"])
    nodes = node_state(run, {
        name: node for name, node in wave["snapshot"].items()
        if name not in mine
    })
    return nodes, [
        (request(run, cls), names, zone == z)
        for cls, z, names in wave["parts"]
    ]


def run(run, control: bool) -> bool:
    spec = run.config["window_binpack_reference"]
    ok = True

    # the residents, as set-up placed them from an empty cluster
    residents = run.binpack["residents"]
    empty = node_state(run, {})
    pod = request(run, run.config["cluster"]["init_pods"]["class"])
    if not binpack_reference.exact_for(empty, pod):
        raise ValueError("the residents' comparison is not exact here")
    ok &= compare(
        f"set-up against the reference: of the {len(residents)} residents, "
        "pods outside what the profile's score rule gives the groups of "
        "nodes alike before them (an empty cluster: one group)",
        binpack_reference.unexplained(
            empty, pod, len(residents),
            received(run, residents, residents),
        ), int(spec["limit_pods"]),
    )

    waves = [w for w in run.waves if w["in_window"] and "snapshot" in w]
    worst = total = seen = pools = 0
    for k, wave in enumerate(waves):
        nodes, parts = wave_pools(run, wave)
        found = 0
        for pod, names, eligible in parts:
            if not binpack_reference.exact_for(nodes, pod, eligible):
                raise ValueError(
                    "a pool whose nodes tie across groups, or whose score "
                    "does not rise with every pod: the comparison is not "
                    "exact there (binpack_reference's lemma)"
                )
            found += binpack_reference.unexplained(
                nodes, pod, len(names),
                received(run, wave["snapshot"], names), eligible,
            )
            pools += 1
        worst = max(worst, found)
        total += found
        seen += len(wave["names"])
        if found:
            print(f"wave {k}: {found} pods outside the reference's group "
                  "multisets", flush=True)
        if control and k == 0:
            for rule, precision in (("default", "exact"), ("most", "float32"),
                                    ("most", "bfloat16")):
                other = sum(
                    binpack_reference.unexplained(
                        nodes, pod, len(names), binpack_reference.schedule(
                            nodes, pod, len(names), eligible, rule, precision,
                        )[0], eligible,
                    ) for pod, names, eligible in parts
                )
                print(f"control window: the reference scheduling the first "
                      f"wave's {len(wave['names'])} pods under the rule "
                      f"{rule!r} in {precision} leaves {other} outside the "
                      f"group multisets (limit {spec['limit_pods']})",
                      flush=True)
    ok &= compare(
        "window against the reference: pods of the worst wave outside what "
        "the profile's score rule gives the groups of nodes alike before it "
        f"({len(waves)} wave(s), {pools} pools, {seen} pods, {total} outside "
        "in all)", worst, int(spec["limit_pods"]),
    )
    return bool(ok)
