"""Closed waves as ``waves.py`` makes them (create as fast as the API
takes it, drain, snapshot, delete), with the two things that generator
cannot be told: a part of a wave carries a node selector, and the
residents that set-up placed lose a seeded share, once, before warm-up.

A part of the mix's ``wave`` names a pod class and the ``zones`` its
pods go to: one app a zone, ``pods_per_app`` pods each, every pod with
the node selector ``topology.kubernetes.io/zone: zone-<z>`` (a zone
stands for an accelerator pool). ``resident_delete_share`` of the
configuration's init pods, drawn by the run's seed, are deleted before
the first warm-up wave: jobs that ended, which leaves holes on nodes the
rule had packed full.

What the comparisons read: ``run.binpack`` keeps ``residents`` (the
apiserver's placements of the init pods as set-up left them) and
``pod_zone`` (pod name -> the zone its selector names); a wave's record
keeps ``parts``, one ``(class, zone, names)`` a pool.
"""

from __future__ import annotations

import gc

from chipbench.checks.binpack_guarantees import node_state, request
from chipbench.harness import ZONE_KEY, BenchError


def _build(run, params: dict) -> tuple:
    parts, pods = [], []
    pod_zone = run.binpack["pod_zone"]
    for part in params["wave"]:
        for zone in part["zones"]:
            made = run.make_pods(
                part["class"], part["pods_per_app"],
                f"{part['class']}z{zone}",
                selector={ZONE_KEY: f"zone-{zone}"},
            )
            names = [p.metadata.name for p in made]
            pod_zone.update(dict.fromkeys(names, int(zone)))
            parts.append((part["class"], int(zone), names))
            pods += made
    if params.get("shuffle"):
        order = run.rng.permutation(len(pods))
        pods = [pods[int(k)] for k in order]
    return parts, pods


def one_wave(run, params: dict) -> None:
    with run.phase("wave_build"):
        parts, pods = _build(run, params)
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
    wave["parts"] = parts
    with run.phase("gap_delete"):
        wave["snapshot"] = run.snapshot()
        run.delete(names, params["delete_timeout_s"])
        gc.collect()  # as waves.py: the harness's own garbage, in the gap


def thin_residents(run, params: dict) -> None:
    """Keep what set-up placed, then delete the seeded share of it."""
    init = run.config["cluster"]["init_pods"]
    placed = {
        p.metadata.name: p.spec.node_name
        for p in run.client.list_pods()[0] if p.spec.node_name
    }
    residents = sorted(
        (n for n in placed if run.created.get(n) == init["class"]
         and n not in run.prebound),
        key=lambda n: int(n.rsplit("-", 1)[1]),
    )
    if len(residents) != int(init["count"]):
        raise BenchError(
            f"{len(residents)} residents are bound, set-up created "
            f"{init['count']}"
        )
    run.binpack = {
        "residents": {n: placed[n] for n in residents}, "pod_zone": {},
    }
    # the mix is sized for residents packed as the profile's rule packs
    # them (whole nodes stay free for the large pods): a scheduler that
    # spread them cannot run this deployment, and its waves would only
    # wait out their deadlines
    cap, pod = node_state(run, {}).cap[0], request(run, init["class"])
    a_node = int((cap[pod > 0] // pod[pod > 0]).min())
    occupied = len(set(placed[n] for n in residents))
    if occupied != -(-len(residents) // a_node):
        raise BenchError(
            f"set-up placed the {len(residents)} residents on {occupied} "
            f"nodes; the profile's score rule (plugins.score of the "
            f"configuration's wire) packs them {a_node} a node onto "
            f"{-(-len(residents) // a_node)}: this scheduler does not score "
            "by its profile, and cannot run this deployment"
        )
    gone = int(round(float(params["resident_delete_share"]) * len(residents)))
    picked = run.rng.choice(len(residents), size=gone, replace=False)
    run.delete([residents[int(k)] for k in picked],
               params["delete_timeout_s"])
    gc.collect()
    print(f"binpack waves: {len(residents)} residents on {occupied} nodes "
          f"as set-up placed them, {gone} deleted by the seed", flush=True)


def warmup(run, params: dict) -> None:
    thin_residents(run, params)
    for _ in range(params["warmup_waves"]):
        one_wave(run, params)


def prepare(run, params: dict, seconds: float):
    return None


def window(run, params: dict, prepared, seconds: float) -> None:
    """Waves until the window closes; the wave in flight at the end is
    finished and counted."""
    start = run.now()
    while run.now() - start < seconds:
        one_wave(run, params)
