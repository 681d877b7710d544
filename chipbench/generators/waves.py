"""Closed waves: create a wave as fast as the API takes it, wait until
every pod is bound, delete it, wait until the scheduler has seen it gone,
repeat. Every wave of a mix is the same multiset of pods; the seed only
shuffles their order."""

from __future__ import annotations

import gc


def _build(run, params: dict) -> list:
    pods = []
    for part in params["wave"]:
        for a in range(part["apps"]):
            pods += run.make_pods(
                part["class"], part["pods_per_app"], f"{part['class']}{a}"
            )
    if params.get("shuffle"):
        order = run.rng.permutation(len(pods))
        pods = [pods[int(k)] for k in order]
    return pods


def one_wave(run, params: dict) -> None:
    with run.phase("wave_build"):
        pods = _build(run, params)
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
        # the apiserver and the client live in this process, so the
        # garbage of a wave's created and deleted pods is the harness's:
        # it is collected here, in the gap (which the window's rate
        # counts), and not by the scheduler's own collector in the
        # middle of a later drain
        gc.collect()


def warmup(run, params: dict) -> None:
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
