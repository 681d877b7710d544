"""Open loop: pods arrive on a schedule fixed before the window, whatever
the scheduler does, and each is timed from the instant it was due.

The schedule is a Poisson process at ``rate`` conditioned on its count:
``round(rate * seconds)`` exponential gaps drawn from the mix's own
``gap_seed``, scaled to fill the window, and put in another order by the
run's seed. So every seed offers the same gaps, the same count and the
same span, and only the order differs. (Copied in idea from
``kubernetes_tpu/streaming/arrivals.py``'s ``poisson_trace``; its rate
ladder and its ``created_ts`` stamped at the send are not.)
"""

from __future__ import annotations

import time

import numpy as np


def offsets(rate: float, seconds: float, gap_seed: int, rng) -> np.ndarray:
    count = int(round(rate * seconds))
    gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate, count)
    gaps *= seconds * count / ((count + 1) * gaps.sum())
    return np.cumsum(gaps[rng.permutation(count)])


def _offer(run, params: dict, pods: list, offs: np.ndarray) -> float:
    """Issue each pod's create at the first tick at or after its due
    instant. Returns the instant the schedule started."""
    tick = params["tick_ms"] / 1e3
    start = run.now()
    i, n, k = 0, len(pods), 0
    with run.phase("tick_wait"):
        while i < n:
            j = int(np.searchsorted(offs, run.now() - start, side="right"))
            if j > i:
                with run.phase("arrive"):
                    run.create(pods[i:j], due=start + offs[i:j])
                i = j
            k += 1
            pause = start + k * tick - run.now()
            if pause > 0:
                time.sleep(pause)
            else:
                k = int((run.now() - start) / tick)
    return start


def warmup(run, params: dict) -> None:
    """``warmup_rounds`` rounds of ``warmup_seconds`` of arrivals, each
    deleted afterwards. Two rounds, because the first batch after a
    delete re-uploads the node state through a program of its own, and
    the window's first batch is such a batch."""
    for _ in range(params["warmup_rounds"]):
        offs = offsets(
            params["rate"], params["warmup_seconds"], params["gap_seed"],
            run.rng,
        )
        pods = run.make_pods(params["class"], len(offs), "warm")
        names = [p.metadata.name for p in pods]
        _offer(run, params, pods, offs)
        run.wait_bound(names, params["deadline_s"])
        run.delete(names, params["delete_timeout_s"])


def prepare(run, params: dict, seconds: float):
    offs = offsets(params["rate"], seconds, params["gap_seed"], run.rng)
    return run.make_pods(params["class"], len(offs), "arrive"), offs


def window(run, params: dict, prepared, seconds: float) -> None:
    pods, offs = prepared
    names = [p.metadata.name for p in pods]
    start = _offer(run, params, pods, offs)
    bound = sum(1 for n in names if n in run.watcher.bind_time)
    print(f"arrivals: {len(names)} offered in {run.now() - start:.3f}s at "
          f"{params['rate']} pods/s, backlog at the close "
          f"{len(names) - bound} pods", flush=True)
    with run.phase("drain_tail"):
        left = params["deadline_s"] - (run.now() - start - float(offs[-1]))
        run.wait_bound(names, left)
