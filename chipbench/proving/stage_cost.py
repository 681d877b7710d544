"""What the stage primitive costs on this host with no profiler session,
and what its clocks cost: us a call of ``time.perf_counter`` and of
``time.thread_time`` (a system call on some hosts), us a stage with and
without totals. Touches no device; run it in a checkout of each side:

    python3 -m chipbench.proving.stage_cost
"""

import json
import time


def per_call(fn, n: int = 200_000) -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def main() -> None:
    from kubernetes_tpu.utils import flightrecorder as fr

    totals = fr.StageTotals()

    def with_totals():
        with fr.stage("ingest", totals=totals, kind="Pod", events=1):
            pass

    def without_totals():
        with fr.stage("commit.gather", batch=3):
            pass

    print("stage cost: " + json.dumps({
        "perf_counter_us": round(per_call(time.perf_counter), 4),
        "thread_time_us": round(per_call(time.thread_time), 4),
        "stage_with_totals_us": round(per_call(with_totals), 4),
        "stage_without_totals_us": round(per_call(without_totals), 4),
        "thread_time_resolution": time.get_clock_info("thread_time").resolution,
    }), flush=True)


if __name__ == "__main__":
    main()
