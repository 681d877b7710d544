"""The rolling cell at a middle size on whatever device JAX has (a CPU
will do): 600 nodes, ``maxBatch`` 512, 400 pods a second, the mix's own
roll. Large enough that a warm-up round's delete changes more rows than
the carry's scatter takes, so the whole-state upload runs, which the
rehearsal's 48 nodes never reach; never a measurement.

    JAX_PLATFORMS=cpu python3 chipbench/proving/mid41.py <rejoin_after_s> <seed> <seconds>

PR 41 used it to reproduce, without the chip, what the chip's run with
no interval between ``remove`` and ``join`` showed on its first tree
(PERF.md section 6): a re-joined node ends the window with more pods
than the rule allows ((b) of ``window_rolling_reference`` FAILED at 0
and, one run in three, at 0.25; since the mend of ``_dispatch_solve`` it
reads 0 at both). The mix's own roll has no interval: pass 0 for it."""

import copy
import json
import sys
import time

CELL = "rolling-upgrade-5000.arrivals-roll-4"


def main() -> int:
    from chipbench import harness

    gap, seed, seconds = float(sys.argv[1]), int(sys.argv[2]), float(sys.argv[3])
    cell = copy.deepcopy(harness.load_cell(harness.ROOT, CELL, rehearsal=True))
    full = harness.load_cell(harness.ROOT, CELL, rehearsal=False)
    config = cell["config"]
    config["cluster"].update(
        nodes=600, zones=10, init_pods={"count": 600, "class": "plain"},
        ballast={"per_zone": 9, "grid": 3,
                 "classes": ["ballast_cpu", "ballast_mem"]},
    )
    config["wire"]["tpuSolver"]["maxBatch"] = 512
    config["rolling_check"] = {"ready_before_close_s": 1.0}
    params = copy.deepcopy(full["mix"]["params"])
    params.update(rate=400)
    params["roll"].update(rejoin_after_s=gap, quiet_last_s=2.5)
    cell["mix"]["params"] = params
    line = harness.run_cell(
        cell, seed, seconds, False, time.perf_counter(),
        harness.find_device(1, rehearsal=True), rehearsal=True,
    )
    print(json.dumps({k: line[k] for k in ("correct", "attempted", "failed")}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parents[2]))
    sys.exit(main())
