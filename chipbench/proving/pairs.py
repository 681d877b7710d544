"""Pairs of the parent's archive against the committed files, on the
chip: ``.checkout/parent`` (``git archive <parent>``) and
``.checkout/change`` (``git archive $(git write-tree)``), both unpacked
before the call since the chip's machine has no git. One seed a pair,
the side that runs first alternating from pair to pair; a spec that ends
in ``trace`` is one traced run of the change alone. Keeps every run's
output under ``chiprun_out/<tag>/`` and prints, for each cell, each
side's runs and medians, whether the two sides' ``compare`` lines differ
in numbers only, and the first run against the second run of each pair
whatever the side. This parent never touches JAX.

``<seconds>`` may carry ``--rehearsal`` behind the number (a CPU try).

    python3 chipbench/proving/pairs.py <tag> <seconds> \\
        "basic-5000.burst-10k 6 2147493101" "spread-anti-5000.burst-5k 1 7401 trace"
"""

import json
import os
import re
import statistics
import subprocess
import sys
import time

SIDES = ("parent", "change")


def one_run(side: str, cell: str, seed: int, seconds: str, trace: int,
            path: str) -> dict:
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell, "--seed",
         str(seed), "--trace", str(trace), "--seconds", *seconds.split()],
        cwd=os.path.join(".checkout", side), text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    with open(path, "w") as f:
        f.write(proc.stdout)
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (ValueError, IndexError):
        result = None
        print(f"   NO RESULT LINE from {side} (rc {proc.returncode}):\n   "
              + "\n   ".join(lines[-12:])[:2500], flush=True)
    return {
        "side": side, "seed": seed, "rc": proc.returncode,
        "wall_s": time.time() - t0, "result": result,
        "compare": [re.sub(r"\d+", "#", l) for l in lines
                    if l.startswith("compare ")],
        "notes": [l for l in lines if l.startswith(
            ("set-up", "window:", "slow wave", "chipbench:"))],
    }


def values(run: dict) -> dict:
    return {k: v["value"] for k, v in run["result"]["metrics"].items()}


def main() -> int:
    tag, seconds, specs = sys.argv[1], sys.argv[2], sys.argv[3:]
    out = os.path.join("chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    summary = {}
    for spec in specs:
        cell, pairs, seed, *rest = spec.split()
        pairs, seed = int(pairs), int(seed)
        runs = []
        if rest == ["trace"]:
            for k in range(pairs):
                runs.append([one_run("change", cell, seed + k, seconds, 1,
                                     f"{out}/{cell}.trace{k}.txt")])
                r = runs[-1][0]
                print(f"== {cell} traced, seed {r['seed']}: rc {r['rc']}, "
                      f"{r['wall_s']:.0f}s wall\n   "
                      + json.dumps(r["result"])[:6000], flush=True)
            summary[cell + " trace"] = runs
            continue
        for k in range(pairs):
            order = SIDES if k % 2 == 0 else SIDES[::-1]
            pair = [one_run(side, cell, seed + k, seconds, 0,
                            f"{out}/{cell}.{k}.{side}.txt") for side in order]
            runs.append(pair)
            for r in pair:
                ok = r["result"] and (r["result"]["correct"],
                                      r["result"]["failed"])
                print(f"== {cell} pair {k} seed {r['seed']} {r['side']}: "
                      f"rc {r['rc']}, {r['wall_s']:.0f}s wall, (correct, "
                      f"failed) {ok}, "
                      + (json.dumps(values(r)) if r["result"] else ""),
                      flush=True)
                for note in r["notes"]:
                    print("   " + note[:300], flush=True)
            same = pair[0]["compare"] == pair[1]["compare"]
            print(f"   compare lines of the two sides differ in numbers "
                  f"only: {same} ({len(pair[0]['compare'])} lines)",
                  flush=True)
        summary[cell] = runs
        good = [p for p in runs if all(r["result"] for r in p)]
        if not good:
            continue
        print(f"-- {cell}: medians over {len(good)} pairs", flush=True)
        for metric in values(good[0][0]):
            by_side = {s: [values(r)[metric] for p in good for r in p
                           if r["side"] == s] for s in SIDES}
            by_turn = [[values(p[i])[metric] for p in good] for i in (0, 1)]
            print(f"   {metric}: parent {statistics.median(by_side['parent']):.4f}"
                  f" change {statistics.median(by_side['change']):.4f} | first "
                  f"run {statistics.median(by_turn[0]):.4f} second run "
                  f"{statistics.median(by_turn[1]):.4f}; second > first in "
                  f"{sum(b > a for a, b in zip(*by_turn))} of {len(good)}",
                  flush=True)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
