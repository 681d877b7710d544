"""Proving runs on the chip: run ``python3 -m chipbench <args>`` (or,
where the arguments hold one of its own, ``python3 -m
chipbench.proving.run <args>``) once for each argument string, one
after the other (this parent never touches
JAX, so each child owns the chip), keep each run's whole output under
``chiprun_out/<tag>/`` and print what a reader needs from each: exit
code, wall seconds, the lines of the check, the result line.

    python3 chipbench/proving/runs.py <tag> "<args of run 1>" "<args of run 2>" ...

Not part of the benchmark's command; it is how PERF.md's readings were
taken (chiprun --chips 1 -- python3 chipbench/proving/runs.py ...).
"""

import json
import os
import shlex
import subprocess
import sys
import time


def main() -> int:
    tag, runs = sys.argv[1], sys.argv[2:]
    out_dir = os.path.join("chiprun_out", tag)
    os.makedirs(out_dir, exist_ok=True)
    summary = []
    for k, args in enumerate(runs, 1):
        words = shlex.split(args)
        proving = {"--override", "--control", "--keep-trace"} & set(words)
        module = "chipbench.proving.run" if proving else "chipbench"
        cmd = [sys.executable, "-m", module] + words
        t0 = time.time()
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=dict(os.environ, PYTHONHASHSEED="0"),
        )
        wall = time.time() - t0
        with open(os.path.join(out_dir, f"run{k}.txt"), "w") as f:
            f.write(proc.stdout)
        lines = proc.stdout.strip().splitlines()
        print(f"== run {k}: {args}\n   rc={proc.returncode} wall={wall:.1f}s")
        for line in lines:
            if line.startswith(("compare", "control", "set-up", "window",
                                "arrivals", "chipbench:", "device:", "gc in", "waves", "slow wave", "counters", "programs compiled",
                                "check wave", "preempt", "refill")):
                print("   " + line[:400])
        last = lines[-1] if lines else ""
        try:
            result = json.loads(last)
        except ValueError:
            result = None
            print("   NO RESULT LINE; tail:\n   " + "\n   ".join(lines[-15:])[:3000])
        else:
            print("   " + last[:6000])
        summary.append({"args": args, "rc": proc.returncode,
                        "wall_s": wall, "result": result})
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
