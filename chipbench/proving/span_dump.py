"""The program's spans of a kept trace in the order they began, one a
line with their stats: which batch uploaded the carry whole, scattered
rows or reused it, between which preemption waves. Reads the
``.xplane.pb`` files under the directories given (``--keep-trace``'s).

    python3 chipbench/proving/span_dump.py chiprun_out/<tag>/trace
"""

import glob
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import program_spans  # noqa: E402

KEEP = ("sched/dispatch", "sched/solve_dispatch", "sched/preempt_wave",
        "sched/victim_wait", "sched/preempt_requeue")


def main() -> int:
    for root in sys.argv[1:]:
        for path in sorted(glob.glob(os.path.join(root, "*.xplane.pb"))):
            trace = program_spans.read_trace(path)
            w0 = trace["window"][0]
            print(f"{path}: slice of {(trace['window'][1] - w0) / 1e9:.3f}s")
            for sp in sorted(trace["spans"], key=lambda sp: sp["start"]):
                if sp["name"].startswith(KEEP):
                    print(f"{(sp['start'] - w0) / 1e6:10.2f} ms "
                          f"{(sp['end'] - sp['start']) / 1e6:9.2f} ms  "
                          f"thread {sp['line'][1]:<3} {sp['name'][6:]:<26} "
                          f"{dict(sorted(sp['stats'].items()))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
