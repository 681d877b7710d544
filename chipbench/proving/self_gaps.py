"""Where a parent span's own time lies: for ``sched/pack``,
``sched/dispatch`` and ``sched/gang_fixup`` of a kept trace, what
``readers/span_self_ms_per_span`` calls their own time, split by the
span directly inside the parent that ends before each uncovered stretch
and the one that begins after it (``<start>`` and ``<end>``: the
parent's own), in ms a dispatch, largest first; before them every
``sched/`` span's wall clock a dispatch and, of it, the ``cpu_ms`` of
the spans that carry one (a stage without totals reads 0: not clocked).
A stretch that holds 1 ms a batch is where the next child span goes. Reads the ``.xplane.pb`` files
under the directories given (``--keep-trace``'s).

    python3 chipbench/proving/self_gaps.py chiprun_out/<tag>/trace
"""

import collections
import glob
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import program_spans  # noqa: E402
from chipbench.readers import span_self_ms_per_span as self_time  # noqa: E402

PARENTS = ("sched/pack", "sched/dispatch", "sched/gang_fixup")


def stretches(parent: dict, line_spans: list) -> dict:
    """(child before, child after) -> ns of ``parent`` that no span of
    its line inside it covers."""
    inside = sorted(
        self_time.inside(parent, line_spans),
        key=lambda sp: (sp["start"], -sp["end"]),
    )
    out: dict = collections.Counter()
    before, cursor = "<start>", parent["start"]
    for sp in inside:
        if sp["end"] <= cursor:
            continue  # inside the child before it
        if sp["start"] > cursor:
            out[before, sp["name"]] += sp["start"] - cursor
        before, cursor = sp["name"], sp["end"]
    if parent["end"] > cursor:
        out[before, "<end>"] += parent["end"] - cursor
    return out


def main() -> int:
    for root in sys.argv[1:]:
        for path in sorted(glob.glob(os.path.join(root, "*.xplane.pb"))):
            trace = program_spans.read_trace(path)
            w0, w1 = trace["window"]
            by_line = self_time.by_line(trace)
            wall: dict = collections.Counter()
            cpu: dict = collections.Counter()
            count: dict = collections.Counter()
            for spans in by_line.values():
                for sp in spans:
                    if w0 <= sp["start"] < w1:
                        wall[sp["name"]] += sp["end"] - sp["start"]
                        cpu[sp["name"]] += sp["stats"].get("cpu_ms", 0.0)
                        count[sp["name"]] += 1
            times = max(1, count["sched/dispatch"])
            print(f"{path}: slice of {(w1 - w0) / 1e9:.3f}s, "
                  f"{count['sched/dispatch']} dispatches")
            for name, ns in sorted(wall.items(), key=lambda kv: -kv[1]):
                print(f"  {name[6:]:<28} {ns / 1e6 / times:10.3f} ms a "
                      f"dispatch, {cpu[name] / times:9.3f} of them its "
                      f"thread's CPU, {count[name]} spans")
            for name in PARENTS:
                own: dict = collections.Counter()
                for parent in program_spans.spans_in_slice(trace, name):
                    own.update(stretches(parent, by_line[parent["line"]]))
                if not own:
                    continue
                print(f"{name}: own time {sum(own.values()) / 1e6 / times:.3f}"
                      f" ms a dispatch, between")
                for (before, after), ns in sorted(
                    own.items(), key=lambda kv: -kv[1]
                )[:12]:
                    print(f"  {before:<30} -> {after:<30} "
                          f"{ns / 1e6 / times:9.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
