"""What the score packer kept over a traced slice: the
``sched/pack.families`` spans of each kept trace (``--keep-trace`` of
``chipbench.proving.run``), their ``score_node_rows`` (node-side rows
asked for: the zones and each image list) beside
``score_node_rows_reused`` (those served from the store), and for each
stage of a batch's way the spans of the slice, their mean length and
what they cover of the slice (``sched/pop_wait`` by what it waits for).
A program without the two stats (before PR 49) reads ``None``.

    python3 tools/score_rows_spans.py chiprun_out/<tag>/trace-<side>
"""

import glob
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import program_spans  # noqa: E402

TIMED = ("sched/pack.score", "sched/pack.score.images",
         "sched/pack.score.zones", "sched/pack.families", "sched/pack",
         "sched/pop_wait", "sched/pop", "sched/dispatch",
         "sched/solve_dispatch", "sched/solve_wait", "sched/commit",
         "sched/bind", "sched/ingest", "sched/events", "sched/gc")


def main() -> int:
    for root in sys.argv[1:]:
        for path in sorted(glob.glob(os.path.join(root, "*.xplane.pb"))):
            trace = program_spans.read_trace(path)
            families = program_spans.spans_in_slice(
                trace, "sched/pack.families"
            )
            out = {"trace": path, "families_spans": len(families)}
            for stat in ("score_node_rows", "score_node_rows_reused",
                         "score_sigs", "score_live"):
                held = [sp["stats"][stat] for sp in families
                        if stat in sp["stats"]]
                out[stat] = sum(map(float, held)) if held else None
            if out["score_node_rows"]:
                out["reused_share"] = (
                    out["score_node_rows_reused"] / out["score_node_rows"]
                )
            print(out, flush=True)
            slice_ns = trace["window"][1] - trace["window"][0]
            for name in TIMED:
                groups = {}
                for sp in program_spans.spans_in_slice(trace, name):
                    key = name[6:]
                    if "waits_for" in sp["stats"]:
                        key += "/" + str(sp["stats"]["waits_for"])
                    groups.setdefault(key, []).append(sp["end"] - sp["start"])
                for key, ns in groups.items():
                    print(f"   {key:<24} {len(ns):5d} spans, mean "
                          f"{sum(ns) / len(ns) / 1e6:8.3f} ms, "
                          f"{100.0 * sum(ns) / slice_ns:6.2f} % of the slice",
                          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
