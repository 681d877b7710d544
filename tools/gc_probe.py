"""One proving run of a cell with every collection of the process on
record, from outside the benchmark's files: a ``gc.callbacks`` hook (who
collected, which generation, how long, what it freed), ``gc.unfreeze``
logged (a walk of the whole heap), the harness's own phases laid beside
them, the ``sched/gc`` spans of a kept trace and the process's peak RSS.
Run from the root of a checkout; everything after the output file goes
to ``chipbench.proving.run`` as it stands:

    PYTHONHASHSEED=0 python3 <repo>/tools/gc_probe.py <out.json> \\
        --workload gpu-binpack-5000.binpack-burst-6k --seed 1 \\
        --seconds 51 --trace 1 --keep-trace <dir>

The scheduler's guard collects on the thread ``scheduler``, the
harness's generators on ``MainThread``. The result line stays the last
line of stdout; the summary goes to stderr and the records to the file.
"""

import gc
import glob
import json
import os
import resource
import statistics
import sys
import threading
import time


def install():
    collections, thaws, began, runs = [], [], {}, []

    def on_gc(phase: str, info: dict) -> None:
        ident = threading.get_ident()
        if phase == "start":
            began[ident] = time.perf_counter()
        elif ident in began:
            t0 = began.pop(ident)
            collections.append({
                "t": t0, "s": time.perf_counter() - t0,
                "generation": info["generation"],
                "collected": info["collected"],
                "thread": threading.current_thread().name,
            })

    gc.callbacks.append(on_gc)
    unfreeze = gc.unfreeze

    def logged_unfreeze() -> None:
        thaws.append((time.perf_counter(), threading.current_thread().name))
        unfreeze()

    gc.unfreeze = logged_unfreeze

    from chipbench import harness

    init = harness.Run.__init__

    def logged_init(self, *args, **kwargs) -> None:
        runs.append(self)
        init(self, *args, **kwargs)

    harness.Run.__init__ = logged_init
    return collections, thaws, runs


def phase_of(phases, t: float) -> str:
    """The innermost of the harness's phases open at ``t``."""
    open_ = [(t1 - t0, name) for name, t0, t1 in phases if t0 <= t < t1]
    return min(open_)[1] if open_ else "outside"


def gc_spans(keep_trace: str) -> list:
    from chipbench import program_spans

    spans = []
    for path in sorted(glob.glob(os.path.join(keep_trace, "*.xplane.pb"))):
        trace = program_spans.read_trace(path)
        w0 = trace["window"][0]
        for sp in sorted(trace["spans"], key=lambda sp: sp["start"]):
            if sp["name"] == "sched/gc":
                spans.append({
                    "at_ms": (sp["start"] - w0) / 1e6,
                    "ms": (sp["end"] - sp["start"]) / 1e6,
                    **sp["stats"],
                })
    return spans


def say(text: str) -> None:
    print(text, file=sys.stderr, flush=True)


def main() -> int:
    out, sys.argv[1:] = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, os.getcwd())
    collections, thaws, runs = install()
    from chipbench.proving import run as proving_run

    rc = proving_run.main()
    record = {
        "argv": sys.argv[1:], "rc": rc,
        "maxrss_kib": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
    }
    say(f"gc_probe: rc {rc}, peak RSS {record['maxrss_kib']} KiB")
    if runs and runs[0].window_end:
        run = runs[0]
        w0, w1 = run.window_start, run.window_end
        phases = [p for p in run.phases if p[2] > w0 and p[1] < w1]
        waves = sorted(t0 for name, t0, _ in phases if name == "wave_create")
        inside = [c for c in collections if w0 <= c["t"] < w1]
        for c in inside:
            c["phase"] = phase_of(phases, c["t"])
            c["wave"] = sum(1 for t in waves if t <= c["t"])
        record.update(
            window_s=w1 - w0, waves=len(waves), collections=[
                {**c, "t": c["t"] - w0} for c in inside
            ],
            whole_walks=[(t - w0, who) for t, who in thaws if w0 <= t < w1],
            phase_seconds={
                name: sum(t1 - t0 for n, t0, t1 in phases if n == name)
                for name in sorted({p[0] for p in phases})
            },
        )
        say(f"gc_probe: window {w1 - w0:.2f} s, {len(waves)} waves, "
            f"{len(record['whole_walks'])} whole walks "
            f"{[(round(t, 2), who) for t, who in record['whole_walks']]}; "
            f"phase seconds "
            f"{ {k: round(v, 2) for k, v in record['phase_seconds'].items()} }")
        keys = sorted({(c["thread"], c["generation"]) for c in inside})
        for thread, generation in keys:
            mine = [c for c in inside
                    if (c["thread"], c["generation"]) == (thread, generation)]
            ms = [c["s"] * 1e3 for c in mine]
            third = max(1, len(ms) // 3)
            where = {}
            for c in mine:
                where[c["phase"]] = where.get(c["phase"], 0) + 1
            say(f"gc_probe: {thread} gen {generation}: {len(ms)} "
                f"({len(ms) / max(1, len(waves)):.2f} a wave), "
                f"{sum(ms):.1f} ms in all = "
                f"{sum(ms) / (w1 - w0):.2f} ms/s, median "
                f"{statistics.median(ms):.2f}, max {max(ms):.2f}, first "
                f"third {statistics.fmean(ms[:third]):.2f} -> last third "
                f"{statistics.fmean(ms[-third:]):.2f} ms each, freed "
                f"{sum(c['collected'] for c in mine)}, in {where}")
    keep = ""
    if "--keep-trace" in sys.argv:
        keep = sys.argv[sys.argv.index("--keep-trace") + 1]
    if keep and rc == 0:
        record["gc_spans"] = spans = gc_spans(keep)
        say(f"gc_probe: {len(spans)} sched/gc spans in the slice: " + ", ".join(
            f"{sp['at_ms']:.0f}:{sp['ms']:.1f}ms/g{sp.get('generation')}"
            f"/w{sp.get('whole', '-')}" for sp in spans
        ))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
