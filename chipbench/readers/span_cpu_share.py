"""Of the wall clock of the program's spans named ``args["span"]`` that
began inside the traced slice and carry the stat ``cpu_ms`` (what the
stage primitive read on its thread's own CPU clock), the share that
thread spent on the CPU: the sum of ``cpu_ms`` over the sum of the
spans' durations. The rest the thread waited, for a lock, a condition or
the GIL: this ratio cannot tell them apart. ``args["less"]`` names spans
that are waits by design (a condition wait is not a wait for the GIL):
where one lies inside a counted span on the same line, its duration is
taken out of the wall clock. A span is counted whole, also where it ends
after the slice does; one that began before the slice is not counted.
The host's CPU clock may tick coarsely (10 ms on the chip's host), so
one span's ``cpu_ms`` means nothing and only the sums do. A program
whose spans lack the stat has nothing to read."""

from chipbench import program_spans


def share(trace: dict, args: dict):
    spans = [
        sp for sp in program_spans.spans_in_slice(trace, args["span"])
        if "cpu_ms" in sp["stats"]
    ]
    wall_ns = sum(sp["end"] - sp["start"] for sp in spans)
    less = set(args.get("less", ()))
    for inner in trace["spans"] if less else ():
        if inner["name"] in less and any(
            sp["line"] == inner["line"] and sp["start"] <= inner["start"]
            and inner["end"] <= sp["end"] for sp in spans
        ):
            wall_ns -= inner["end"] - inner["start"]
    if wall_ns <= 0:
        return None
    return sum(float(sp["stats"]["cpu_ms"]) for sp in spans) * 1e6 / wall_ns


def read(sample: dict, args: dict):
    trace = program_spans.load(sample)
    if trace is None:
        return None
    return share(trace, args)
