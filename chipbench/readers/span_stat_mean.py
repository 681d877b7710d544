"""The mean of the stat ``args["stat"]`` over the program's spans named
``args["span"]`` that began inside the traced slice and carry it: a
batch's own count (the rows sent to bring its resident state up to
date). A program whose spans lack the stat has nothing to read."""

from chipbench import program_spans


def mean(trace: dict, args: dict):
    values = [
        float(sp["stats"][args["stat"]])
        for sp in program_spans.spans_in_slice(trace, args["span"])
        if args["stat"] in sp["stats"]
    ]
    return sum(values) / len(values) if values else None


def read(sample: dict, args: dict):
    trace = program_spans.load(sample)
    if trace is None:
        return None
    return mean(trace, args)
