"""The largest value of the stat ``args["stat"]`` over the program's
spans named ``args["span"]`` that began inside the traced slice."""

from chipbench import program_spans


def largest(trace: dict, args: dict):
    values = [
        float(sp["stats"][args["stat"]])
        for sp in program_spans.spans_in_slice(trace, args["span"])
        if args["stat"] in sp["stats"]
    ]
    return max(values) if values else None


def read(sample: dict, args: dict):
    trace = program_spans.load(sample)
    if trace is None:
        return None
    return largest(trace, args)
