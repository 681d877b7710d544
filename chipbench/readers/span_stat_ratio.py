"""A stat of the program's spans over another: the sum of
``args["numerator"]`` over the sum of ``args["denominator"]``, over the
spans named ``args["span"]`` that began inside the traced slice (the
queue wait a pod: ``queue_wait_sum_ms`` over ``pods`` of the
``sched/dispatch`` spans)."""

from chipbench import program_spans


def ratio(trace: dict, args: dict):
    spans = program_spans.spans_in_slice(trace, args["span"])
    below = sum(float(sp["stats"].get(args["denominator"], 0)) for sp in spans)
    if below <= 0:
        return None
    above = sum(float(sp["stats"].get(args["numerator"], 0)) for sp in spans)
    return above / below


def read(sample: dict, args: dict):
    trace = program_spans.load(sample)
    if trace is None:
        return None
    return ratio(trace, args)
