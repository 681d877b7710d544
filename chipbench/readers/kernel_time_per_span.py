"""Device time of the operations matching ``args["pattern"]`` in the
traced slice over the program's spans named ``args["span"]`` that began
in it, in milliseconds: what a kernel that runs a few times inside a
host stage costs each time the stage comes (the preemption kernel's
calls a wave)."""

from chipbench import program_spans
from chipbench.readers.kernel_time import kernel_calls


def read(sample: dict, args: dict):
    calls, seconds = kernel_calls(sample, args["pattern"])
    trace = program_spans.load(sample)
    if calls == 0 or trace is None:
        return None
    spans = len(program_spans.spans_in_slice(trace, args["span"]))
    if spans == 0:
        return None
    return seconds * 1e3 / spans
