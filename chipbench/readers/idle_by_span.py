"""Of the first device's idle time inside the traced slice, the share in
percent during which the program was in a given stage: a span of the
name ``args["span"]`` was open on any thread (threads overlap, so the
shares of several spans may sum past 100). With ``args["no_span_but"]``
instead, the share during which no ``sched/`` span but that one was open
anywhere: given ``sched/pop_wait``, the scheduler had nothing to do. That
share is exclusive: it and the share under any other span make 100."""

from chipbench import program_spans


def share(trace: dict, args: dict):
    gaps = program_spans.idle(trace)
    idle_ns = program_spans.total(gaps)
    if idle_ns <= 0:
        return None
    if "span" in args:
        under = program_spans.open_intervals(
            trace, lambda name: name == args["span"]
        )
        covered = program_spans.total(program_spans.intersect(gaps, under))
    else:
        working = program_spans.open_intervals(
            trace, lambda name: name != args["no_span_but"]
            and not name.startswith(program_spans.MARK_PREFIX)
        )
        covered = idle_ns - program_spans.total(
            program_spans.intersect(gaps, working)
        )
    return 100.0 * covered / idle_ns


def read(sample: dict, args: dict):
    trace = program_spans.load(sample)
    if trace is None:
        return None
    return share(trace, args)
