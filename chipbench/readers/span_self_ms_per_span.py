"""What a span of the program spends outside every span inside it: for
each span named ``args["span"]`` that began inside the traced slice, its
duration less the union of the other ``sched/`` spans (marks excepted:
they have no length) on the same line, a thread's, that lie inside it,
summed, in milliseconds, over the spans named ``args["per"]`` that began
in the slice (the dispatches: a batch). None where there is no span of
``args["per"]``; 0 where there is none of ``args["span"]``.

It is for the remainder that no child span names: a stage's children say
where its time goes, and this says how much of it they leave unsaid, so
that work which moves out of every named part still shows in a number.
The union makes nesting count once: a ``pack.score.ipa`` inside
``pack.score`` inside ``pack.families`` takes nothing more off ``pack``
than ``pack.families`` does, and a re-dispatch's ``sched/dispatch``
inside another's is the outer one's child like any other. A span of
another line that falls in the interval (a commit on the committer's
while the dispatcher packs) is another thread's time and is not taken
off."""

from chipbench import program_spans
from chipbench.tracing import _union


def by_line(trace: dict) -> dict:
    """line -> the trace's spans on it, marks left out."""
    out: dict = {}
    for sp in trace["spans"]:
        if not sp["name"].startswith(program_spans.MARK_PREFIX):
            out.setdefault(sp["line"], []).append(sp)
    return out


def inside(span: dict, others: list) -> list:
    """Those of ``others`` (spans of ``span``'s line) that lie inside
    it."""
    start, end = span["start"], span["end"]
    return [sp for sp in others
            if sp is not span and start <= sp["start"] and sp["end"] <= end]


def self_ns(span: dict, others: list) -> int:
    """``span``'s duration less what ``others`` (spans of its line)
    cover of it."""
    covered = _union([(sp["start"], sp["end"]) for sp in inside(span, others)])
    return (span["end"] - span["start"]) - program_spans.total(covered)


def per_span(trace: dict, args: dict):
    times = len(program_spans.spans_in_slice(trace, args["per"]))
    if times <= 0:
        return None
    lines = by_line(trace)
    spent_ns = sum(
        self_ns(sp, lines[sp["line"]])
        for sp in program_spans.spans_in_slice(trace, args["span"])
    )
    return spent_ns / 1e6 / times


def read(sample: dict, args: dict):
    trace = program_spans.load(sample)
    if trace is None:
        return None
    return per_span(trace, args)
