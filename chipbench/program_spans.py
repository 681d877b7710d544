"""The program's own spans in the traced slice, beside the device's busy
intervals, on the profiler's one clock.

The scheduler times each stage with ``flightrecorder.stage``, which is a
``jax.profiler.TraceAnnotation("sched/<name>")`` while a profiler session
runs: the slice's ``.xplane.pb`` holds them on the line of the thread
that did the work, with their stats (``batch``, ``pods``,
``queue_wait_sum_ms``...). ``load`` opens that file once a run and the
readers (``idle_by_span``, ``span_stat_ratio``, ``span_stat_max``) share
the result.
"""

from __future__ import annotations

import glob
import os

from chipbench.tracing import DEVICE_OP_LINES, SLICE, _union

PREFIX = "sched/"
MARK_PREFIX = "sched/mark/"

_loaded: dict = {}  # path -> what read_trace returned: one run, one file


def read_trace(path: str) -> dict:
    """``window``: (start, end) of the ``chipbench/slice`` span in ns;
    ``busy``: merged (start, end) intervals inside it in which an
    operation ran on the first device (the rule of ``tracing.reduce``: the
    ``XLA Ops`` and ``Async XLA Ops`` lines of the first ``/device:``
    plane; with no device plane, which only a rehearsal's CPU trace lacks,
    the host events that carry an ``hlo_op`` stat); ``device_plane``:
    whether there was one; ``spans``: the ``sched/`` events of every host
    line as dicts ``name``, ``start``, ``end``, ``line`` (plane and the
    line's index in it: a thread) and ``stats``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    per_device: dict = {}
    host_ops: list = []
    spans: list = []
    window = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for index, line in enumerate(plane.lines):
            if is_device:
                if line.name in DEVICE_OP_LINES:
                    events = per_device.setdefault(plane.name, [])
                    for ev in line.events:
                        events.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
                continue
            for ev in line.events:
                name = ev.name
                if name == SLICE:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith(PREFIX):
                    spans.append({
                        "name": name,
                        "start": ev.start_ns,
                        "end": ev.start_ns + ev.duration_ns,
                        "line": (plane.name, index),
                        "stats": dict(ev.stats),
                    })
                elif ev.duration_ns > 0 and any(
                    key == "hlo_op" for key, _ in ev.stats
                ):
                    host_ops.append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    if window is None:
        raise RuntimeError(f"no {SLICE!r} span in {path}")
    device_plane = bool(per_device)
    ops = per_device[sorted(per_device)[0]] if device_plane else host_ops
    w0, w1 = window
    busy = _union([
        (max(s, w0), min(e, w1)) for s, e in ops if e > w0 and s < w1
    ])
    return {
        "window": window,
        "busy": [tuple(b) for b in busy],
        "device_plane": device_plane,
        "spans": spans,
    }


def program_has_spans() -> bool:
    """Whether the program under test has the stage primitive at all: a
    commit from before it has nothing to read, which is no error."""
    from kubernetes_tpu.utils import flightrecorder

    return hasattr(flightrecorder, "stage")


def load(sample: dict):
    """The run's slice, read once; None where there is nothing to read: no
    traced slice, or a program that has no spans. A chip's trace (one with
    a device plane) of a program that has them and shows none is an
    error, not a zero."""
    found = sorted(glob.glob(os.path.join(
        str(sample["root"]), ".chipbench_trace", sample["cell"]["name"],
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not found:
        return None
    path = found[-1]
    if path not in _loaded:
        _loaded.clear()
        _loaded[path] = read_trace(path)
    trace = _loaded[path]
    if not trace["spans"]:
        if trace["device_plane"] and program_has_spans():
            raise RuntimeError(
                f"the device's trace {path} holds no {PREFIX!r} span of a "
                f"program that writes them"
            )
        return None
    return trace


# -- intervals: sorted, merged (start, end) lists --------------------------


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if end > start:
            out.append((start, end))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals, w0, w1) -> list:
    """What of (w0, w1) the intervals leave uncovered."""
    out, cursor = [], w0
    for start, end in intervals:
        if start > cursor:
            out.append((cursor, min(start, w1)))
        cursor = max(cursor, end)
        if cursor >= w1:
            break
    if w1 > cursor:
        out.append((cursor, w1))
    return [(s, e) for s, e in out if e > s]


def idle(trace: dict) -> list:
    """The first device's idle intervals inside the slice."""
    return complement(trace["busy"], *trace["window"])


def open_intervals(trace: dict, keep) -> list:
    """Where a span that ``keep(name)`` accepts was open on any thread,
    inside the slice: a span that crosses the slice's edge counts for the
    part inside."""
    w0, w1 = trace["window"]
    return [tuple(i) for i in _union([
        (max(sp["start"], w0), min(sp["end"], w1))
        for sp in trace["spans"]
        if keep(sp["name"]) and sp["end"] > w0 and sp["start"] < w1
    ])]


def spans_in_slice(trace: dict, name: str) -> list:
    """The spans of that name that began inside the slice."""
    w0, w1 = trace["window"]
    return [sp for sp in trace["spans"]
            if sp["name"] == name and w0 <= sp["start"] < w1]
