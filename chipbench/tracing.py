"""The profiler's trace of a slice of the window, and its reduction to
what the result line carries: device busy seconds, the device
operations that took most time, and the idle gaps by what the harness
was doing. Written new for this benchmark: nothing in the repo reduced
an ``.xplane.pb`` before.
"""

from __future__ import annotations

import glob
import os
import threading
import time

SLICE = "chipbench/slice"
DEVICE_OP_LINES = ("XLA Ops", "Async XLA Ops")  # compute, and DMA in flight


def op_name(event_name: str) -> str:
    """The TPU's events are named by their whole HLO text,
    ``%pallas_greedy_solve.1 = (s32[4096]...) custom-call(...)``: keep
    the operation's own name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


class Slice:
    """Traces the first ``seconds`` of the window. ``start`` returns once
    the profiler runs; a thread of its own stops it, so the generator is
    not held up."""

    def __init__(self, log_dir: str, seconds: float) -> None:
        self.log_dir = log_dir
        self.seconds = seconds
        self.host_start = 0.0  # perf_counter when the slice span began
        self._thread = threading.Thread(
            target=self._hold, name="chipbench-trace", daemon=True
        )

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the harness's own spans only
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._thread.start()

    def _hold(self) -> None:
        import jax

        self.host_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(SLICE):
            time.sleep(self.seconds)
        jax.profiler.stop_trace()

    def join(self) -> None:
        self._thread.join()

    def path(self) -> str:
        found = sorted(glob.glob(
            os.path.join(self.log_dir, "plugins", "profile", "*", "*.xplane.pb")
        ))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.log_dir}")
        return found[-1]


def _union(intervals: list) -> list:
    """Sorted, merged (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _flatten(phases: list, w0: float, w1: float) -> list:
    """Nested (start, end, name) spans of one thread as a sorted list of
    spans that do not overlap, each named for the innermost span over
    it; what no span covers is ``outside_any_phase``."""
    points = []
    for start, end, name in phases:
        if end > start:
            points.append((start, 1, name))
            points.append((end, 0, name))
    points.sort(key=lambda p: (p[0], p[1]))
    out, stack, cursor = [], [], w0
    for t, opens, name in points:
        t = min(max(t, w0), w1)
        if t > cursor:
            out.append((cursor, t, stack[-1] if stack else "outside_any_phase"))
            cursor = t
        if opens:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
    if w1 > cursor:
        out.append((cursor, w1, stack[-1] if stack else "outside_any_phase"))
    return out


def reduce(path: str, host_phases=(), host_start: float = 0.0,
           rehearsal: bool = False) -> dict:
    """Read one ``.xplane.pb`` and return

    - ``window_s``: the traced slice (the ``chipbench/slice`` span),
    - ``busy_s``: the union of the intervals in which an operation ran on
      a device, inside the slice, averaged over the devices,
    - ``ops``: device operation name -> [calls, seconds], whole trace,
    - ``device_ops``: the ten that took most time, [[name, seconds]],
    - ``idle_gaps``: the device's idle time inside the slice by the
      harness phase the host was in, [[phase, seconds]], longest first.
      ``host_phases`` are the harness's own (name, start, end) spans on
      the host clock; ``host_start`` is the host clock at the start of
      the slice span, which puts them on the trace's clock.

    Device operations are the events of each ``/device:*`` plane's
    ``XLA Ops`` and ``Async XLA Ops`` lines. A trace without a device
    plane, or without the slice span, is an error: nothing else may
    stand under the name of a device reading. Only a ``rehearsal``, whose
    CPU has no device plane, reads the host events that carry an
    ``hlo_op`` stat in their place."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    per_device: dict = {}  # plane name -> [(start, end, name)]
    host_ops: list = []
    window = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_device:
                if line.name not in DEVICE_OP_LINES:
                    continue
                events = per_device.setdefault(plane.name, [])
                for ev in line.events:
                    events.append((
                        ev.start_ns, ev.start_ns + ev.duration_ns,
                        op_name(ev.name),
                    ))
                continue
            for ev in line.events:
                if ev.name == SLICE:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif rehearsal and ev.duration_ns > 0:
                    if any(key == "hlo_op" for key, _ in ev.stats):
                        host_ops.append((
                            ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name,
                        ))
    if not per_device and rehearsal:
        per_device = {"/host:CPU": host_ops}
    if not per_device:
        raise RuntimeError(f"no /device: plane with XLA ops in {path}")
    if window is None:
        raise RuntimeError(f"no {SLICE!r} span in {path}")
    w0, w1 = window
    phases = [
        (w0 + (t0 - host_start) * 1e9, w0 + (t1 - host_start) * 1e9, name)
        for name, t0, t1 in host_phases
    ]

    ops: dict = {}
    for _, events in per_device.items():
        for start, end, name in events:
            entry = ops.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += (end - start) / 1e9
    busy = []
    first_merged = []
    for k, (_, events) in enumerate(sorted(per_device.items())):
        clipped = [
            (max(s, w0), min(e, w1)) for s, e, _ in events
            if e > w0 and s < w1
        ]
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if k == 0:
            first_merged = merged
    busy_s = sum(busy) / len(busy) if busy else 0.0

    # idle gaps of the first device, split over the innermost harness
    # phase the host was in
    gaps = []
    cursor = w0
    for start, end in first_merged:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if w1 > cursor:
        gaps.append((cursor, w1))
    idle: dict = {}
    segments = _flatten(phases, w0, w1)
    k = 0
    for start, end in gaps:
        while k < len(segments) and segments[k][1] <= start:
            k += 1
        j = k
        while j < len(segments) and segments[j][0] < end:
            s0, s1, name = segments[j]
            share = min(end, s1) - max(start, s0)
            if share > 0:
                idle[name] = idle.get(name, 0.0) + share / 1e9
            j += 1

    top = sorted(ops.items(), key=lambda kv: -kv[1][1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_s,
        "devices": len(per_device),
        "ops": ops,
        "device_ops": [[name, v[1]] for name, v in top[:10]],
        "idle_gaps": [
            [name, s] for name, s in
            sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        ],
    }
