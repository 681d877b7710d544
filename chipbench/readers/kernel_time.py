"""Device time of the solver kernel's events in the traced slice, in
milliseconds a call. The pattern that tells the kernel's events from
other device operations is data in the metric's file."""

import re


def kernel_calls(sample: dict, pattern: str):
    """(calls, seconds) of the device operations whose name matches."""
    trace = sample.get("trace")
    if not trace:
        return 0, 0.0
    rx = re.compile(pattern)
    calls = seconds = 0
    for name, (count, total) in trace["ops"].items():
        if rx.search(name):
            calls += count
            seconds += total
    return calls, seconds


def read(sample: dict, args: dict):
    calls, seconds = kernel_calls(sample, args["pattern"])
    if calls == 0:
        return None
    return seconds * 1e3 / calls
