"""A quantile of due -> bind over the window's pods of one app label, in
milliseconds: ``pod_to_bind_quantile`` over a part of the window (the
replacements of drained pods, app ``redo``). A pod's name begins with
its app (``Run.make_pods``). None where the window held no such pod
that was bound."""

import numpy as np


def read(sample: dict, args: dict):
    run = sample["run"]
    prefix = args["app"] + "-"
    bind = run.watcher.bind_time
    latencies = [
        (bind[n] - run.due[n]) * 1e3
        for n in run.window_names if n.startswith(prefix) and n in bind
    ]
    if not latencies:
        return None
    return float(np.percentile(latencies, args["quantile"]))
