"""What a rolled node's owner feels: from the instant a node was made
Ready (the ``ready`` entry of ``run.node_log``: when the call returned)
to the first bind the watch shows on that node after it, in
milliseconds; a quantile over the nodes made Ready in the window that
took a pod. None where no node was made Ready in the window."""

import numpy as np


def read(sample: dict, args: dict):
    run = sample["run"]
    ready = {
        name: t for t, name, kind in run.node_log
        if kind == "ready" and run.window_start <= t <= run.window_end
    }
    if not ready:
        return None
    first: dict = {}
    bind_time = run.watcher.bind_time
    for pod, node in list(run.watcher.bind_node.items()):
        since = ready.get(node)
        if since is None:
            continue
        at = bind_time[pod]
        if at >= since and at < first.get(node, float("inf")):
            first[node] = at
    waits = [(first[node] - ready[node]) * 1e3 for node in first]
    if not waits:
        return None
    return float(np.percentile(waits, args["quantile"]))
