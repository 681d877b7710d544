"""How long after its due instant a pod's create call began: a quantile
over the window's pods, in milliseconds."""

import numpy as np


def read(sample: dict, args: dict):
    run = sample["run"]
    late = [
        (run.issued[n] - run.due[n]) * 1e3
        for n in run.window_names if n in run.issued
    ]
    if not late:
        return None
    return float(np.percentile(late, args["quantile"]))
