"""A quantile of due -> bind over every pod of the window, in
milliseconds: the same reading as the end-to-end latencies, for a cell
where that quantile is too unsteady to carry a bound."""

import numpy as np


def read(sample: dict, args: dict):
    latencies = sample["run"].latencies_ms()
    if not latencies:
        return None
    return float(np.percentile(latencies, args["quantile"]))
