"""Pods of a wave over that wave's drain (wave start -> last bind on the
watch), median over the waves that started in the window. The gaps
between waves are not in it, and a median does not see one stalled wave:
it is the steadier reading of the drain alone, beside the end-to-end
``bound_pods_per_s``, which is all the pods over all the window."""

import statistics


def read(sample: dict, args: dict):
    rates = [
        w["pods"] / w["drain_s"] for w in sample["run"].waves
        if w["in_window"] and w["drain_s"] > 0
    ]
    return statistics.median(rates) if rates else None
