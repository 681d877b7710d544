"""The growth of a counter of the run's samples in the window
(``args["counter"]``) over ``args["over"]``: another counter's growth,
``window_pods`` (the pods the window timed) or ``window_waves`` (the
harness's waves that began in it). None where either is missing or the
denominator is 0."""


def read(sample: dict, args: dict):
    start, end = sample["start"], sample["end"]
    counter, over = args["counter"], args["over"]
    if counter not in end:
        return None
    if over == "window_pods":
        below = len(sample["run"].window_names)
    elif over == "window_waves":
        below = sum(1 for w in sample["run"].waves if w["in_window"])
    elif over in end:
        below = end[over] - start[over]
    else:
        return None
    if below <= 0:
        return None
    return (end[counter] - start[counter]) / below
