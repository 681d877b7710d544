"""A counter of the run's samples: its value at the window's end less
its value at the start."""


def read(sample: dict, args: dict):
    name = args["counter"]
    if name not in sample["end"]:
        return None
    return sample["end"][name] - sample["start"][name]
