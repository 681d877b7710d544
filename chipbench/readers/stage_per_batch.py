"""A stage timer of ``sched.stage_seconds`` over the batches solved in
the window, in milliseconds a batch."""


def read(sample: dict, args: dict):
    start, end = sample["start"], sample["end"]
    batches = end["batches"] - start["batches"]
    stage = args["stage"]
    if batches <= 0 or stage not in end["stage_seconds"]:
        return None
    spent = end["stage_seconds"][stage] - start["stage_seconds"].get(stage, 0.0)
    return spent * 1e3 / batches
