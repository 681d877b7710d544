"""A stage timer of ``sched.stage_seconds`` over the window's seconds, in
milliseconds a second: what a stage that does not come once a batch (a
collection) takes of the time. A stage that never ran has no key: it
reads 0 where the timer named ``args["beside"]`` is there (the program
has both, and this one spent nothing), and None where that is missing
too (a program from before the timers)."""


def read(sample: dict, args: dict):
    start, end = sample["start"], sample["end"]
    stage = args["stage"]
    window_s = end["t"] - start["t"]
    if window_s <= 0:
        return None
    if stage not in end["stage_seconds"]:
        return 0.0 if args.get("beside") in end["stage_seconds"] else None
    spent = end["stage_seconds"][stage] - start["stage_seconds"].get(stage, 0.0)
    return spent * 1e3 / window_s
