"""Stage timers of ``sched.stage_seconds`` (``args["stages"]``, summed)
over the growth of a counter of the run's samples (``args["counter"]``)
in the window, in milliseconds: what a stage that does not come once a
batch costs each time it comes (a preemption wave's parts over the
preemptor's waves). None where the counter did not move. A stage that
never ran has no key: the sum then reads 0 for it where the timer named
``args["beside"]`` is there (the program has both), and None where that
is missing too (a program from before the timers)."""


def read(sample: dict, args: dict):
    start, end = sample["start"], sample["end"]
    counter = args["counter"]
    if counter not in end:
        return None
    times = end[counter] - start[counter]
    seconds = end["stage_seconds"]
    if times <= 0 or args.get("beside", args["stages"][0]) not in seconds:
        return None
    spent = sum(
        seconds.get(stage, 0.0) - start["stage_seconds"].get(stage, 0.0)
        for stage in args["stages"]
    )
    return spent * 1e3 / times
