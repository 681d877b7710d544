"""Pods bound in the window over batches x maxBatch, in percent: how
full the padded batches were."""


def read(sample: dict, args: dict):
    start, end = sample["start"], sample["end"]
    batches = end["batches"] - start["batches"]
    if batches <= 0:
        return None
    pods = end["pods_bound"] - start["pods_bound"]
    return 100.0 * pods / (batches * sample["run"].max_batch)
