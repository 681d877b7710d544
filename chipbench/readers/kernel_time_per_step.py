"""Device time of the operations matching ``args["pattern"]`` over the
calls of the operations matching ``args["step_pattern"]``, in
milliseconds: what a scan step spends in a group of operations that it
runs more than once (the mesh's two all-reduces a step, counted against
the shard kernel's one call a step). Both are summed over the mesh's
chips, so the result is one chip's."""

from chipbench.readers.kernel_time import kernel_calls


def read(sample: dict, args: dict):
    _, seconds = kernel_calls(sample, args["pattern"])
    steps, _ = kernel_calls(sample, args["step_pattern"])
    if steps == 0 or seconds <= 0:
        return None
    return seconds * 1e3 / steps
