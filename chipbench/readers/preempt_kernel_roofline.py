"""The preemption kernel's share of its roofline, in percent: the least
time the chip could take to move the bytes one call must move
(``preempt_kernel_bytes.preempt_call_bytes`` from the configuration's
``preempt_kernel_shape``, over the device's HBM bandwidth from
``peaks.json``) over the kernel's measured time a call."""

import json

from chipbench.preempt_kernel_bytes import preempt_call_bytes
from chipbench.readers.kernel_time import kernel_calls


def read(sample: dict, args: dict):
    calls, seconds = kernel_calls(sample, args["pattern"])
    shape = sample["cell"]["config"].get("preempt_kernel_shape")
    if calls == 0 or seconds <= 0 or shape is None:
        return None
    with open(sample["root"] / "chipbench" / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    kind = sample["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    least_s = preempt_call_bytes(**shape) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / calls)
