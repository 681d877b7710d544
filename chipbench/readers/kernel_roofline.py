"""The solver kernel's share of its roofline, in percent: the least time
the chip could take to move the bytes one call must move
(``kernel_bytes.solve_call_bytes`` over the device's HBM bandwidth from
``peaks.json``) over the kernel's measured time a call. The kernel does
integer compares and adds on the vector unit, so bytes, not operations,
are its bound."""

import json

from chipbench.kernel_bytes import solve_call_bytes
from chipbench.readers.kernel_time import kernel_calls


def read(sample: dict, args: dict):
    calls, seconds = kernel_calls(sample, args["pattern"])
    if calls == 0 or seconds <= 0:
        return None
    with open(sample["root"] / "chipbench" / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    kind = sample["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    shape = sample["cell"]["config"]["kernel_shape"]
    least_s = solve_call_bytes(**shape) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / calls)
