"""The mesh's shard kernel's share of its roofline, in percent: the least
time one chip could take to move the bytes one call must move
(``shard_kernel_bytes.shard_call_bytes`` from the configuration's
``kernel_shape``, over the device's HBM bandwidth from ``peaks.json``)
over the kernel's measured time a call, averaged over the mesh's chips
(every chip runs the same call on its own shard)."""

import json

from chipbench.readers.kernel_time import kernel_calls
from chipbench.shard_kernel_bytes import shard_call_bytes


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
    least_s = shard_call_bytes(**shape) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / calls)
