"""The HBM bytes one call of the mesh's shard kernel has to move, from
its shapes: every operand read once and every result written once,
int32 (the two results: a float32 and an int32 scalar).

``pallas_shard_candidate`` is one pod's step on one chip's shard of the
cluster: it reads the shard's ``alloc`` [R, n], ``req_state`` [R, n],
``nzr_state`` [2, n], ``valid`` [1, n] and the static mask rows [U, n]
(``n`` node rows a chip), and the pod's ``req`` [R], ``nzr`` [2] and
``mask_index`` [1]; it writes the shard's best score and the row that
holds it. The scan makes one such call a step, 4,096 a batch, and the
winner's bump and the two all-reduces happen outside the kernel: the
node state lives in HBM between calls, so every call reads all of it
again. That is what is counted here: the least THIS call can move,
given where its operands are. A kernel that kept the shard's state in
VMEM across a batch's steps, as the one-chip ``pallas_greedy_solve``
does, would move a few thousandths of it; that is the kernel's to gain,
not this count's to assume.

The step is integer compares and adds on the vector unit over ``n``
rows, so bytes, not operations, are its bound.
"""

from __future__ import annotations

BYTES = 4  # every operand and both results are four bytes wide


def shard_call_bytes(rows_per_chip: int, r: int, u: int,
                     pods_per_call: int = 1) -> int:
    per_node = r + r + 2 + 1 + u  # alloc, req, nzr, valid, mask rows
    per_pod = r + 2 + 1 + 2  # req, nzr, mask index; best score, its row
    return BYTES * (rows_per_chip * per_node + pods_per_call * per_pod)
