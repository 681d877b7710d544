"""The HBM bytes one call of the solver kernel has to move, from its
shapes: every operand read once and every result written once, int32.

The basic kernel (``pallas_greedy_solve``) reads, per call, the batch's
``active``, ``req`` [B, R], ``nzr`` [B, 2] and ``mask_index`` vectors,
the node-side ``alloc`` [R, N], ``req_state`` [R, N], ``nzr_state``
[2, N], ``valid`` [1, N] and the static mask rows [U, N]; it writes the
assignments [B] and the two states back. The constrained kernel adds
``family_rows`` node-length rows (domain ids in, counts in and out, for
each spread and affinity row) and one index per pod and family.

This is the least the call can move. The kernel itself is a chain of B
dependent steps over state held in VMEM, so it is bound by latency and
its share of this roofline is expected to be far under 1%.
"""

from __future__ import annotations

BYTES = 4  # every operand is int32


def solve_call_bytes(n_cap: int, r: int, u: int, b: int,
                     family_rows: int = 0, families: int = 0) -> int:
    per_pod = 1 + r + 2 + 1 + 1 + families  # active, req, nzr, midx, asg
    per_node = (r + r + 2 + 1 + u) + (r + 2) + family_rows
    return BYTES * (b * per_pod + n_cap * per_node)
