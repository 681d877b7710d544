"""The HBM bytes one call of the preemption kernel
(``pallas_preempt_solve``) has to move, from its shapes: every operand
read once and every result written once, all int32 (start times ride as
their float32 bit patterns).

One call searches victims for ``chunk`` preemptors (512; a wave of 1,000
is two calls chained through the node state) over ``n`` node rows with
``v`` victim slots a node, fitting on ``a`` active resource dimensions
of the ``r`` the state carries. It reads, a node row each, the
allocatable [a, n], the victims' priorities, start times and active
flags [v, n] each, their requests twice ([v*a, n] victim-major for the
class prologue, [a*v, n] dimension-major for the reprieve), the ``u``
deduplicated candidate-mask rows [u, n] and the ``m`` nomination slots'
requests [m*a, n]; it reads the node state [r, n] and writes it back.
A preemptor: its request [r], priority, candidate index and active flag
in; its node and two halves of its victim mask out; and, once it has
chosen, one row of the row-major victim pack (priorities, flags, start
times, requests and allocatable of the chosen node, padded to 128
lanes), fetched from HBM for the fix-up of that node alone. The ``m``
nomination priorities ride once.

This is the least the call can move. The kernel is a chain of dependent
steps (a preemptor's choice changes the node state the next one sees)
over rows held in VMEM, so it is bound by latency, and its share of
this roofline is expected far under 1 %.
"""

from __future__ import annotations

BYTES = 4  # every operand is int32
LANES = 128


def preempt_call_bytes(n: int, v: int, a: int, r: int, u: int, m: int,
                       chunk: int) -> int:
    pack_row = LANES * -(-(3 * v + a * v + a) // LANES)
    per_node = a + 3 * v + 2 * a * v + u + m * a + 2 * r
    per_pod = (r + 3) + 3 + pack_row
    return BYTES * (n * per_node + chunk * per_pod + m)
