"""Hold the compiled Pallas solver kernels to the XLA scan on the chip,
at and past the "does it fit" gates' edges.

That a kernel compiles does not make it right. On the TPU v5e (JAX
0.9.0 / libtpu 0.0.34) the constrained kernel's spread+affinity
specialization compiled without complaint and placed WRONGLY, with
answers that changed from call to call, at (n, b) = (16512, 1024),
(19712, 1024), (30976, 1024) and (19712, 64), while agreeing at
(16384, 1024), (42240, 1024) and (19712, 4096), whatever
``vmem_limit_bytes`` said: it read its initial state through output refs
that were only ALIASED to the inputs, never copied (PERF.md, PR 21).
This is the sweep that found it, and that passed once the kernel copied
its state in; run it after any change to a kernel, a gate or the
installed compiler:

    python tools/kernel_parity.py            # on the chip; ~5 min cold

With every constraint family a no-op the constrained kernels must
reproduce the basic scan exactly, so one cheap XLA reference judges
every specialization. Each case is solved twice by the one compiled
program: the whole batch, and a partial one whose pods end a step past a
quarter of it (``partial_live``: one step into the second SMEM chunk at
b = 4,096, so the kernel's step loop ends inside a chunk and skips the
chunks behind it; PR 30), where every slot from there on must answer
NO_NODE. One JSON line per case; exits 1 if any case
disagrees or fails to compile. The run loop's own guard is the warm-up
canary (scheduler/batch.py ``_pallas_canary``), which applies the same
comparison to the shapes a scheduler actually warms.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

#: (n, b) points around the spread+affinity divergence PR 21 found and fixed
SPREAD_AFFINITY_POINTS = (
    (16384, 1024), (16512, 1024), (19712, 1024), (19712, 64),
    (19712, 4096), (30976, 1024), (42240, 1024),
)


def problem(seed: int, n: int, b: int, r: int = 4, u: int = 8):
    """A half-loaded n-node cluster and b mixed pods, from ``seed``."""
    rng = np.random.default_rng(seed)
    alloc = np.zeros((n, r), np.int32)
    alloc[:, 0] = 32000
    alloc[:, 1] = 64 * 1024 * 1024
    alloc[:, 3] = 110
    requested = np.zeros_like(alloc)
    requested[:, 0] = rng.integers(0, 20000, n)
    requested[:, 1] = rng.integers(0, 40 << 20, n)
    requested[:, 3] = rng.integers(0, 40, n)
    nzr = np.ascontiguousarray(requested[:, :2])
    pod_req = np.zeros((b, r), np.int32)
    pod_req[:, 0] = rng.choice([100, 250, 500, 1000, 1500], b)
    pod_req[:, 1] = rng.choice([128, 256, 512, 1024, 2048], b) * 1024
    pod_req[:, 3] = 1
    rows = np.ones((u, n), bool)
    rows[1:] = rng.random((u - 1, n)) > 0.3
    return (
        alloc, requested, nzr, np.ones(n, bool), pod_req,
        np.ascontiguousarray(pod_req[:, :2]), rows,
        rng.integers(0, u, b).astype(np.int32), np.ones(b, bool),
    )


def main() -> int:
    import jax

    from kubernetes_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("kernel_parity: needs a TPU (the kernels do not lower "
              "anywhere else)", file=sys.stderr)
        return 2

    from kubernetes_tpu.ops.affinity import noop_affinity_tensors
    from kubernetes_tpu.ops.assignment import (
        GreedyConfig,
        greedy_assign_compact,
    )
    from kubernetes_tpu.ops.pallas_constrained import (
        DEFAULT_LIVE,
        FULL_CAPS,
        VMEM_BUDGET,
        constrained_vmem_bytes,
        live_caps,
        pallas_constrained_solve,
    )
    from kubernetes_tpu.ops.pallas_solver import (
        BASIC_VMEM_BUDGET,
        basic_vmem_bytes,
        pallas_greedy_solve,
    )
    from kubernetes_tpu.ops.scoring import noop_score_tensors
    from kubernetes_tpu.ops.topology import noop_spread_tensors

    config = GreedyConfig()
    failures = 0

    def report(kernel, tag, args, est, solve):
        nonlocal failures
        case = {"kernel": kernel, "case": tag, "n": args[0].shape[0],
                "b": args[4].shape[0], "est_mib": round(est / (1 << 20), 2)}
        b = case["b"]
        live = case["partial_live"] = b // 4 + 1
        partial = args[:8] + (np.arange(b) < live,)
        try:
            for key, problem_args in (
                ("mismatch", args), ("partial_mismatch", partial),
            ):
                got = np.asarray(
                    jax.block_until_ready(solve(problem_args))[0]
                )
                want = np.asarray(
                    greedy_assign_compact(*problem_args, config=config)[0]
                )
                case[key] = int((got != want).sum())
            case["partial_mismatch"] += int((got[live:] != -1).sum())
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            case["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        failures += bool(
            case.get("mismatch") or case.get("partial_mismatch")
            or case.get("error")
        )
        print(json.dumps(case), flush=True)

    # -- the basic kernel at its gate's edge, three (r, u) shapes --------
    for r, u, b in ((4, 8, 4096), (8, 8, 4096), (4, 128, 4096)):
        n = BASIC_VMEM_BUDGET // basic_vmem_bytes(1, r, u) // 128 * 128
        report(
            "basic", f"r{r} u{u} edge", problem(6, n, b, r, u),
            basic_vmem_bytes(n, r, u),
            lambda args: pallas_greedy_solve(*args, config=config),
        )

    # -- every constrained specialization at its gate's edge and past it --
    def constrained(caps, n, b):
        families = tuple(
            tuple(np.asarray(a) for a in noop(b, n))
            for noop in (
                noop_spread_tensors, noop_affinity_tensors,
                noop_score_tensors,
            )
        )
        return lambda args: pallas_constrained_solve(
            *args, *families, config=config, caps=caps
        )

    def estimate(caps, n, b):
        # hostname spread: the spread value space is the node count
        return constrained_vmem_bytes(
            n, 4, 8, 4, 64, n, caps, chunk=min(b, 1024)
        )

    def edge(caps, factor):
        n = 128
        while estimate(caps, n + 128, 1024) <= VMEM_BUDGET * factor:
            n += 128
        return n

    specializations = {
        "all": DEFAULT_LIVE, "all escalated": FULL_CAPS,
        "sp": live_caps(True, False, False),
        "af": live_caps(False, True, False),
        "sc": live_caps(False, False, True),
        "sp+af": live_caps(True, True, False),
        "sp+sc": live_caps(True, False, True),
        "af+sc": live_caps(False, True, True),
    }
    for tag, caps in specializations.items():
        for factor in (1.0, 1.5, 2.0):
            n = edge(caps, factor)
            report("constrained", f"{tag} x{factor}", problem(7, n, 1024),
                   estimate(caps, n, 1024), constrained(caps, n, 1024))
    for n, b in SPREAD_AFFINITY_POINTS:
        caps = specializations["sp+af"]
        report("constrained", "sp+af PR-21 point", problem(7, n, b),
               estimate(caps, n, b), constrained(caps, n, b))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
