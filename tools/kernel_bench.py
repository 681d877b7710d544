"""Microbench the assignment kernels at bench shapes.

Times greedy_assign_compact / greedy_assign_constrained for
N=5000 nodes x B=2048 pods: compile time, then
steady-state solve latency with and without the result download.

Usage: python tools/kernel_bench.py [N] [B]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

from kubernetes_tpu.ops.assignment import (
    GreedyConfig,
    greedy_assign_compact,
)


def main() -> None:
    from kubernetes_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 5000
    b = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
    r = 8
    rng = np.random.default_rng(0)

    allocatable = np.zeros((n, r), dtype=np.int32)
    allocatable[:, 0] = 32000
    allocatable[:, 1] = 64 * 1024 * 1024
    allocatable[:, 2] = 10**9
    allocatable[:, 3] = 110
    requested = np.zeros((n, r), dtype=np.int32)
    nzr = np.zeros((n, 2), dtype=np.int32)
    valid = np.ones(n, dtype=bool)
    pod_req = np.zeros((b, r), dtype=np.int32)
    pod_req[:, 0] = 250
    pod_req[:, 1] = 512 * 1024
    pod_req[:, 3] = 1
    pod_nzr = np.tile(np.array([[250, 512 * 1024]], dtype=np.int32), (b, 1))
    rows = np.ones((8, n), dtype=bool)
    midx = np.zeros(b, dtype=np.int32)
    active = np.ones(b, dtype=bool)

    t0 = time.perf_counter()
    up = jax.device_put(
        (allocatable, requested, nzr, valid, pod_req, pod_nzr, rows, midx,
         active)
    )
    jax.block_until_ready(up)
    t_up = time.perf_counter() - t0
    print(f"device_put ({n}x{r} nodes + {b} pods): {t_up*1000:.1f} ms")

    cfg = GreedyConfig()
    t0 = time.perf_counter()
    out = greedy_assign_compact(*up, config=cfg)
    jax.block_until_ready(out)
    print(f"compile+first solve: {time.perf_counter()-t0*1:.2f} s")

    for trial in range(3):
        t0 = time.perf_counter()
        out = greedy_assign_compact(*up, config=cfg)
        jax.block_until_ready(out)
        t_solve = time.perf_counter() - t0
        t0 = time.perf_counter()
        a = np.asarray(out[0])
        t_dl = time.perf_counter() - t0
        print(
            f"trial {trial}: solve {t_solve*1000:.1f} ms, "
            f"download {t_dl*1000:.1f} ms, placed {(a >= 0).sum()}"
        )

    # dispatch-only latency (what the pipelined path pays on the host)
    t0 = time.perf_counter()
    out = greedy_assign_compact(*up, config=cfg)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(out)
    print(f"dispatch (async) returned in {t_dispatch*1000:.1f} ms")

    # A/B vs the fused Pallas kernel via forced 10-solve chains (a
    # dependent chain amortizes the per-dispatch round trip)
    from kubernetes_tpu.ops.pallas_solver import pallas_greedy_solve

    def chain(fn, k):
        a = out[0]
        req_s, nzr_s = up[1], up[2]
        for _ in range(k):
            a, req_s, nzr_s = fn(
                up[0], req_s, nzr_s, up[3], up[4], up[5], up[6], up[7],
                up[8], config=cfg,
            )
        return np.asarray(a)

    chain(pallas_greedy_solve, 1)  # compile
    for name, fn in (
        ("xla   ", greedy_assign_compact),
        ("pallas", pallas_greedy_solve),
    ):
        t1 = time.perf_counter()
        chain(fn, 1)
        one = time.perf_counter() - t1
        t1 = time.perf_counter()
        chain(fn, 10)
        ten = time.perf_counter() - t1
        print(
            f"{name}: marginal solve ~{(ten - one) / 9 * 1000:.1f} ms "
            f"(chain1 {one*1000:.0f} ms, chain10 {ten*1000:.0f} ms)"
        )


if __name__ == "__main__":
    main()
