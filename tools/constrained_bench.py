"""A/B the CONSTRAINED solvers at big cluster shapes.

Packs a realistic constrained batch with the real family packers
(spread-only by default -- the BigClusterSpread shape; --mixed adds
required/preferred pod affinity) and times the XLA constrained scan vs
the family-specialized Pallas kernel, printing the chosen Caps and the
VMEM estimate. This is the proof that the specialization breaks the old
~5.6k-node all-family VMEM ceiling on real hardware.

Usage: python tools/constrained_bench.py [N] [B] [--mixed]
"""

from __future__ import annotations

import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

from kubernetes_tpu.cache.snapshot import new_snapshot
from kubernetes_tpu.ops.affinity import (
    noop_affinity_tensors,
    pack_affinity_batch,
    pad_affinity_tensors,
)
from kubernetes_tpu.ops.assignment import (
    GreedyConfig,
    greedy_assign_constrained,
)
from kubernetes_tpu.ops.host_masks import static_mask_compact
from kubernetes_tpu.ops.pallas_constrained import (
    Caps,
    VMEM_BUDGET,
    constrained_vmem_bytes,
    pallas_constrained_solve,
)
from kubernetes_tpu.ops.scoring import (
    noop_score_tensors,
    pack_score_batch,
    pad_score_tensors,
)
from kubernetes_tpu.ops.topology import (
    noop_spread_tensors,
    pack_spread_batch,
    pad_spread_tensors,
)
from kubernetes_tpu.tensors import NodeTensorCache, pack_pod_batch
from kubernetes_tpu.testing import make_node, make_pod

POD_BUCKET = 64
MASK_ROW_BUCKET = 8

DEFAULT_WEIGHTS = {
    "NodeAffinity": 1,
    "TaintToleration": 1,
    "DefaultPodTopologySpread": 1,
    "PodTopologySpread": 2,
    "InterPodAffinity": 1,
}


def build(n_nodes: int, b: int, mixed: bool):
    nodes = []
    for i in range(n_nodes):
        nodes.append(
            make_node(f"node-{i}")
            .capacity(cpu="32", memory="64Gi", pods=110)
            .label("topology.kubernetes.io/zone", f"zone-{i % 16}")
            .label("kubernetes.io/hostname", f"node-{i}")
            .obj()
        )
    existing = [
        make_pod(f"ex-{i}")
        .node(f"node-{i % n_nodes}")
        .container(cpu="100m", memory="128Mi")
        .labels(app="spread")
        .obj()
        for i in range(min(1000, n_nodes))
    ]
    pods = []
    for i in range(b):
        p = (
            make_pod(f"pod-{i}")
            .container(cpu="100m", memory="128Mi")
            .labels(app="spread")
            .spread_constraint(
                max_skew=250,
                topology_key="topology.kubernetes.io/zone",
                when_unsatisfiable="DoNotSchedule",
                match_labels={"app": "spread"},
            )
        )
        if mixed and i % 3 == 0:
            p = p.pod_affinity(
                "topology.kubernetes.io/zone", {"app": "spread"}
            )
        if mixed and i % 5 == 0:
            p = p.preferred_pod_affinity(
                "topology.kubernetes.io/zone", {"app": "spread"}, weight=5
            )
        pods.append(p.obj())

    snap = new_snapshot(existing, nodes)
    nt = NodeTensorCache().update(snap)
    batch = pack_pod_batch(pods, nt.dims)
    mask_rows, mask_index = static_mask_compact(pods, snap, nt)
    padded = POD_BUCKET * math.ceil(batch.size / POD_BUCKET)
    order = batch.order
    req = np.zeros((padded, nt.dims.num_dims), dtype=np.int32)
    nzr = np.zeros((padded, 2), dtype=np.int32)
    midx = np.zeros(padded, dtype=np.int32)
    active = np.zeros(padded, dtype=bool)
    req[:batch.size] = batch.requests[order]
    nzr[:batch.size] = batch.non_zero_requests[order]
    midx[:batch.size] = mask_index[order]
    active[:batch.size] = True
    u = mask_rows.shape[0]
    u_padded = MASK_ROW_BUCKET * math.ceil(u / MASK_ROW_BUCKET)
    rows = np.zeros((u_padded, nt.capacity), dtype=bool)
    rows[:u] = mask_rows

    ordered = [pods[int(i)] for i in order]
    sp = pack_spread_batch(ordered, snap, nt)
    af = pack_affinity_batch(ordered, snap, nt)
    sc = pack_score_batch(
        ordered, snap, nt, None, DEFAULT_WEIGHTS,
        hard_pod_affinity_weight=1, cluster_affinity_scoring=None,
    )
    sp_t = (
        pad_spread_tensors(sp, padded)
        if sp is not None else noop_spread_tensors(padded, nt.capacity)
    )
    af_t = (
        pad_affinity_tensors(af, padded)
        if af is not None else noop_affinity_tensors(padded, nt.capacity)
    )
    sc_t = (
        pad_score_tensors(sc, padded)
        if sc is not None else noop_score_tensors(padded, nt.capacity)
    )
    common = (
        nt.allocatable, nt.requested, nt.non_zero_requested, nt.valid,
        req, nzr, rows, midx, active,
    )
    present = (sp is not None, af is not None, sc is not None)
    return common, tuple(sp_t), tuple(af_t), tuple(sc_t), present


def derive_caps(sp_t, af_t, sc_t, sp_p, af_p, sc_p):
    from kubernetes_tpu.ops.assignment import caps_for_families

    return caps_for_families(sp_t, af_t, sc_t, sp_p, af_p, sc_p)


def main() -> None:
    from kubernetes_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    mixed = "--mixed" in sys.argv
    n = int(args[0]) if args else 20000
    b = int(args[1]) if len(args) > 1 else 1024
    t0 = time.perf_counter()
    common, sp_t, af_t, sc_t, present = build(n, b, mixed)
    print(f"pack: {time.perf_counter()-t0:.1f}s")
    caps = derive_caps(sp_t, af_t, sc_t, *present)
    n_cap = common[0].shape[0]
    est = constrained_vmem_bytes(
        n_cap, common[0].shape[1], common[6].shape[0],
        sc_t[0].shape[0], sc_t[5].shape[1], sp_t[0].shape[1], caps,
        chunk=min(common[4].shape[0], 1024),
    )
    print(
        f"caps={caps} vmem_est={est/2**20:.1f}MiB "
        f"budget={VMEM_BUDGET/2**20:.1f}MiB fits={est <= VMEM_BUDGET}"
    )

    up = jax.device_put(common)
    sp_d = jax.device_put(sp_t)
    af_d = jax.device_put(af_t)
    sc_d = jax.device_put(sc_t)
    jax.block_until_ready(up)
    cfg = GreedyConfig()

    def run(fn, tag, chain=4, **kw):
        t0 = time.perf_counter()
        out = fn(*up, sp_d, af_d, sc_d, config=cfg, **kw)
        jax.block_until_ready(out)
        print(f"{tag}: compile+first {time.perf_counter()-t0:.1f}s")

        def chained(k):
            """k dependent solves (carry req/nzr) + result download --
            the steady-state dispatch pattern; defeats async-dispatch
            timing artifacts."""
            req_s, nzr_s = up[1], up[2]
            o = None
            for _ in range(k):
                o = fn(
                    up[0], req_s, nzr_s, *up[3:], sp_d, af_d, sc_d,
                    config=cfg, **kw,
                )
                req_s, nzr_s = o[1], o[2]
            return np.asarray(o[0])

        chained(1)
        t1 = time.perf_counter()
        a1 = chained(1)
        one = time.perf_counter() - t1
        t1 = time.perf_counter()
        chained(1 + chain)
        more = time.perf_counter() - t1
        per = (more - one) / chain
        print(
            f"{tag}: marginal solve {per*1000:.1f} ms "
            f"({b/per:.0f} pods/s), placed {(a1 >= 0).sum()}"
        )
        return a1

    a_pl = run(pallas_constrained_solve, "pallas", caps=caps)
    a_xla = run(greedy_assign_constrained, "xla   ")
    same = (a_pl == a_xla).all()
    print(f"assignments identical: {same}")
    if not same:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
