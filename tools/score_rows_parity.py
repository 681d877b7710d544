"""Hold the constrained Pallas kernel to the XLA scan on the chip at the
shape a live score family has since PR 48: 64 static rows a batch
(``ops/scoring.MAX_SCORE_SIGS``) at the 5,632 node slots of a 5,000-node
cluster, with real ImageLocality rows (the catalogue of the benchmark's
``image-locality-5000``), for the score family alone and with all three
families at their default caps. Prints the kernel's operand shapes (what
``chipbench/configs/image-locality-5000.json`` ``kernel_shape`` counts),
the VMEM estimate beside its gate, and one JSON line a case, each with
the platform it ran on; exits 1 if a case disagrees or fails to compile.

Only a TPU answers the question: in interpret mode nothing is laid out
in VMEM and Mosaic compiles nothing, so the gate could not fail there
(``tests/test_score_signatures.py`` holds the interpreted kernel to the
scan on the CPU). On any other backend this exits 2 and runs nothing.
``--nodes`` moves the cluster off the cell's 5,000, to try the gate
where a larger cluster meets it.

    chiprun -- python tools/score_rows_parity.py        # ~3 min cold
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def packed(apps: int, b: int, nodes: int):
    from chipbench import image_reference
    from kubernetes_tpu.cache.cache import SchedulerCache
    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.ops.host_masks import static_mask_compact
    from kubernetes_tpu.ops.scoring import pack_score_batch, pad_score_tensors
    from kubernetes_tpu.tensors import NodeTensorCache, pack_pod_batch
    from kubernetes_tpu.testing import make_node, make_pod

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/image-locality-5000.json")) as f:
        spec = dict(json.load(f)["images"], apps=apps, max_per_node=80)
    cat = image_reference.catalogue(spec, nodes)
    rng = np.random.default_rng(apps)
    cache = SchedulerCache()
    for j in range(nodes):
        w = make_node(f"node-{j}").labels(
            **{"topology.kubernetes.io/zone": f"zone-{j % 10}"}
        ).capacity(cpu="32", memory="64Gi", pods=110)
        for image, size in cat.node_images(j):
            w.image(image, size)
        cache.add_node(w.obj())
    snap = cache.update_snapshot(Snapshot())
    nt = NodeTensorCache().update(snap)
    pods = [
        make_pod(f"p{i}").container(
            cpu=f"{rng.choice([100, 250, 500])}m",
            memory=f"{rng.choice([128, 512])}Mi",
            image=cat.apps[int(rng.integers(apps)) if i >= apps else i],
        ).obj()
        for i in range(b)
    ]
    batch = pack_pod_batch(pods, nt.dims)
    mask_rows, mask_index = static_mask_compact(pods, snap, nt)
    order = batch.order
    rows = np.zeros((8, nt.capacity), dtype=bool)
    rows[:mask_rows.shape[0]] = mask_rows
    requested = nt.requested.copy()
    requested[:, 0] = rng.integers(0, 16000, nt.capacity)
    sc = pack_score_batch(
        [pods[int(i)] for i in order], snap, nt, None, {"ImageLocality": 1}
    )
    common = (
        nt.allocatable, requested, np.ascontiguousarray(requested[:, :2]),
        nt.valid, batch.requests[order], batch.non_zero_requests[order],
        rows, mask_index[order].astype(np.int32), np.ones(b, dtype=bool),
    )
    return common, tuple(pad_score_tensors(sc, b)), int(sc.pod_sig.max()) + 1


def main() -> int:
    import jax

    from kubernetes_tpu.ops.affinity import noop_affinity_tensors
    from kubernetes_tpu.ops.assignment import (
        GreedyConfig,
        greedy_assign_constrained,
    )
    from kubernetes_tpu.ops.pallas_constrained import (
        VMEM_BUDGET,
        _spec_plan,
        constrained_vmem_bytes,
        live_caps,
        pallas_constrained_solve,
    )
    from kubernetes_tpu.ops.topology import noop_spread_tensors

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--nodes", type=int, default=5000)
    nodes = parser.parse_args().nodes
    device = jax.devices()[0]
    if jax.default_backend() != "tpu":
        print(f"score_rows_parity: needs a TPU, found {device.platform}: "
              "the VMEM gate is only tried where Mosaic compiles the "
              "kernel (run it through chiprun)", file=sys.stderr)
        return 2
    config = GreedyConfig()
    failures = 0
    b = 1024
    for apps in (48, 64):
        common, sc_t, sigs = packed(apps, b, nodes)
        n = common[0].shape[0]
        sp_t = tuple(np.asarray(a) for a in noop_spread_tensors(b, n))
        af_t = tuple(np.asarray(a) for a in noop_affinity_tensors(b, n))
        want = np.asarray(greedy_assign_constrained(
            *common, sp_t, af_t, sc_t, config=config)[0])
        for tag, caps in (("sc", live_caps(False, False, True)),
                          ("all", live_caps(True, True, True))):
            shapes = {"r": 4, "n": n, "u": 8, "s": sc_t[0].shape[0],
                      "z": sc_t[5].shape[1], "v_sp": sp_t[0].shape[1],
                      "grid": 1}
            in_specs, out_shapes, *_ = _spec_plan(caps, shapes, b)
            node_rows = sum(
                spec.block_shape[0] for spec in in_specs
                if len(spec.block_shape) == 2 and spec.block_shape[1] == n
            )
            case = {
                "platform": device.platform, "kind": device.device_kind,
                "case": tag, "image_lists": apps, "signatures": sigs,
                "n": n, "b": b, "s": shapes["s"],
                "node_length_rows_in": node_rows,
                "node_length_rows_out": sum(
                    s.shape[0] for s in out_shapes
                    if len(s.shape) == 2 and s.shape[1] == n),
                "est_mib": round(constrained_vmem_bytes(
                    n, 4, 8, shapes["s"], shapes["z"], shapes["v_sp"], caps
                ) / (1 << 20), 2),
                "gate_mib": VMEM_BUDGET / (1 << 20),
            }
            try:
                got = np.asarray(jax.block_until_ready(
                    pallas_constrained_solve(
                        *common, sp_t, af_t, sc_t, config=config, caps=caps,
                    ))[0])
                case["mismatch"] = int((got != want).sum())
                case["placed"] = int((got >= 0).sum())
            except Exception as e:  # noqa: BLE001 - a refusal is a result
                case["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            failures += bool(case.get("mismatch") or case.get("error"))
            print(json.dumps(case), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
