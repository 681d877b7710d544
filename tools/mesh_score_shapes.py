"""What the score family's two shapes cost a mesh: on a four-chip mesh at
the 5,632 node slots of a 5,000-node cluster whose nodes report the
catalogue of the benchmark's ``image-locality-5000``, time each program
``warmup()`` compiles for the constrained path (three state variants, the
score family absent at its placeholders' ``SIG_BUCKET`` rows and live at
``MAX_SCORE_SIGS``), then land a wave of spread pods that name no image
(constrained, family absent) and a wave of pods of 48 image lists (family
live), and say what each wave's batches uploaded and took and whether
anything compiled after warm-up. One JSON line; exits 1 if a wave left
the device path or compiled, 2 where there is no TPU with four chips
(on virtual CPU devices ``tests/test_score_signatures.py`` holds the
counts; a time comes only from the chip).

    chiprun --chips 4 -- python tools/mesh_score_shapes.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NODES, ZONES, WAVE, APPS = 5000, 10, 4096, 48
ZONE = "topology.kubernetes.io/zone"


def four_chips(devices) -> bool:
    return devices[0].platform == "tpu" and len(devices) >= 4


def main() -> int:
    import jax

    devices = jax.devices()
    if not four_chips(devices):
        print(f"mesh_score_shapes: needs a TPU with four chips, found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2

    from chipbench import image_reference
    from kubernetes_tpu.apiserver.server import APIServer
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.informer import InformerFactory
    from kubernetes_tpu.config.loader import load_config_from_dict
    from kubernetes_tpu.ops.assignment import jit_cache_sizes
    from kubernetes_tpu.scheduler import batch as batch_mod
    from kubernetes_tpu.scheduler.scheduler import new_scheduler_from_config
    from kubernetes_tpu.testing import make_node, make_pod
    from kubernetes_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/image-locality-5000.json")) as f:
        cat = image_reference.catalogue(json.load(f)["images"], NODES)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler_from_config(client, informers, load_config_from_dict(
        {"tpuSolver": {"maxBatch": WAVE, "meshDevices": 4}}))
    for j in range(NODES):
        w = make_node(f"node-{j}").label(ZONE, f"zone-{j % ZONES}").capacity(
            cpu="32", memory="64Gi", pods=110)
        for image, size in cat.node_images(j):
            w.image(image, size)
        client.create_node(w.obj())
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()

    # every program warm-up sends, in its order: mode, the score family's
    # static rows, the replicated buffer's bytes, seconds (a first call of
    # a signature compiles it)
    calls = []
    solve_packed = batch_mod.solve_packed

    def timed(pieces, *state, **kw):
        t0 = time.perf_counter()
        out = jax.block_until_ready(solve_packed(pieces, *state, **kw))
        by_name = dict(pieces)
        calls.append({
            "mode": kw.get("mode"),
            "sc_rows": by_name["sc0"].shape[0] if "sc0" in by_name else None,
            "buffer_mb": round(sum(
                a.size * 4 for _, a in pieces if hasattr(a, "size")) / 1e6, 2),
            "s": round(time.perf_counter() - t0, 3),
        })
        return out

    batch_mod.solve_packed = timed
    t0 = time.perf_counter()
    sched.warmup()
    warmup_s = time.perf_counter() - t0
    batch_mod.solve_packed = solve_packed
    constrained = [c for c in calls if c["mode"] == "constrained"]
    sealed = dict(jit_cache_sizes(sched.mesh))
    sched.start()

    def wave(tag, pods):
        solved, spent = sched.batches_solved, sched.stage_totals.seconds()
        live = sched.family_facts.score_live
        t0 = time.perf_counter()
        for i in range(0, len(pods), 256):
            client.create_pods_bulk(pods[i:i + 256])
        deadline = time.time() + 300
        while time.time() < deadline:
            sched.wait_for_inflight_binds(timeout=60)
            if sum(1 for p in client.list_pods()[0]
                   if p.metadata.name.startswith(tag) and p.spec.node_name
                   ) == len(pods):
                break
            time.sleep(0.05)
        batches = sched.batches_solved - solved
        now = sched.stage_totals.seconds()
        return {
            "pods": len(pods), "batches": batches,
            "score_live_batches": sched.family_facts.score_live - live,
            "wall_s": round(time.perf_counter() - t0, 3),
            **{
                f"{stage}_ms_per_batch": round(
                    1000 * (now.get(stage, 0.0) - spent.get(stage, 0.0))
                    / max(batches, 1), 2)
                for stage in ("pack", "device_solve", "download")
            },
        }

    absent = wave("spread", [
        make_pod(f"spread-{i}").labels(app="spread")
        .container(cpu="250m", memory="512Mi")
        .spread_constraint(1, ZONE, match_labels={"app": "spread"}).obj()
        for i in range(WAVE)
    ])
    live = wave("apps", [
        make_pod(f"apps-{i}").labels(app=f"app-{i % APPS}")
        .container(cpu="250m", memory="512Mi", image=cat.apps[i % APPS]).obj()
        for i in range(WAVE)
    ])
    grew = {k: v - sealed.get(k, 0)
            for k, v in jit_cache_sizes(sched.mesh).items()
            if v != sealed.get(k, 0)}
    sched.stop()
    informers.stop()
    report = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "devices": len(devices),
        "warmup_s": round(warmup_s, 1),
        "warmup_constrained": constrained,
        "warmup_constrained_s": {
            str(rows): round(sum(
                c["s"] for c in constrained if c["sc_rows"] == rows), 2)
            for rows in sorted({c["sc_rows"] for c in constrained})
        },
        "wave_family_absent": absent, "wave_family_live": live,
        "compiled_after_warmup": grew,
        "pods_fallback": sched.pods_fallback,
        "solves_by_tier": dict(sched.ladder.solves_by_tier),
    }
    print(json.dumps(report), flush=True)
    ok = (not grew and sched.pods_fallback == 0
          and absent["score_live_batches"] == 0
          and live["score_live_batches"] == live["batches"] > 0)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
