"""The control of ``correct``: the reference put in the program's place,
scoring in bfloat16 (the nearest precision below the float32 the
configurations state), at the cells' own pool size. The check must fail
it and pass the same scheduler in float32. On the chip the same control
is read with ``--control`` (PERF.md section 2 has the readings)."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import reference as ref
from test_chipbench_reference import MIB, ballast_pool

ROOT = Path(__file__).resolve().parents[2]


def unexplained(nodes, pod, count, per_node):
    quota = count
    rounds = 0
    if pod.spread_max_skew:
        zones = int(nodes.zone.max()) + 1
        quota = np.bincount(nodes.zone, weights=per_node, minlength=zones)
        rounds = ref.zone_quota_error(quota, count)
    lo, hi = ref.bands(nodes, pod, quota)
    return ref.outside(per_node, lo, hi) + rounds


def classes():
    for name in ("basic-5000", "spread-anti-5000"):
        config = json.loads((ROOT / "chipbench/configs" / f"{name}.json").read_text())
        for cls_name, cls in config["pod_classes"].items():
            if "check" in cls:
                yield pytest.param(cls, id=f"{name}.{cls_name}")


@pytest.mark.parametrize("cls", classes())
def test_bfloat16_scoring_fails_the_check_and_float32_passes(cls):
    pool = ballast_pool(640, zones=10, grid=8)  # the configurations' pool
    pod = ref.PodClass(
        cls["cpu_milli"], cls["memory_mib"] * MIB,
        spread_max_skew=cls.get("spread", {}).get("max_skew", 0),
        anti_hostname="anti_affinity" in cls,
    )
    count, limit = cls["check"]["count"], cls["check"]["limit_pods"]
    sound, _ = ref.schedule(pool, pod, count, "float32")
    assert unexplained(pool, pod, count, sound) <= limit
    control, _ = ref.schedule(pool, pod, count, "bfloat16")
    assert unexplained(pool, pod, count, control) > 3 * max(limit, 1)


def cluster_before_a_wave(config):
    """The whole cluster as a window's wave finds it: the ballast pool,
    then the init pods where the reference itself puts them."""
    cluster = config["cluster"]
    n, zones = cluster["nodes"], cluster["zones"]
    pool = ballast_pool(
        zones * cluster["ballast"]["per_zone"], zones, cluster["ballast"]["grid"]
    )
    nodes = ref.Nodes(
        cap_cpu=np.full(n, 32000), cap_mem=np.full(n, 64 << 30),
        cap_pods=np.full(n, 110), used_cpu=np.zeros(n, np.int64),
        used_mem=np.zeros(n, np.int64), used_pods=np.zeros(n, np.int64),
        zone=np.arange(n) % zones,
    )
    for field in ("used_cpu", "used_mem", "used_pods"):
        getattr(nodes, field)[:pool.zone.shape[0]] = getattr(pool, field)
    init = config["pod_classes"][cluster["init_pods"]["class"]]
    per, _ = ref.schedule(
        nodes, ref.PodClass(init["cpu_milli"], init["memory_mib"] * MIB),
        cluster["init_pods"]["count"],
    )
    nodes.used_cpu += per * init["cpu_milli"]
    nodes.used_mem += per * init["memory_mib"] * MIB
    nodes.used_pods += per
    return nodes


@pytest.mark.parametrize("name, mix", [
    ("basic-5000", "burst-10k"), ("spread-anti-5000", "burst-5k"),
])
def test_bfloat16_scoring_leaves_a_bursts_wave_outside_the_bands(name, mix):
    """The window's own comparison, at the cells' own cluster and wave:
    unconstrained pods of the wave's size over all 5,000 nodes."""
    config = json.loads((ROOT / "chipbench/configs" / f"{name}.json").read_text())
    wave = json.loads(
        (ROOT / "chipbench/traffic" / f"{mix}.json").read_text()
    )
    count = sum(p["apps"] * p["pods_per_app"] for p in wave["params"]["wave"])
    cls = config["pod_classes"][wave["params"]["wave"][0]["class"]]
    pod = ref.PodClass(cls["cpu_milli"], cls["memory_mib"] * MIB)
    before = cluster_before_a_wave(config)
    lo, hi = ref.bands(before, pod, count)
    limit = wave["window_check"]["limit_pods"]
    sound, _ = ref.schedule(before, pod, count, "float32")
    assert ref.outside(sound, lo, hi) <= limit
    control, _ = ref.schedule(before, pod, count, "bfloat16")
    assert ref.outside(control, lo, hi) > 3 * max(limit, 1)
