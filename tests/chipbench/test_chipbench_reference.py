"""chipbench/reference.py on hand-worked cases, against itself in lower
precision (the control), and its two forms against each other."""

import numpy as np
import pytest

from chipbench import reference as ref

MIB = 1 << 20
GIB = 1 << 30


def nodes(used_pods, cpu=250, mem_mib=512, zones=1, cap_pods=110):
    used = np.asarray(used_pods, dtype=np.int64)
    n = used.shape[0]
    return ref.Nodes(
        cap_cpu=np.full(n, 32000), cap_mem=np.full(n, 64 * GIB),
        cap_pods=np.full(n, cap_pods), used_cpu=used * cpu,
        used_mem=used * mem_mib * MIB, used_pods=used.copy(),
        zone=np.arange(n) % zones,
    )


def ballast_pool(n=640, zones=10, grid=8):
    """The configurations' ballast pool: node j of a zone carries j % grid
    pods of 1000m/128Mi and (j // grid) % grid of 100m/2Gi."""
    j = np.arange(n) // zones
    a, b = j % grid, (j // grid) % grid
    return ref.Nodes(
        cap_cpu=np.full(n, 32000), cap_mem=np.full(n, 64 * GIB),
        cap_pods=np.full(n, 110), used_cpu=a * 1000 + b * 100,
        used_mem=(a * 128 + b * 2048) * MIB, used_pods=a + b,
        zone=np.arange(n) % zones,
    )


def test_scores_by_hand():
    # 32-cpu / 64Gi node, requested 8 cpu / 16Gi with the pod: a quarter
    # of both, so LeastAllocated = (75 + 75) // 2 and Balanced = 100
    assert ref.scores([32000], [64 * GIB], [8000], [16 * GIB])[0] == 175
    # 8 cpu (25%) and 32Gi (50%): least (75 + 50) // 2 = 62, balanced
    # trunc((1 - 0.25) * 100) = 75
    assert ref.scores([32000], [64 * GIB], [8000], [32 * GIB])[0] == 137
    # a full dimension scores 0 on both
    assert ref.scores([32000], [64 * GIB], [32000], [1 * GIB])[0] == 49
    # over capacity: that dimension's least-allocated part is 0
    assert ref.scores([1000], [GIB], [2000], [GIB // 2])[0] == 25


def test_float32_scores_equal_exact_over_the_cells_range():
    k = np.arange(1, 111)
    for cpu, mem in ((250, 512), (100, 128), (1000, 128), (100, 2048)):
        args = (np.full(110, 32000), np.full(110, 64 * GIB), k * cpu,
                k * mem * MIB)
        assert (ref.scores(*args, "float32") == ref.scores(*args)).all()
        assert (ref.scores(*args, "bfloat16") != ref.scores(*args)).any()


def test_round_bfloat16():
    x = np.array([1.0, 1.00390625, 31500.0, 3.14159], dtype=np.float32)
    got = ref.round_bfloat16(x)
    assert got.tolist() == [1.0, 1.0, 31488.0, 3.140625]


def test_schedule_by_hand_plain():
    # three empty nodes, 4 pods: every placement lowers that node's score,
    # so the pods go round (lowest index first) and node 0 gets the 4th
    per_node, unplaced = ref.schedule(nodes([0, 0, 0]), ref.PodClass(250, 512 * MIB), 4)
    assert per_node.tolist() == [2, 1, 1] and unplaced == 0
    # a loaded node is passed over until the others have caught up
    per_node, _ = ref.schedule(nodes([5, 0, 0]), ref.PodClass(250, 512 * MIB), 6)
    assert per_node.tolist() == [0, 3, 3]


def test_schedule_by_hand_constraints():
    pod = ref.PodClass(100, 128 * MIB, spread_max_skew=1)
    # 2 zones (node i in zone i % 2): zone counts may differ by 1 at most
    per_node, _ = ref.schedule(nodes([0, 9, 0, 9], 100, 128, zones=2), pod, 4)
    assert per_node[[0, 2]].sum() == 2 and per_node[[1, 3]].sum() == 2
    anti = ref.PodClass(100, 128 * MIB, anti_hostname=True)
    per_node, unplaced = ref.schedule(nodes([0, 0, 0], 100, 128), anti, 5)
    assert per_node.tolist() == [1, 1, 1] and unplaced == 2
    # the node selector
    per_node, _ = ref.schedule(
        nodes([0, 0, 0]), ref.PodClass(250, 512 * MIB), 3,
        eligible=np.array([False, True, False]),
    )
    assert per_node.tolist() == [0, 3, 0]
    # pod count is a dimension of the fit
    per_node, unplaced = ref.schedule(
        nodes([0, 0], cap_pods=2), ref.PodClass(250, 512 * MIB), 5
    )
    assert per_node.tolist() == [2, 2] and unplaced == 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bands_hold_the_sequential_scheduler(seed):
    """Whatever the tie-break, a greedy placement lies inside the bands;
    here the tie-break is the node order, shuffled."""
    rng = np.random.default_rng(seed)
    pool = ballast_pool(200, zones=5)
    order = rng.permutation(200)
    shuffled = ref.Nodes(*[
        getattr(pool, f.name)[order]
        for f in ref.dataclasses.fields(ref.Nodes)
    ])
    for pod, count in (
        (ref.PodClass(250, 512 * MIB), 700),
        (ref.PodClass(100, 128 * MIB, anti_hostname=True), 90),
        (ref.PodClass(100, 128 * MIB, spread_max_skew=1), 150),
    ):
        got, unplaced = ref.schedule(shuffled, pod, count)
        assert unplaced == 0
        quota = count
        if pod.spread_max_skew:
            quota = np.bincount(shuffled.zone, weights=got, minlength=5)
            assert ref.zone_quota_error(quota, count) == 0
        lo, hi = ref.bands(shuffled, pod, quota)
        assert ref.outside(got, lo, hi) == 0
        assert (hi - lo).sum() > 0  # there were ties to break


def test_outside_counts_pods():
    lo, hi = np.array([1, 0, 2]), np.array([2, 0, 2])
    assert ref.outside([2, 0, 2], lo, hi) == 0
    assert ref.outside([0, 1, 4], lo, hi) == 1 + 1 + 2
    assert ref.zone_quota_error([5, 5, 6], 16) == 0
    assert ref.zone_quota_error([3, 7, 6], 16) == 2 + 1
