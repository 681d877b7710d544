"""Constrained Pallas kernel (ops/pallas_constrained.py) vs the XLA
constrained scan (ops/assignment.greedy_assign_constrained): randomized
differential parity in interpreter mode, over batches packed by the real
family packers exactly the way the BatchScheduler packs them."""

import math
import random

import numpy as np
import pytest

from kubernetes_tpu.cache.snapshot import new_snapshot
from kubernetes_tpu.ops.affinity import (
    noop_affinity_tensors,
    pack_affinity_batch,
    pad_affinity_tensors,
)
from kubernetes_tpu.ops.assignment import (
    NO_NODE,
    GreedyConfig,
    greedy_assign_constrained,
)
from kubernetes_tpu.ops.host_masks import static_mask_compact
from kubernetes_tpu.ops.pallas_constrained import pallas_constrained_solve
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
from test_pallas_solver import PARTIAL_BATCHES, last_active, partial_active

MASK_ROW_BUCKET = 8
POD_BUCKET = 64

DEFAULT_WEIGHTS = {
    "NodeAffinity": 1,
    "TaintToleration": 1,
    "DefaultPodTopologySpread": 1,
    "PodTopologySpread": 2,
    "InterPodAffinity": 1,
}


def _cluster(rng, n_nodes=24):
    nodes = []
    for i in range(n_nodes):
        nd = (
            make_node(f"node-{i}")
            .capacity(cpu="16", memory="32Gi", pods=32)
            .label("topology.kubernetes.io/zone", f"zone-{i % 3}")
            .label("rack", f"rack-{i % 5}")
            .label("kubernetes.io/hostname", f"node-{i}")
        )
        nodes.append(nd.obj())
    apps = ["a", "b", "c"]
    existing = []
    for i in range(rng.randrange(10, 30)):
        p = (
            make_pod(f"ex-{i}")
            .node(f"node-{rng.randrange(n_nodes)}")
            .container(cpu="200m", memory="256Mi")
            .labels(app=rng.choice(apps))
        )
        roll = rng.random()
        if roll < 0.25:
            p = p.pod_affinity(
                "topology.kubernetes.io/zone",
                {"app": rng.choice(apps)},
                anti=True,
            )
        elif roll < 0.4:
            p = p.preferred_pod_affinity(
                "rack",
                {"app": rng.choice(apps)},
                weight=rng.randrange(1, 20),
                anti=rng.random() < 0.5,
            )
        existing.append(p.obj())
    return existing, nodes


def _batch(rng, b=24):
    apps = ["a", "b", "c"]
    out = []
    for i in range(b):
        p = (
            make_pod(f"pod-{i}")
            .container(cpu="300m", memory="384Mi")
            .labels(app=rng.choice(apps))
        )
        roll = rng.random()
        if roll < 0.2:
            p = p.pod_affinity(
                "kubernetes.io/hostname",
                {"app": rng.choice(apps)},
                anti=True,
            )
        elif roll < 0.35:
            p = p.pod_affinity(
                "topology.kubernetes.io/zone", {"app": rng.choice(apps)}
            )
        elif roll < 0.5:
            p = p.spread_constraint(
                max_skew=rng.randrange(1, 4),
                topology_key="topology.kubernetes.io/zone",
                when_unsatisfiable="DoNotSchedule",
                match_labels={"app": p.obj().metadata.labels["app"]},
            )
        elif roll < 0.65:
            p = p.preferred_pod_affinity(
                "topology.kubernetes.io/zone",
                {"app": rng.choice(apps)},
                weight=rng.randrange(1, 30),
                anti=rng.random() < 0.4,
            )
        out.append(p.obj())
    return out


def _packed_problem(seed, b=24, n_nodes=24):
    """Mirror batch.py _dispatch_solve's packing for a constrained batch
    (no nominees, no gangs)."""
    rng = random.Random(seed)
    existing, nodes = _cluster(rng, n_nodes)
    snap = new_snapshot(existing, nodes)
    nt = NodeTensorCache().update(snap)
    pods = _batch(rng, b)

    batch = pack_pod_batch(pods, nt.dims)
    mask_rows, mask_index = static_mask_compact(pods, snap, nt)
    if batch.unsatisfiable.any():
        mask_rows = np.concatenate(
            [mask_rows, np.zeros((1, nt.capacity), dtype=bool)]
        )
        mask_index = mask_index.copy()
        mask_index[batch.unsatisfiable] = mask_rows.shape[0] - 1

    b = batch.size
    padded = POD_BUCKET * math.ceil(b / POD_BUCKET)
    order = batch.order
    req = np.zeros((padded, nt.dims.num_dims), dtype=np.int32)
    nzr = np.zeros((padded, 2), dtype=np.int32)
    midx = np.zeros(padded, dtype=np.int32)
    active = np.zeros(padded, dtype=bool)
    req[:b] = batch.requests[order]
    nzr[:b] = batch.non_zero_requests[order]
    midx[:b] = mask_index[order]
    active[:b] = True
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
        if sp is not None
        else noop_spread_tensors(padded, nt.capacity)
    )
    af_t = (
        pad_affinity_tensors(af, padded)
        if af is not None
        else noop_affinity_tensors(padded, nt.capacity)
    )
    sc_t = (
        pad_score_tensors(sc, padded)
        if sc is not None
        else noop_score_tensors(padded, nt.capacity)
    )
    common = (
        nt.allocatable, nt.requested, nt.non_zero_requested, nt.valid,
        req, nzr, rows, midx, active,
    )
    return common, tuple(sp_t), tuple(af_t), tuple(sc_t)


@pytest.mark.parametrize("seed", [0, 3, 11, 42])
def test_constrained_kernel_matches_xla(seed):
    common, sp_t, af_t, sc_t = _packed_problem(seed)
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig()
    )
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(), interpret=True
    )
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_noop_families_match_basic_path():
    """All-noop family tensors: the constrained kernel must agree with
    the XLA scan on a plain resource batch too."""
    common, _, _, _ = _packed_problem(7)
    padded = common[4].shape[0]
    n_cap = common[0].shape[0]
    sp_t = tuple(noop_spread_tensors(padded, n_cap))
    af_t = tuple(noop_affinity_tensors(padded, n_cap))
    sc_t = tuple(noop_score_tensors(padded, n_cap))
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig()
    )
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(), interpret=True
    )
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def _derive_caps(sp_t, af_t, sc_t):
    """The caps the solver would pick for this batch (all families
    treated as present -- _packed_problem always packs real batches)."""
    from kubernetes_tpu.ops.assignment import caps_for_families

    return caps_for_families(sp_t, af_t, sc_t, True, True, True)


@pytest.mark.parametrize("seed", [0, 11, 42])
def test_constrained_kernel_reduced_caps_matches_xla(seed):
    """The family-specialized kernel (reduced Caps, the VMEM-cap
    breaker) must agree with the XLA scan exactly like the full-caps
    kernel does."""
    common, sp_t, af_t, sc_t = _packed_problem(seed)
    caps = _derive_caps(sp_t, af_t, sc_t)
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig()
    )
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(),
        interpret=True, caps=caps,
    )
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_constrained_kernel_zero_caps_matches_basic():
    """All families absent -> Caps all zero: the specialized kernel
    degenerates to the plain greedy scan."""
    from kubernetes_tpu.ops.pallas_constrained import Caps

    common, _, _, _ = _packed_problem(7)
    padded = common[4].shape[0]
    n_cap = common[0].shape[0]
    sp_t = tuple(noop_spread_tensors(padded, n_cap))
    af_t = tuple(noop_affinity_tensors(padded, n_cap))
    sc_t = tuple(noop_score_tensors(padded, n_cap))
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig()
    )
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(),
        interpret=True, caps=Caps(0, 0, 0, 0, 0, 0, 0),
    )
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


# -- partial batches: the step loop ends at the last active slot -------------
# (the cases are tests/test_pallas_solver.py's)


@pytest.fixture(scope="module")
def full_batches():
    """One packed batch a size, every slot a real pod with its family
    rows: a case keeps a prefix of it active, so what a skipped step
    would have read is a pod's data and not zeros."""
    return {b: _packed_problem(3, b=b, n_nodes=96) for b in (256, 4096)}


@pytest.mark.parametrize("b,prefix,cleared", PARTIAL_BATCHES)
def test_partial_batch_matches_xla(full_batches, b, prefix, cleared):
    common, sp_t, af_t, sc_t = full_batches[b]
    active = partial_active(b, prefix, cleared)
    n_live = last_active(active)
    common = common[:8] + (active,)
    caps = _derive_caps(sp_t, af_t, sc_t)
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig()
    )
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(),
        interpret=True, caps=caps,
    )
    a2 = np.asarray(a2)
    np.testing.assert_array_equal(np.asarray(a1), a2)
    assert (a2[n_live:] == NO_NODE).all()
    assert (a2[~active] == NO_NODE).all()
    assert (a2[:n_live] != NO_NODE).any() or n_live == 0
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    if n_live == 0:  # all padding: the state it was given
        np.testing.assert_array_equal(np.asarray(r2), common[1])
        np.testing.assert_array_equal(np.asarray(z2), common[2])


def test_one_program_a_shape_whatever_the_batch_holds(full_batches):
    """Where the batch ends is read on the device from ``active``: it is
    no argument of the jitted solve, so a shape compiles once."""
    common, sp_t, af_t, sc_t = full_batches[4096]
    caps = _derive_caps(sp_t, af_t, sc_t)

    def solve(n_live):
        pallas_constrained_solve(
            *common[:8], partial_active(4096, n_live, ()), sp_t, af_t, sc_t, config=GreedyConfig(),
            interpret=True, caps=caps,
        )

    solve(4096)
    programs = pallas_constrained_solve._cache_size()
    for n_live in (0, 1, 42, 1023, 1024, 1025, 4095):
        solve(n_live)
    assert pallas_constrained_solve._cache_size() == programs
