"""Pallas solver kernel (ops/pallas_solver.py) vs the XLA scan
(ops/assignment.py): randomized differential parity in interpreter mode.

On the chip the kernel is the greedy packed path's default
(KTPU_PALLAS=0 opts out); measured 4.5x faster per solve than the XLA
lowering with bit-identical outputs.
"""

import numpy as np
import pytest

from kubernetes_tpu.ops.assignment import (
    NO_NODE,
    GreedyConfig,
    greedy_assign_compact,
)
from kubernetes_tpu.ops.pallas_solver import pallas_greedy_solve


def _random_problem(seed, n=256, b=256, r=6):
    rng = np.random.default_rng(seed)
    alloc = np.zeros((n, r), np.int32)
    alloc[:, 0] = rng.choice([2000, 4000, 8000], n)
    alloc[:, 1] = rng.choice([4, 8, 16], n) * 1024 * 1024
    alloc[:, 2] = rng.choice([0, 1 << 20], n)
    alloc[:, 3] = rng.choice([3, 40, 110], n)
    if r > 4:
        alloc[:, 4] = rng.choice([0, 8], n)  # scalar/extended resource
    requested = np.zeros_like(alloc)
    requested[:, 0] = rng.integers(0, 2000, n)
    requested[:, 3] = rng.integers(0, 3, n)
    nzr = np.zeros((n, 2), np.int32)
    nzr[:, 0] = requested[:, 0]
    nzr[:, 1] = rng.integers(0, 1 << 22, n)
    valid = rng.random(n) > 0.05
    pod_req = np.zeros((b, r), np.int32)
    pod_req[:, 0] = rng.choice([0, 100, 500, 1500], b)
    pod_req[:, 1] = rng.choice([0, 128, 512], b) * 1024
    pod_req[:, 3] = 1
    if r > 4:
        pod_req[:, 4] = rng.choice([0, 0, 0, 1], b)
    pod_nzr = np.maximum(pod_req[:, :2], [100, 200 * 1024]).astype(np.int32)
    rows = rng.random((8, n)) > 0.2
    midx = rng.integers(0, 8, b).astype(np.int32)
    active = rng.random(b) > 0.1
    return (
        alloc, requested, nzr, valid, pod_req, pod_nzr, rows, midx, active
    )


@pytest.mark.parametrize("seed", [0, 7, 21, 99])
@pytest.mark.parametrize(
    "config",
    [
        GreedyConfig(),
        GreedyConfig(
            least_allocated_weight=0,
            balanced_allocation_weight=0,
            most_allocated_weight=1,
        ),
    ],
)
def test_pallas_matches_xla_scan(seed, config):
    args = _random_problem(seed)
    a1, r1, z1 = greedy_assign_compact(*args, config=config)
    a2, r2, z2 = pallas_greedy_solve(*args, config=config, interpret=True)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(r1), np.asarray(r2))
    assert np.array_equal(np.asarray(z1), np.asarray(z2))


def test_multi_chunk_grid(seed=3):
    """Batches beyond one SMEM chunk walk the grid; state carries
    across chunks."""
    args = _random_problem(seed, n=256, b=2048, r=4)
    a1, r1, z1 = greedy_assign_compact(*args, config=GreedyConfig())
    a2, r2, z2 = pallas_greedy_solve(
        *args, config=GreedyConfig(), interpret=True
    )
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(r1), np.asarray(r2))
    assert np.array_equal(np.asarray(z1), np.asarray(z2))


@pytest.mark.parametrize("seed", [1, 13])
@pytest.mark.parametrize(
    "cfg",
    [
        GreedyConfig(),
        GreedyConfig(
            least_allocated_weight=0,
            balanced_allocation_weight=0,
            most_allocated_weight=1,
        ),
    ],
)
def test_shard_candidate_kernel_matches_jnp_step(seed, cfg):
    """The per-shard candidate kernel (the mesh Pallas tier's TPU step
    body, ops/pallas_solver.pallas_shard_candidate) vs the jnp step the
    shard_map twin runs on non-TPU backends: identical (best score,
    lowest-index argmax) per pod over randomized shard-local state --
    the bit-parity that makes the cross-shard combine exact on either
    body."""
    import jax.numpy as jnp

    from kubernetes_tpu.ops.assignment import _combined_score, _fits
    from kubernetes_tpu.ops.pallas_solver import pallas_shard_candidate

    n, r, u = 128, 6, 8
    (alloc, requested, nzr, valid, pod_req, pod_nzr, rows, midx,
     _active) = _random_problem(seed, n=n, b=16, r=r)
    for k in range(16):
        free = jnp.asarray(alloc - requested)
        fits = _fits(free, jnp.asarray(pod_req[k]))
        feasible = (
            fits & jnp.asarray(rows[midx[k]]) & jnp.asarray(valid)
        )
        score = _combined_score(
            jnp.asarray(alloc[:, :2]), jnp.asarray(nzr),
            jnp.asarray(pod_nzr[k]), cfg,
        )
        masked = jnp.where(feasible, score, -jnp.inf)
        best_t = float(jnp.max(masked))
        idx_t = int(jnp.min(jnp.where(
            masked == jnp.max(masked), jnp.arange(n), 1 << 30
        )))
        best_k, idx_k = pallas_shard_candidate(
            jnp.asarray(alloc.T), jnp.asarray(requested.T),
            jnp.asarray(nzr.T),
            jnp.asarray(valid.astype(np.int32))[None, :],
            jnp.asarray(rows.astype(np.int32)),
            jnp.asarray(pod_req[k]), jnp.asarray(pod_nzr[k]),
            jnp.asarray(np.int32(midx[k])),
            config=cfg, interpret=True,
        )
        if bool(jnp.any(feasible)):
            assert float(best_k) == best_t and int(idx_k) == idx_t, (
                seed, k, float(best_k), best_t, int(idx_k), idx_t
            )
        else:
            assert float(best_k) == best_t == float("-inf")


# -- partial batches: the step loop ends at the last active slot -------------

#: (b, live prefix, slots cleared inside it): the chunk edges of 1,024
#: crossed from both sides at the cells' 4,096, the gang fix-up's cleared
#: slots (the last of them trailing, so the loop ends before the prefix
#: does), and the binary's default batch
PARTIAL_BATCHES = [
    pytest.param(4096, n_live, (), id=f"b4096-live{n_live}")
    for n_live in (0, 1, 42, 1023, 1024, 1025, 4095, 4096)
] + [
    pytest.param(4096, 1500, (0, 7, 1023, 1024, 1400, 1498, 1499),
                 id="b4096-live1500-cleared"),
    pytest.param(256, 42, (), id="b256-live42"),
]


def partial_active(b, prefix, cleared):
    active = np.zeros(b, bool)
    active[:prefix] = True
    active[list(cleared)] = False
    return active


def last_active(active):
    return int(np.flatnonzero(active).max()) + 1 if active.any() else 0


@pytest.mark.parametrize("b,prefix,cleared", PARTIAL_BATCHES)
def test_partial_batch_matches_xla_scan(b, prefix, cleared):
    args = list(_random_problem(5, n=128, b=b, r=4))
    active = args[8] = partial_active(b, prefix, cleared)
    n_live = last_active(active)
    a1, r1, z1 = greedy_assign_compact(*args, config=GreedyConfig())
    a2, r2, z2 = pallas_greedy_solve(
        *args, config=GreedyConfig(), interpret=True
    )
    a2 = np.asarray(a2)
    assert np.array_equal(np.asarray(a1), a2)
    assert (a2[n_live:] == NO_NODE).all()
    assert (a2[~active] == NO_NODE).all()
    assert (a2[:n_live] != NO_NODE).any() or n_live == 0
    assert np.array_equal(np.asarray(r1), np.asarray(r2))
    assert np.array_equal(np.asarray(z1), np.asarray(z2))
    if n_live == 0:  # all padding: the state it was given
        assert np.array_equal(np.asarray(r2), args[1])
        assert np.array_equal(np.asarray(z2), args[2])


def test_one_program_a_shape_whatever_the_batch_holds():
    """Where the batch ends is read on the device from ``active``: it is
    no argument of the jitted solve, so a shape compiles once."""
    from kubernetes_tpu.ops.assignment import jit_cache_sizes, solve_packed

    args = list(_random_problem(5, n=128, b=4096, r=4))
    names = ("alloc", "req_state", "nzr_state", "valid", "req", "nzr",
             "rows", "midx", "active")

    def solve(n_live):
        args[8] = partial_active(4096, n_live, ())
        pallas_greedy_solve(*args, config=GreedyConfig(), interpret=True)
        # the packed solve around the kernel (on a CPU its XLA tier)
        solve_packed(
            [(k, np.asarray(a)) for k, a in zip(names, args)],
            None, None, None, None,
        )

    solve(4096)
    kernel_programs = pallas_greedy_solve._cache_size()
    packed_programs = jit_cache_sizes()
    for n_live in (0, 1, 42, 1023, 1024, 1025, 4095):
        solve(n_live)
    assert pallas_greedy_solve._cache_size() == kernel_programs
    assert jit_cache_sizes() == packed_programs


# -- bin-packing: every tier against the plain reference ----------------------


def _binpack_problem(seed, r, n=128, zones=4):
    """Eight-unit nodes that hold 0-8 units, and pods of 1, 2, 4 and 8
    units, one size a zone, in seeded order. At ``r`` 5 a unit is a GPU
    and the fifth column alone refuses a ninth unit (cpu and memory
    would take it); at ``r`` 4 the node has cpu for eight and no GPUs."""
    rng = np.random.default_rng(seed)
    mib = 1 << 20
    alloc = np.zeros((n, r), np.int32)
    alloc[:, 0] = 32000 if r > 4 else 28000
    alloc[:, 1] = 64 * 1024 * 1024  # KiB
    alloc[:, 3] = 110
    unit = np.zeros(r, np.int32)
    unit[:2] = 3500, 7000 * 1024
    if r > 4:
        alloc[:, 4] = 8
        unit[4] = 1
    held = rng.integers(0, 9, n)
    requested = held[:, None].astype(np.int32) * unit[None, :]
    requested[:, 3] = held
    nzr = np.ascontiguousarray(requested[:, :2])
    zone = np.arange(n) % zones
    sizes = [1, 2, 4, 8][:zones]
    pods = [(z, s) for z, s in enumerate(sizes)
            for _ in range(int(rng.integers(4, 40)))]
    pods = [pods[int(k)] for k in rng.permutation(len(pods))]
    b = 256
    pod_req = np.zeros((b, r), np.int32)
    midx = np.zeros(b, np.int32)
    active = np.zeros(b, bool)
    for k, (z, s) in enumerate(pods):
        pod_req[k] = unit * s
        pod_req[k, 3] = 1
        midx[k] = z
        active[k] = True
    rows = np.zeros((8, n), bool)
    for z in range(zones):
        rows[z] = zone == z
    device = (alloc, requested, nzr, np.ones(n, bool), pod_req,
              np.ascontiguousarray(pod_req[:, :2]), rows, midx, active)
    # the same problem in the reference's columns: bytes, no ephemeral
    keep = [0, 1, 3] + ([4] if r > 4 else [])
    scale = np.array([1, 1024, 1] + ([1] if r > 4 else []), np.int64)
    return device, pods, sizes, zone, unit[keep] * scale, (
        alloc[:, keep].astype(np.int64) * scale,
        requested[:, keep].astype(np.int64) * scale,
    )


def _tier(name):
    from kubernetes_tpu.robustness.ladder import host_greedy_assign

    if name == "pallas":
        return lambda *a, config: pallas_greedy_solve(
            *a, config=config, interpret=True)
    if name == "xla":
        return greedy_assign_compact
    return host_greedy_assign


@pytest.mark.parametrize("seed", [43, 4343])
@pytest.mark.parametrize("r", [4, 5])
@pytest.mark.parametrize("tier", ["pallas", "xla", "host_greedy"])
def test_every_tier_packs_as_the_binpack_reference(tier, r, seed):
    """MostAllocated alone, with node-selector mask rows: each tier's
    placements are the plain reference's (``chipbench/
    binpack_reference.py``: both break ties by the lowest index, and
    pools are disjoint, so node by node)."""
    from chipbench import binpack_reference

    device, pods, sizes, zone, unit, (cap, used) = _binpack_problem(seed, r)
    packing = GreedyConfig(0, 0, 1)
    assigned = np.asarray(_tier(tier)(*device, config=packing)[0])
    n = cap.shape[0]
    nodes = binpack_reference.Nodes(cap, used)
    for z, size in enumerate(sizes):
        mine = [k for k, (pz, _) in enumerate(pods) if pz == z]
        got = np.bincount(
            assigned[mine][assigned[mine] != NO_NODE], minlength=n
        )
        pod = unit * size
        pod[2] = 1
        want, unplaced = binpack_reference.schedule(
            nodes, pod, len(mine), zone == z
        )
        assert np.array_equal(got, want), (tier, r, z)
        assert int((assigned[mine] == NO_NODE).sum()) == unplaced
        assert binpack_reference.unexplained(
            nodes, pod, len(mine), got, zone == z) == 0
        if r > 4:  # eight GPUs a node, whatever cpu and memory allow
            assert ((used[:, 3] + got * size) <= 8).all()
