"""Batched assignment: priority-ordered greedy with on-device capacity
replay.

This is the TPU replacement for the serialized scheduleOne loop
(/root/reference/pkg/scheduler/scheduler.go:548): instead of popping one
pod, filtering/scoring all nodes, assuming, and repeating, a whole batch
of pods is solved in one jitted ``lax.scan``. Each scan step is one pod's
cycle -- feasibility mask, score matrix row, argmax -- and the carry
replays the cache ``assume`` (internal/cache/cache.go:344 AssumePod): the
chosen node's requested/non-zero-requested accumulators are bumped before
the next pod is considered, so a batch can never double-book capacity
(sequential-consistency inside the batch; SURVEY.md section 7 "hardest
parts (a)").

Pods must arrive in activeQ order (priority desc, then FIFO --
queuesort/priority_sort.go) so the device replay equals the sequential
order. Ties in the score argmax pick the lowest node index; the reference
reservoir-samples among ties (generic_scheduler.go:242), so decisions are
identical modulo tie-break RNG.

Sharding: all ``[N, ...]`` operands carry a node-axis sharding; under a
``jax.sharding.Mesh`` the per-step mask/score map is embarrassingly
parallel over node shards and XLA inserts the argmax all-reduce over ICI
(SURVEY.md section 2.5: data parallelism over the node axis).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops.scores import (
    balanced_allocation_score,
    least_allocated_score,
    most_allocated_score,
)
from kubernetes_tpu.tensors.node_tensor import NUM_FIXED_DIMS, PODS

NO_NODE = -1

import os as _os

# lax.scan unroll. unroll > 1 multiplies compiled-program size and
# GSPMD compile time (the 8-device CPU dryrun went 2.5min -> 5s at
# unroll=1); its effect on solve time was last looked at on an earlier
# machine and is not re-measured on this one (PERF.md "Decisions to
# re-measure").
SCAN_UNROLL = 1

_PODS_COL = PODS  # the pod-count dimension of the node tensor


def _fits(free: jnp.ndarray, pod_req: jnp.ndarray) -> jnp.ndarray:
    """Fit semantics (fit.go:181-252): the pod-count dimension is always
    checked; when every OTHER request is zero the reference short-circuits
    after it; otherwise EVERY dimension is checked strictly -- a zero
    request on an over-committed dimension (requested > allocatable,
    reachable via the nominated-pod overlay) still rejects, because the
    reference test is ``allocatable < requested + request``.

    free: [N, R] (allocatable - requested), pod_req: [R]. Returns [N] bool.
    """
    cols = jnp.arange(pod_req.shape[0])
    dim_ok = pod_req[None, :] <= free  # [N, R]
    # scalar/extended columns (>= NUM_FIXED_DIMS) are only checked when the
    # pod actually requests them: fit.go iterates podRequest.ScalarResources,
    # unlike the fixed cpu/memory/ephemeral checks which are unconditional
    scalar_skip = (cols >= NUM_FIXED_DIMS) & (pod_req == 0)
    dim_ok = dim_ok | scalar_skip[None, :]
    nonpods = cols != _PODS_COL
    all_zero = jnp.max(jnp.where(nonpods, pod_req, 0)) == 0
    return jnp.where(all_zero, dim_ok[:, _PODS_COL], dim_ok.all(axis=-1))


#: the profile's score plugins that the device models, by the field of
#: ``GreedyConfig`` that carries each one's weight
RESOURCE_SCORE_PLUGINS = {
    "NodeResourcesLeastAllocated": "least_allocated_weight",
    "NodeResourcesBalancedAllocation": "balanced_allocation_weight",
    "NodeResourcesMostAllocated": "most_allocated_weight",
}
#: resource scorers no device tier models: the pods of a profile that
#: scores with one keep the host path (scheduler/batch.py), so that
#: they are never scored as something else
UNMODELLED_RESOURCE_SCORE_PLUGINS = (
    "RequestedToCapacityRatio", "NodeResourceLimits",
)


@dataclass(frozen=True)
class GreedyConfig:
    """Device resource-scorer weights, the same in every tier (Pallas,
    the XLA scan, the mesh, ``host_greedy``). They come from the pod's
    PROFILE: ``from_score_weights`` reads the weights of the profile's
    enabled ``NodeResourcesLeastAllocated``, ``...BalancedAllocation``
    and ``...MostAllocated`` (``plugins.score`` of the
    KubeSchedulerConfiguration), and ``BatchScheduler`` asks it of each
    profile, so the default provider gives least 1, balanced 1, most 0
    and a bin-packing profile most 1 alone. A driver that passes
    ``new_scheduler(solver_config=...)`` overrides every profile
    (``benchmarks/runner.py``'s ``solver:`` rows). The label-dependent
    scorers (ImageLocality, preferred NodeAffinity, TaintToleration
    PreferNoSchedule, SelectorSpread, soft spread, NodePreferAvoidPods)
    ride the ``scoring`` tensors of greedy_assign_constrained
    (ops/scoring.py) with the profile's own weights."""

    least_allocated_weight: int = 1
    balanced_allocation_weight: int = 1
    most_allocated_weight: int = 0

    @classmethod
    def from_score_weights(cls, weights) -> Optional["GreedyConfig"]:
        """The config of a profile whose enabled score plugins and
        weights are ``weights`` (``Framework.score_plugin_weights``);
        None where it enables a resource scorer the device does not
        model."""
        if any(weights.get(name) for name in UNMODELLED_RESOURCE_SCORE_PLUGINS):
            return None
        return cls(**{
            field: int(weights.get(name, 0))
            for name, field in RESOURCE_SCORE_PLUGINS.items()
        })

    def label(self) -> str:
        """The rule in a few letters, for a metric's label:
        ``least+balanced``, ``most``, ``leastx2+balanced``, ``none``."""
        parts = [
            name if weight == 1 else f"{name}x{weight}"
            for name, weight in (
                ("least", self.least_allocated_weight),
                ("balanced", self.balanced_allocation_weight),
                ("most", self.most_allocated_weight),
            ) if weight
        ]
        return "+".join(parts) or "none"


def _combined_score(caps, nzr_state, p_nzr, config) -> jnp.ndarray:
    """Weighted resource score for one pod against node state of any
    leading shape: caps/nzr_state [..., 2], p_nzr [2]. Elementwise ops
    only, so the [N] batch form and the single-node form run the exact
    same arithmetic (bit-identical on device)."""
    score = None
    if config.least_allocated_weight:
        s = config.least_allocated_weight * least_allocated_score(
            caps, nzr_state, p_nzr[None, :]
        )[0]
        score = s if score is None else score + s
    if config.balanced_allocation_weight:
        s = config.balanced_allocation_weight * balanced_allocation_score(
            caps, nzr_state, p_nzr[None, :]
        )[0]
        score = s if score is None else score + s
    if config.most_allocated_weight:
        s = config.most_allocated_weight * most_allocated_score(
            caps, nzr_state, p_nzr[None, :]
        )[0]
        score = s if score is None else score + s
    if score is None:
        score = jnp.zeros(caps.shape[:-1], dtype=jnp.float32)
    return score


def _greedy_assign_impl(
    allocatable: jnp.ndarray,  # [N, R] int32
    requested: jnp.ndarray,  # [N, R] int32 (batch-start state)
    nzr: jnp.ndarray,  # [N, 2] int32 non-zero requested (cpu, memKiB)
    valid: jnp.ndarray,  # [N] bool
    pod_requests: jnp.ndarray,  # [B, R] int32, in solve order
    pod_nzr: jnp.ndarray,  # [B, 2] int32, in solve order
    static_mask: jnp.ndarray,  # [B, N] bool host-side label filters
    active: jnp.ndarray,  # [B] bool (False for padding rows)
    config: GreedyConfig = GreedyConfig(),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (assignment [B] int32 node index or NO_NODE,
    requested' [N, R], nzr' [N, 2]) -- the post-batch node state so the
    host can incrementally reconcile instead of repacking.

    (An incremental same-pod variant -- recompute only the previously
    chosen node's score/fit row under a lax.cond -- was slower on an
    earlier machine: the conditional defeats XLA's fusion of the step.
    Not re-measured on this one. The straight full-recompute scan
    stays.)"""
    caps = allocatable[:, :2]  # (milliCPU, memKiB) capacities for scorers
    n = allocatable.shape[0]
    node_iota = jnp.arange(n, dtype=jnp.int32)

    def step(carry, inputs):
        req_state, nzr_state = carry
        pod_req, p_nzr, smask, is_active = inputs

        free = allocatable - req_state
        fits = _fits(free, pod_req)
        feasible = fits & smask & valid
        score = _combined_score(caps, nzr_state, p_nzr, config)

        score = jnp.where(feasible, score, -jnp.inf)
        choice = jnp.argmax(score).astype(jnp.int32)
        placed = feasible.any() & is_active
        assignment = jnp.where(placed, choice, NO_NODE)

        chosen = (node_iota == choice) & placed
        req_state = req_state + chosen[:, None] * pod_req[None, :]
        nzr_state = nzr_state + chosen[:, None] * p_nzr[None, :]
        return (req_state, nzr_state), assignment

    (req_out, nzr_out), assignments = jax.lax.scan(
        step,
        (requested, nzr),
        (pod_requests, pod_nzr, static_mask, active),
        unroll=SCAN_UNROLL,
    )
    return assignments, req_out, nzr_out


def _greedy_assign_scored_impl(
    allocatable: jnp.ndarray,  # [N, R] int32
    requested: jnp.ndarray,  # [N, R] int32
    valid: jnp.ndarray,  # [N] bool
    pod_requests: jnp.ndarray,  # [B, R] int32, solve order
    static_mask: jnp.ndarray,  # [B, N] bool
    active: jnp.ndarray,  # [B] bool
    score_matrix: jnp.ndarray,  # [B, N] float32 precomputed (e.g. Sinkhorn)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-replay commit scan over a PRECOMPUTED score matrix (the
    Sinkhorn mode): feasibility is re-checked exactly per step, only the
    ranking comes from the matrix. Returns (assignment, requested')."""
    n = allocatable.shape[0]
    node_iota = jnp.arange(n, dtype=jnp.int32)

    def step(carry, inputs):
        req_state = carry
        pod_req, smask, is_active, row = inputs
        free = allocatable - req_state
        fits = _fits(free, pod_req)
        feasible = fits & smask & valid
        score = jnp.where(feasible, row, -jnp.inf)
        choice = jnp.argmax(score).astype(jnp.int32)
        placed = feasible.any() & is_active
        assignment = jnp.where(placed, choice, NO_NODE)
        chosen = (node_iota == choice) & placed
        req_state = req_state + chosen[:, None] * pod_req[None, :]
        return req_state, assignment

    req_out, assignments = jax.lax.scan(
        step, requested, (pod_requests, static_mask, active, score_matrix),
        unroll=SCAN_UNROLL,
    )
    return assignments, req_out


def _greedy_assign_spread_impl(
    allocatable: jnp.ndarray,  # [N, R] int32
    requested: jnp.ndarray,  # [N, R] int32
    nzr: jnp.ndarray,  # [N, 2] int32
    valid: jnp.ndarray,  # [N] bool
    pod_requests: jnp.ndarray,  # [B, R] int32, solve order
    pod_nzr: jnp.ndarray,  # [B, 2] int32
    static_mask: jnp.ndarray,  # [B, N] bool
    active: jnp.ndarray,  # [B] bool
    group_counts: jnp.ndarray,  # [G, V] int32 initial spread counts
    value_valid: jnp.ndarray,  # [G, V] bool
    node_value: jnp.ndarray,  # [G, N] int32 (-1 = ineligible)
    pod_groups: jnp.ndarray,  # [B, C] int32 (-1 pad)
    pod_max_skew: jnp.ndarray,  # [B, C] int32
    pod_self: jnp.ndarray,  # [B, C] int32
    pod_match: jnp.ndarray,  # [B, G] int32
    config: GreedyConfig = GreedyConfig(),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """greedy_assign + topology-spread filtering with within-batch count
    replay (ops/topology.py). Returns (assignment, requested', nzr',
    group_counts')."""
    caps = allocatable[:, :2]
    n = allocatable.shape[0]
    g_count = group_counts.shape[0]
    node_iota = jnp.arange(n, dtype=jnp.int32)
    group_iota = jnp.arange(g_count, dtype=jnp.int32)
    big = jnp.int32(1 << 20)

    def step(carry, inputs):
        req_state, nzr_state, counts = carry
        pod_req, p_nzr, smask, is_active, groups, skews, selfs, match = inputs

        free = allocatable - req_state
        fits = _fits(free, pod_req)
        feasible = fits & smask & valid

        # spread check per constraint slot (filtering.go:322 skew rule)
        def one_constraint(c):
            g = groups[c]
            safe_g = jnp.maximum(g, 0)
            counts_g = counts[safe_g]  # [V]
            min_v = jnp.min(
                jnp.where(value_valid[safe_g], counts_g, big)
            )
            vals = node_value[safe_g]  # [N]
            node_count = counts_g[jnp.clip(vals, 0, counts_g.shape[0] - 1)]
            ok = (vals >= 0) & (
                node_count + selfs[c] - min_v <= skews[c]
            )
            return jnp.where(g >= 0, ok, jnp.ones_like(ok))

        spread_ok = jax.vmap(one_constraint)(
            jnp.arange(groups.shape[0])
        ).all(axis=0)
        feasible = feasible & spread_ok

        score = jnp.zeros((n,), dtype=jnp.float32)
        if config.least_allocated_weight:
            score += config.least_allocated_weight * least_allocated_score(
                caps, nzr_state, p_nzr[None, :]
            )[0]
        if config.balanced_allocation_weight:
            score += (
                config.balanced_allocation_weight
                * balanced_allocation_score(caps, nzr_state, p_nzr[None, :])[0]
            )
        if config.most_allocated_weight:
            score += config.most_allocated_weight * most_allocated_score(
                caps, nzr_state, p_nzr[None, :]
            )[0]

        score = jnp.where(feasible, score, -jnp.inf)
        choice = jnp.argmax(score).astype(jnp.int32)
        placed = feasible.any() & is_active
        assignment = jnp.where(placed, choice, NO_NODE)

        chosen = (node_iota == choice) & placed
        req_state = req_state + chosen[:, None] * pod_req[None, :]
        nzr_state = nzr_state + chosen[:, None] * p_nzr[None, :]

        # count replay: the placed pod bumps every group it matches
        # (updateWithPod generalized to the batch)
        vals_at_choice = node_value[:, choice]  # [G]
        bump = (
            placed & (vals_at_choice >= 0) & (match > 0)
        ).astype(jnp.int32)
        counts = counts.at[
            group_iota, jnp.clip(vals_at_choice, 0, counts.shape[1] - 1)
        ].add(bump)
        return (req_state, nzr_state, counts), assignment

    (req_out, nzr_out, counts_out), assignments = jax.lax.scan(
        step,
        (requested, nzr, group_counts),
        (
            pod_requests, pod_nzr, static_mask, active,
            pod_groups, pod_max_skew, pod_self, pod_match,
        ),
        unroll=SCAN_UNROLL,
    )
    return assignments, req_out, nzr_out, counts_out


greedy_assign = partial(jax.jit, static_argnames=("config",))(
    _greedy_assign_impl
)
greedy_assign_scored = jax.jit(_greedy_assign_scored_impl)
greedy_assign_spread = partial(jax.jit, static_argnames=("config",))(
    _greedy_assign_spread_impl
)


@partial(jax.jit, static_argnames=("config",))
def greedy_assign_compact(
    allocatable: jnp.ndarray,
    requested: jnp.ndarray,
    nzr: jnp.ndarray,
    valid: jnp.ndarray,
    pod_requests: jnp.ndarray,
    pod_nzr: jnp.ndarray,
    mask_rows: jnp.ndarray,  # [U, N] deduplicated static-mask rows
    mask_index: jnp.ndarray,  # [B] int32 row index per pod
    active: jnp.ndarray,
    config: GreedyConfig = GreedyConfig(),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """greedy_assign with the static mask shipped deduplicated (see
    host_masks.static_mask_compact) and expanded by an on-device gather --
    the host->device transfer is O(U x N + B) instead of O(B x N)."""
    return _greedy_assign_impl(
        allocatable, requested, nzr, valid, pod_requests, pod_nzr,
        mask_rows[mask_index], active, config=config,
    )


#: family tuple sizes for the packed constrained layout (the order
#: matches greedy_assign_constrained's spread/affinity/scoring tuples)
_N_SPREAD = 7
_N_AFFINITY = 14
_N_SCORING = 20


def _unpack_buffer(buf: jnp.ndarray, layout: Tuple) -> dict:
    """Re-slice the single uploaded int32 buffer into named arrays
    (static offsets, free after fusion). ``kind`` restores dtypes: 'i'
    int32, 'b' bool, 'f' float32 (bitcast -- float tensors ride the
    int32 buffer bit-exactly); ``("Z*", fill)`` marks a ConstPiece
    materialized on device as a free constant."""
    arrs = {}
    off = 0
    for name, shape, kind in layout:
        if isinstance(kind, tuple):
            base, fill = kind
            dt = {"Zi": jnp.int32, "Zf": jnp.float32, "Zb": bool}[base]
            arrs[name] = jnp.full(shape, fill, dtype=dt)
            continue
        size = 1
        for d in shape:
            size *= d
        a = buf[off:off + size].reshape(shape)
        if kind == "b":
            a = a.astype(bool)
        elif kind == "f":
            a = jax.lax.bitcast_convert_type(a, jnp.float32)
        arrs[name] = a
        off += size
    return arrs


def shard_local_row_set(
    state: jnp.ndarray,  # [N, ...] node-axis leading
    idx: jnp.ndarray,  # [K] global row indices (>= N = padding, drops)
    rows: jnp.ndarray,  # [K, ...] replacement rows (replicated)
) -> jnp.ndarray:
    """Scatter ``rows`` onto ``state`` with shard-LOCAL arithmetic: each
    node row decides elementwise whether one of the K slots targets it,
    so under a node-axis sharding every shard resolves only its own rows
    against the small replicated (idx, rows) operands -- no cross-shard
    traffic (every global row index maps to exactly one shard-local
    row). The dense `.at[].set` scatter is kept on the single-device
    path; this formulation is the mesh twin's, where GSPMD must not be
    tempted into gather/scatter collectives."""
    n = state.shape[0]
    onehot = idx[None, :] == jnp.arange(n, dtype=idx.dtype)[:, None]  # [N, K]
    hit = onehot.any(axis=1)
    picked = rows[jnp.argmax(onehot, axis=1)].astype(state.dtype)  # [N, ...]
    mask = hit.reshape((n,) + (1,) * (state.ndim - 1))
    return jnp.where(mask, picked, state)


def _apply_row_patches(arrs, alloc, valid, req_state, nzr_state, shard_local):
    """Row-delta scatter (the steady-state patch path): changed node rows
    ride the same single upload buffer as (indices, rows) and are
    scattered onto the device-RESIDENT state here, so external churn
    costs O(changed rows) on the host-device link instead of a full
    [N, R] re-upload. Padding slots carry index >= N and drop."""
    setter = (
        shard_local_row_set
        if shard_local
        else (lambda s, i, r: s.at[i].set(r.astype(s.dtype), mode="drop"))
    )
    if "didx" in arrs:
        didx = arrs["didx"]
        req_state = setter(req_state, didx, arrs["dreq"])
        nzr_state = setter(nzr_state, didx, arrs["dnzr"])
    if "sidx" in arrs:
        alloc = setter(alloc, arrs["sidx"], arrs["salloc"])
        if "svalid" in arrs:
            # membership churn: retired/claimed row slots also flip the
            # resident valid mask (padding slots carry index >= N, drop)
            valid = setter(valid, arrs["sidx"], arrs["svalid"].astype(bool))
    return alloc, valid, req_state, nzr_state


@partial(
    jax.jit,
    static_argnames=("layout", "config", "mode", "use_pallas", "caps"),
)
def _solve_packed_jit(
    buf: jnp.ndarray,  # [T] int32: every uploaded piece, concatenated
    alloc_in,  # [N, R] int32 device-resident, or None when in buf
    valid_in,  # [N] bool device-resident, or None when in buf
    req_in,  # [N, R] int32 carried device state, or None when in buf
    nzr_in,  # [N, 2] int32 carried device state, or None when in buf
    layout: Tuple,  # static ((name, shape, kind), ...) describing buf slices
    config: GreedyConfig = GreedyConfig(),
    mode: str = "greedy",
    use_pallas: bool = False,
    caps=None,  # static pallas_constrained.Caps family specialization
):
    """Solve from a SINGLE uploaded buffer.

    Every device_put operand is its own host->device transfer (a
    basic batch has 5-9 arrays, a constrained batch ~40 family
    tensors); concatenating the per-batch upload into one int32 buffer
    makes it one transfer and this wrapper re-slices it on device
    (``_unpack_buffer``). The design was chosen on an earlier machine
    with a far slower link; what one transfer versus many costs on the
    current chip is not re-measured (PERF.md "Decisions to re-measure").
    Returns (assignment, requested', nzr', allocatable, valid) -- the
    last two so the caller can keep device-resident refs when they rode
    the buffer."""
    arrs = _unpack_buffer(buf, layout)
    alloc = arrs["alloc"] if "alloc" in arrs else alloc_in
    valid = arrs["valid"].astype(bool) if "valid" in arrs else valid_in
    req_state = arrs["req_state"] if "req_state" in arrs else req_in
    nzr_state = arrs["nzr_state"] if "nzr_state" in arrs else nzr_in
    alloc, valid, req_state, nzr_state = _apply_row_patches(
        arrs, alloc, valid, req_state, nzr_state, shard_local=False
    )
    return _packed_solve_tail(
        arrs, alloc, valid, req_state, nzr_state, config, mode,
        use_pallas, caps,
    )


def _packed_solve_tail(
    arrs, alloc, valid, req_state, nzr_state, config, mode, use_pallas,
    caps,
):
    """Solver dispatch shared by the single-device jit and its sharded
    mesh twin: pick the solver for (mode, use_pallas) and run it on the
    (possibly row-patched) node state."""
    pod_req = arrs["req"]
    pod_nzr_ = arrs["nzr"]
    midx = arrs["midx"]
    active = arrs["active"].astype(bool)
    rows = arrs["rows"].astype(bool)
    if mode == "constrained":
        spread = tuple(arrs[f"sp{i}"] for i in range(_N_SPREAD))
        affinity = tuple(arrs[f"af{i}"] for i in range(_N_AFFINITY))
        scoring = tuple(arrs[f"sc{i}"] for i in range(_N_SCORING))
        if use_pallas:
            # fused constrained kernel (ops/pallas_constrained.py),
            # specialized to the batch's active families via caps
            from kubernetes_tpu.ops.pallas_constrained import (
                pallas_constrained_solve,
            )

            c_solver = partial(pallas_constrained_solve, caps=caps)
        else:
            c_solver = greedy_assign_constrained
        assignment, req_out, nzr_out = c_solver(
            alloc, req_state, nzr_state, valid, pod_req, pod_nzr_, rows,
            midx, active, spread, affinity, scoring, config=config,
        )
        return assignment, req_out, nzr_out, alloc, valid
    if mode == "sinkhorn":
        solver = sinkhorn_assign
    elif use_pallas:
        # the fused Pallas solver (ops/pallas_solver.py)
        from kubernetes_tpu.ops.pallas_solver import pallas_greedy_solve

        solver = pallas_greedy_solve
    else:
        solver = greedy_assign_compact
    assignment, req_out, nzr_out = solver(
        alloc, req_state, nzr_state, valid, pod_req, pod_nzr_, rows, midx,
        active, config=config,
    )
    return assignment, req_out, nzr_out, alloc, valid


#: ship the [U, N] mask rows as their own column-sharded bool operand
#: only when the REPLICATED int32 payload (u * n * 4 * P bytes, what
#: the in-buffer form costs across the mesh) exceeds this -- below it
#: the rows stay in the single replicated buffer and the dispatch is
#: one transfer instead of two. The 1 MiB cutoff was set on an earlier
#: machine and is not re-measured on this one (PERF.md "Decisions to
#: re-measure")
MESH_MASK_SHARD_MIN_BYTES = 1 << 20


def mesh_pallas_candidate(mode: str, n_cap: int, mesh) -> bool:
    """Whether the mesh dispatch would run the shard_map'd Pallas tier
    for this (mode, shape): greedy batches only (the constrained and
    sinkhorn modes stay on the GSPMD twin), ``KTPU_MESH_PALLAS=0`` pins
    the twin-only behavior, and shard_map needs the node axis to split
    evenly over the mesh (NodeTensorCache pads to 128 rows, so any
    power-of-two mesh divides; a ragged capacity falls back to the
    twin instead of failing the shard_map trace). Shared with the
    degradation ladder (scheduler/batch.py ``_device_tiers``) so a
    shape that would never run the sharded kernel never gets a
    'pallas' tier attempt."""
    if mesh is None or "nodes" not in mesh.axis_names:
        return False
    p = int(mesh.devices.size)
    return (
        mode == "greedy"
        and _os.environ.get("KTPU_MESH_PALLAS", "1") != "0"
        and p > 1
        and n_cap % p == 0
    )


def mesh_shard_uses_kernel() -> bool:
    """Whether the shard_map tier's step is the fused Pallas candidate
    kernel (TPU backends) or its jnp twin (anywhere else). Shared with
    the tier ledger (scheduler/batch.py): a shard_map batch that ran
    without the kernel is not counted ``pallas``."""
    return jax.default_backend() == "tpu"


def _mesh_shard_solver(mesh, config: GreedyConfig, use_kernel: bool):
    """The shard_map'd solver tail (the mesh's Pallas tier): each device
    runs the whole-array greedy step on its OWN ``[N/P, R]`` shard of
    the resident carry, and the per-pod argmax reduces across shards
    with one psum-style best-of-shards combine -- a pmax of the shard
    best scores plus a pmin of the winning global index -- instead of
    the GSPMD twin's per-step full-score gather. Placement parity with
    the sequential oracle is exact: the per-shard arithmetic is the
    same elementwise fit/score math, and (max score, lowest global
    index) over shard-local (max, lowest-local-index) candidates equals
    the global argmax's lowest-index tie-break because shard i's global
    indices all precede shard i+1's.

    ``use_kernel`` routes the shard-local step through the fused Pallas
    candidate kernel (ops/pallas_solver.pallas_shard_candidate) on TPU
    backends -- one kernel call per step instead of the ~10-op XLA
    lowering -- and through the bit-identical jnp formulation
    elsewhere (CPU meshes: the win is the scalar combine replacing the
    per-step [N] gather)."""
    from jax.sharding import PartitionSpec as P

    big = jnp.int32(1 << 30)

    def body(alloc, req, nzr, valid, preq, pnzr, rows, midx, act):
        n_loc = alloc.shape[0]
        p_idx = jax.lax.axis_index("nodes")
        offset = (p_idx * n_loc).astype(jnp.int32)
        node_iota = jnp.arange(n_loc, dtype=jnp.int32)
        gidx_iota = node_iota + offset

        def combine(lbest, lidx, is_active):
            """The best-of-shards combine: max score, then lowest
            global node index among the shards holding it. Returns
            (assignment, chosen): the winner's bump (``chosen``) lands
            on exactly one shard's local rows."""
            gbest = jax.lax.pmax(lbest, "nodes")
            gidx = jax.lax.pmin(
                jnp.where(lbest == gbest, lidx, big), "nodes"
            )
            placed = (gbest > -jnp.inf) & is_active
            assignment = jnp.where(placed, gidx, NO_NODE).astype(jnp.int32)
            chosen = (gidx_iota == gidx) & placed
            return assignment, chosen

        if use_kernel:
            from kubernetes_tpu.ops.pallas_solver import (
                pallas_shard_candidate,
            )

            alloc_t = alloc.T
            valid_row = valid.astype(jnp.int32)[None, :]
            rows_i = rows.astype(jnp.int32)

            def step(carry, inputs):
                req_t, nzr_t = carry  # transposed [R, n_loc] / [2, n_loc]
                p_req, p_nzr, mi, is_active = inputs
                lbest, llocal = pallas_shard_candidate(
                    alloc_t, req_t, nzr_t, valid_row, rows_i,
                    p_req, p_nzr, mi, config=config,
                )
                assignment, chosen = combine(
                    lbest, llocal + offset, is_active
                )
                req_t = req_t + chosen[None, :] * p_req[:, None]
                nzr_t = nzr_t + chosen[None, :] * p_nzr[:, None]
                return (req_t, nzr_t), assignment

            (req_t, nzr_t), assignments = jax.lax.scan(
                step, (req.T, nzr.T), (preq, pnzr, midx, act),
                unroll=SCAN_UNROLL,
            )
            return assignments, req_t.T, nzr_t.T

        caps = alloc[:, :2]

        def step(carry, inputs):
            req_state, nzr_state = carry
            p_req, p_nzr, mi, is_active = inputs
            free = alloc - req_state
            fits = _fits(free, p_req)
            feasible = fits & rows[mi] & valid
            score = _combined_score(caps, nzr_state, p_nzr, config)
            masked = jnp.where(feasible, score, -jnp.inf)
            lbest = jnp.max(masked)
            lidx = jnp.min(jnp.where(masked == lbest, gidx_iota, big))
            assignment, chosen = combine(lbest, lidx, is_active)
            req_state = req_state + chosen[:, None] * p_req[None, :]
            nzr_state = nzr_state + chosen[:, None] * p_nzr[None, :]
            return (req_state, nzr_state), assignment

        (req_out, nzr_out), assignments = jax.lax.scan(
            step, (req, nzr), (preq, pnzr, midx, act),
            unroll=SCAN_UNROLL,
        )
        return assignments, req_out, nzr_out

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("nodes", None), P("nodes", None), P("nodes", None),
            P("nodes"), P(), P(), P(None, "nodes"), P(), P(),
        ),
        out_specs=(P(), P("nodes", None), P("nodes", None)),
        check_vma=False,
    )


#: one jitted sharded twin per Mesh (BatchScheduler holds one mesh for
#: its lifetime; tests/benches may build a few)
_MESH_PACKED_JIT: dict = {}


def make_mesh_packed_solver(mesh: "jax.sharding.Mesh"):
    """The sharded twin of ``_solve_packed_jit`` for one mesh: the same
    single-buffer layout contract, with the resident node state
    (requested/nzr/allocatable/valid) living SHARDED over the ``nodes``
    mesh axis and the steady-state row-delta scatter applied shard-
    locally (``shard_local_row_set``). Output shardings are pinned so
    one step's carry feeds the next step's inputs with no resharding
    (SNIPPETS.md pjit guidance: ``out_axis_resources`` of step k ==
    ``in_axis_resources`` of step k+1).

    The ``[U, N]`` static-mask rows leave the replicated buffer above
    ``MESH_MASK_SHARD_MIN_BYTES``: they arrive as their own bool
    operand already device_put COLUMN-sharded over the node axis
    (``solve_packed``), so each shard's host->device link carries only
    its ``[U, N/P]`` mask columns instead of the full replicated rows;
    below the cutoff (small clusters, where a second link round trip
    costs more than the bytes save) ``rows_in`` is None and the rows
    ride the buffer as before.

    ``use_pallas=True`` routes greedy batches through the shard_map'd
    Pallas tier (``_mesh_shard_solver``): each device runs the fused
    whole-array step on its own carry shard with a single
    best-of-shards combine per pod. One jitted instance per mesh,
    cached -- its signature count (BOTH tiers' layouts) is observable
    via ``mesh_packed_cache_size`` (the dryrun's zero-recompile
    probe)."""
    fn = _MESH_PACKED_JIT.get(mesh)
    if fn is not None:
        return fn
    from jax.sharding import NamedSharding, PartitionSpec as P

    node = NamedSharding(mesh, P("nodes"))
    node2d = NamedSharding(mesh, P("nodes", None))
    rows_sh = NamedSharding(mesh, P(None, "nodes"))

    @partial(
        jax.jit, static_argnames=("layout", "config", "mode", "use_pallas")
    )
    def solve(
        buf, rows_in, alloc_in, valid_in, req_in, nzr_in, layout,
        config=GreedyConfig(), mode="greedy", use_pallas=False,
    ):
        arrs = _unpack_buffer(buf, layout)
        alloc = arrs["alloc"] if "alloc" in arrs else alloc_in
        valid = arrs["valid"].astype(bool) if "valid" in arrs else valid_in
        req_state = arrs["req_state"] if "req_state" in arrs else req_in
        nzr_state = arrs["nzr_state"] if "nzr_state" in arrs else nzr_in
        alloc, valid, req_state, nzr_state = _apply_row_patches(
            arrs, alloc, valid, req_state, nzr_state, shard_local=True
        )
        # pin the node-axis layout: cold uploads (riding the replicated
        # buffer) reshard HERE once, steady dispatches enter already
        # sharded and the constraints are no-ops
        alloc = jax.lax.with_sharding_constraint(alloc, node2d)
        valid = jax.lax.with_sharding_constraint(valid, node)
        req_state = jax.lax.with_sharding_constraint(req_state, node2d)
        nzr_state = jax.lax.with_sharding_constraint(nzr_state, node2d)
        # below the MESH_MASK_SHARD_MIN_BYTES cutoff the rows rode the
        # replicated buffer (rows_in is None); above it they arrive as
        # their own column-sharded bool operand
        rows_arr = arrs["rows"] if rows_in is None else rows_in
        arrs["rows"] = jax.lax.with_sharding_constraint(
            rows_arr.astype(bool), rows_sh
        )
        if use_pallas and mode == "greedy":
            solver = _mesh_shard_solver(
                mesh, config, use_kernel=mesh_shard_uses_kernel(),
            )
            assignment, req_out, nzr_out = solver(
                alloc, req_state, nzr_state, valid,
                arrs["req"], arrs["nzr"], arrs["rows"], arrs["midx"],
                arrs["active"].astype(bool),
            )
        else:
            assignment, req_out, nzr_out, alloc, valid = (
                _packed_solve_tail(
                    arrs, alloc, valid, req_state, nzr_state, config,
                    mode, use_pallas=False, caps=None,
                )
            )
        req_out = jax.lax.with_sharding_constraint(req_out, node2d)
        nzr_out = jax.lax.with_sharding_constraint(nzr_out, node2d)
        return assignment, req_out, nzr_out, alloc, valid

    _MESH_PACKED_JIT[mesh] = solve
    return solve


def mesh_packed_cache_size(mesh) -> int:
    """Compiled-signature count of the mesh's packed solver: the
    multichip dryrun probes this before/after the steady phase so a
    second-signature regression (a mid-run recompile on the mesh hot
    path) fails loudly instead of silently eating a multi-second GSPMD
    compile inside a measured window."""
    fn = _MESH_PACKED_JIT.get(mesh)
    if fn is None:
        return 0
    return int(fn._cache_size())


def jit_cache_sizes(mesh=None) -> dict:
    """Compiled-signature counts of every jitted solver family the
    dispatch path can hit, keyed by a stable signature-family name.

    The runtime jit-cache watchdog (scheduler/batch.py) diffs this per
    batch: growth books ``scheduler_tpu_jit_compiles_total{signature}``
    and, once warmup has sealed the cache, fires a flight-recorder mark
    -- the production generalization of the test-only
    ``mesh_packed_cache_size`` probe. O(1) per family (a dict __len__
    on the jit cache), cheap enough to run after every solve."""
    out = {}
    for name, fn in (
        ("solve_packed", _solve_packed_jit),
        ("greedy_compact", greedy_assign_compact),
        ("greedy_constrained", greedy_assign_constrained),
    ):
        out[name] = int(fn._cache_size())
    if mesh is not None:
        out["mesh_packed"] = mesh_packed_cache_size(mesh)
    return out


@jax.jit
def apply_assignment_delta(
    req_state: jnp.ndarray,  # [N, R] int32 device-resident
    nzr_state: jnp.ndarray,  # [N, 2] int32 device-resident
    assignments: jnp.ndarray,  # [B] int32 node index or NO_NODE
    pod_req: jnp.ndarray,  # [B, R] int32, solve order
    pod_nzr: jnp.ndarray,  # [B, 2] int32, solve order
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter-add one solve's own assignment output onto the
    device-resident node state: every placed pod's request row lands on
    its chosen node row; NO_NODE / inactive-padding slots drop. JAX
    WRAPS negative indices even under ``mode="drop"``, so NO_NODE (-1)
    must be remapped to an out-of-bounds index first or every unplaced
    slot would land on the last node row. The device-tier scans apply
    this inside their own carry; this standalone jit keeps the carry
    warm when the assignments were produced OFF device (the host-greedy
    ladder tier), at an O(B*R) upload instead of a full [N, R]
    re-upload next dispatch."""
    idx = jnp.where(assignments < 0, req_state.shape[0], assignments)
    return (
        req_state.at[idx].add(pod_req, mode="drop"),
        nzr_state.at[idx].add(pod_nzr, mode="drop"),
    )


class ConstPiece:
    """Marker operand: uniformly filled with one value (absent
    constraint families are all-zero counts / all -1 sentinel ids).
    Materialized on device as a free constant inside the jit instead of
    riding the upload buffer -- they would otherwise ship ~1MB of
    constants host->device per constrained batch."""

    __slots__ = ("shape", "kind")

    def __init__(self, shape, dtype, fill) -> None:
        import numpy as _np

        self.shape = tuple(shape)
        if dtype == _np.float32:
            base = "f"
            fill = float(fill)
        elif dtype == _np.bool_:
            base = "b"
            fill = bool(fill)
        else:
            base = "i"
            fill = int(fill)
        self.kind = ("Z" + base, fill)

    @staticmethod
    def from_uniform(arr):
        """ConstPiece for a uniformly-filled array (asserts uniformity:
        a non-uniform 'noop' tensor silently changing semantics is
        exactly the bug this guards against)."""
        import numpy as _np

        arr = _np.asarray(arr)
        fill = arr.flat[0] if arr.size else 0
        assert (arr == fill).all(), "ConstPiece source is not uniform"
        return ConstPiece(arr.shape, arr.dtype, fill)


def _piece_kind(arr):
    import numpy as _np

    if isinstance(arr, ConstPiece):
        return arr.kind
    if arr.dtype == _np.float32:
        return "f"
    if arr.dtype == _np.bool_:
        return "b"
    return "i"


def caps_for_families(sp_t, af_t, sc_t, sp_present, af_present, sc_present):
    """Derive the kernel specialization Caps from the padded family
    tuples. Row usage comes from the small per-row/per-pod arrays,
    except ipa (scanned from its node-value rows); usage only matters
    for the rare escalation past DEFAULT_LIVE."""
    import numpy as _np

    from kubernetes_tpu.ops.pallas_constrained import _RP, live_caps

    def max_plus_one(a):
        a = _np.asarray(a)
        return 0 if a.size == 0 else int(a.max()) + 1

    def key_rows(a):
        return int(_np.count_nonzero(_np.asarray(a) >= 0))

    sp_used = max_plus_one(sp_t[3]) if sp_present else 0
    af_used = (
        (key_rows(af_t[2]), key_rows(af_t[7]), key_rows(af_t[11]))
        if af_present else (0, 0, 0)
    )
    if sc_present:
        rp_rows = _np.flatnonzero(
            (_np.asarray(sc_t[13]) >= 0).any(axis=1)
        )
        sc_used = (
            max_plus_one(sc_t[11]),
            max_plus_one(rp_rows),
            max_plus_one(sc_t[7]),
        )
        # the packer's wide shape (ops/scoring.WIDE_IPA_ROWS)
        sc_wide = sc_t[13].shape[0] > _RP
    else:
        sc_used = (0, 0, 0)
        sc_wide = False
    return live_caps(
        sp_present, af_present, sc_present, sp_used, af_used, sc_used,
        sc_wide,
    )


def _constrained_caps(pieces_by_name):
    """Caps from the HOST-side packed pieces (a ConstPiece family piece
    marks that family absent)."""

    def fam(prefix, count):
        arrs = [pieces_by_name.get(f"{prefix}{i}") for i in range(count)]
        present = not any(isinstance(a, ConstPiece) for a in arrs)
        return arrs, present

    sp_t, sp_present = fam("sp", _N_SPREAD)
    af_t, af_present = fam("af", _N_AFFINITY)
    sc_t, sc_present = fam("sc", _N_SCORING)
    return caps_for_families(
        sp_t, af_t, sc_t, sp_present, af_present, sc_present
    )


#: (mode, batch pad, node capacity) shapes whose compiled Pallas kernel
#: DISAGREED with the XLA scan on this process's compiler (the warm-up
#: canary, scheduler/batch.py). A kernel the compiler accepts is not
#: thereby right: on the v5e one constrained specialization returned
#: wrong placements, with no error, at some node counts past 16k until
#: its state initialization was repaired (PERF.md, PR 21).
#: Process-wide like the jit caches it describes.
_PALLAS_DISTRUST: set = set()


def distrust_pallas(mode: str, b: int, n_cap: int) -> None:
    """Take the Pallas tier out of the ladder for this (mode, shape):
    ``pallas_candidate`` answers False from here on, so the batch is
    offered to -- and booked under -- the XLA tier."""
    _PALLAS_DISTRUST.add((mode, b, n_cap))


def pallas_candidate(
    mode: str, b: int, n_cap: int, r_dims: int, u_rows: int
) -> bool:
    """Whether solve_packed would attempt the fused Pallas kernel for
    this (mode, shape): backend + env gate, the kernel's batch-shape
    tiling constraint, the warm-up canary's verdict (``distrust_pallas``)
    and the basic kernel's VMEM estimate against its
    budget (ops/pallas_solver.basic_vmem_bytes / BASIC_VMEM_BUDGET).
    The constrained kernel's exact per-family VMEM estimate may still
    downgrade inside solve_packed. Shared with the degradation ladder
    (scheduler/batch.py _device_tiers) so a shape that would never run
    the kernel never gets a 'pallas' tier attempt -- failures charge the
    tier that actually executed."""
    from kubernetes_tpu.ops.pallas_solver import (
        BASIC_VMEM_BUDGET,
        basic_vmem_bytes,
    )

    basic_vmem_ok = (
        basic_vmem_bytes(n_cap, r_dims, u_rows) <= BASIC_VMEM_BUDGET
    )
    return (
        mode in ("greedy", "constrained")
        and _os.environ.get("KTPU_PALLAS", "1") != "0"
        and jax.default_backend() == "tpu"
        and (b <= 1024 or b % 1024 == 0)
        and (mode == "constrained" or basic_vmem_ok)
        and (mode, b, n_cap) not in _PALLAS_DISTRUST
    )


def solve_packed(
    pieces,  # ordered [(name, ndarray)] to ride the buffer
    alloc_in,
    valid_in,
    req_in,
    nzr_in,
    config: GreedyConfig = GreedyConfig(),
    mode: str = "greedy",
    allow_pallas: bool = True,
    mesh=None,
):
    """Host-side companion of _solve_packed_jit: concatenates the pieces
    (int32 / bool / float32 -- see _solve_packed_jit's kind codes) and
    dispatches one upload + one solve. The greedy mode runs the fused
    Pallas kernel on TPU backends (KTPU_PALLAS=0 opts out; batch shapes
    the kernel's SMEM chunking can't tile fall back to the XLA scan).
    Constrained batches pick a family specialization (Caps) from the
    packed pieces and gate on an explicit VMEM estimate -- node count,
    mask-row diversity U, score-signature count S and zone count all
    contribute, so a batch that cannot fit falls back to the XLA scan
    instead of failing Mosaic compilation (ADVICE r4).

    ``mesh``: a ``jax.sharding.Mesh`` with a "nodes" axis routes the
    solve through the sharded twin (``make_mesh_packed_solver``): the
    batch buffer uploads replicated, the resident node state stays
    sharded over the node axis, and the ``[U, N]`` static-mask rows
    ship as their own bool operand COLUMN-sharded host-side (each
    shard uploads only its ``[U, N/P]`` columns -- at the 100k-node
    tier the replicated int32 rows were the dominant link payload).
    Greedy mesh batches additionally run the shard_map'd Pallas tier
    (``mesh_pallas_candidate``) unless ``allow_pallas`` is False (the
    ladder's xla tier) -- the single-core whole-array kernels
    themselves are still never attempted on a mesh."""
    import numpy as _np

    layout = tuple(
        (name, arr.shape, _piece_kind(arr)) for name, arr in pieces
    )
    b = next(s for n, s, _ in layout if n == "req")[0]
    if alloc_in is not None:
        n_cap, r_dims = alloc_in.shape
    else:
        n_cap, r_dims = next(s for n, s, _ in layout if n == "alloc")
    u_rows = next((s for n, s, _ in layout if n == "rows"), (8,))[0]
    use_pallas = (
        allow_pallas  # the degradation ladder's xla tier forces this off
        # when the pallas breaker is open (robustness/ladder.py)
        and mesh is None
        and pallas_candidate(mode, b, n_cap, r_dims, u_rows)
    )
    caps = None
    if mode == "constrained" and use_pallas:
        from kubernetes_tpu.ops.pallas_constrained import (
            VMEM_BUDGET,
            constrained_vmem_bytes,
        )

        by_name = dict(pieces)
        caps = _constrained_caps(by_name)
        u = next(s for n, s, _ in layout if n == "rows")[0]
        s_sig = next(s for n, s, _ in layout if n == "sc0")[0]
        z = next(s for n, s, _ in layout if n == "sc5")[1]
        v_sp = next(s for n, s, _ in layout if n == "sp0")[1]
        est = constrained_vmem_bytes(
            n_cap, r_dims, u, s_sig, z, v_sp, caps, chunk=min(b, 1024)
        )
        if est > VMEM_BUDGET:
            use_pallas = False
            caps = None

    def as_i32(arr):
        if arr.dtype == _np.float32:
            return _np.ascontiguousarray(arr).view(_np.int32)
        if arr.dtype == _np.int32:
            return arr
        return arr.astype(_np.int32)

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # the [U, N] static-mask rows ship OUTSIDE the replicated
        # buffer, as a bool array column-sharded over the node axis:
        # each shard's link carries [U, N/P] bytes instead of the
        # replicated 4-byte int32 rows (the next link cost at the
        # 100k-node tier). BUT only above MESH_MASK_SHARD_MIN_BYTES:
        # small clusters keep the rows inside the buffer (one
        # transfer per dispatch) and only above-threshold payloads
        # ship the second, sharded operand.
        # The decision is a pure shape function, so warmup and
        # dispatch always agree and each side keeps ONE jit signature
        # per U bucket.
        rows_host = next(arr for name, arr in pieces if name == "rows")
        p = int(mesh.devices.size)
        shard_rows = (
            rows_host.size * 4 * p > MESH_MASK_SHARD_MIN_BYTES
        )
        if shard_rows:
            rows_d = jax.device_put(
                _np.ascontiguousarray(rows_host, dtype=bool),
                NamedSharding(mesh, P(None, "nodes")),
            )
            mesh_layout = tuple(e for e in layout if e[0] != "rows")
        else:
            rows_d = None
            mesh_layout = layout
        buf = _np.concatenate(
            [
                as_i32(arr).ravel()
                for name, arr in pieces
                if not (shard_rows and name == "rows")
                and not isinstance(arr, ConstPiece)
            ]
        )
        buf_d = jax.device_put(buf, NamedSharding(mesh, P()))
        return make_mesh_packed_solver(mesh)(
            buf_d, rows_d, alloc_in, valid_in, req_in, nzr_in,
            layout=mesh_layout, config=config, mode=mode,
            use_pallas=(
                allow_pallas and mesh_pallas_candidate(mode, n_cap, mesh)
            ),
        )
    buf = _np.concatenate(
        [
            as_i32(arr).ravel()
            for _, arr in pieces
            if not isinstance(arr, ConstPiece)
        ]
    )
    buf_d = jax.device_put(buf)
    # a Pallas failure (Mosaic lowering, VMEM) RAISES: the degradation
    # ladder owns the step-down to the XLA scan, so the batch is booked
    # under the tier that actually ran it
    return _solve_packed_jit(
        buf_d, alloc_in, valid_in, req_in, nzr_in,
        layout=layout, config=config, mode=mode,
        use_pallas=use_pallas, caps=caps,
    )


def affinity_node_ok(
    counts_aff: jnp.ndarray,  # [Ra, V]
    counts_anti: jnp.ndarray,  # [Rt, V]
    counts_exist: jnp.ndarray,  # [Re, V]
    vals_aff: jnp.ndarray,  # [Ra, N] per-row node values (-1 absent)
    vals_anti: jnp.ndarray,  # [Rt, N]
    vals_exist: jnp.ndarray,  # [Re, N]
    aff_rows: jnp.ndarray,  # [C] the pod's affinity rows (-1 pad)
    self_match: jnp.ndarray,  # [] bool
    anti_rows: jnp.ndarray,  # [C]
    exist_match: jnp.ndarray,  # [Re] bool
) -> jnp.ndarray:
    """The three required-affinity Filter checks for ONE pod against all
    nodes, straight from interpodaffinity/filtering.go -- shared by the
    constrained scan and the differential tests. Returns [N] bool."""
    v = counts_aff.shape[1]

    # incoming affinity: every term's pair positive
    # (nodeMatchesAllTopologyTerms :420)
    aff_cnt = jnp.take_along_axis(
        counts_aff, jnp.clip(vals_aff, 0, v - 1), axis=1
    )  # [Ra, N]
    aff_pos = (vals_aff >= 0) & (aff_cnt > 0)
    safe_rows = jnp.clip(aff_rows, 0)
    row_ok = aff_pos[safe_rows]  # [C, N]
    aff_all = jnp.where((aff_rows >= 0)[:, None], row_ok, True).all(0)
    # first-pod escape (filtering.go:494): no match anywhere for the
    # pod's term-set AND the pod matches its own terms
    row_tot = counts_aff.sum(axis=1)
    total = jnp.sum(row_tot[safe_rows] * (aff_rows >= 0))
    aff_ok = aff_all | ((total == 0) & self_match)

    # incoming anti-affinity: any positive pair blocks
    # (nodeMatchesAnyTopologyTerm :437)
    anti_cnt = jnp.take_along_axis(
        counts_anti, jnp.clip(vals_anti, 0, v - 1), axis=1
    )
    anti_bad = (vals_anti >= 0) & (anti_cnt > 0)
    safe_anti = jnp.clip(anti_rows, 0)
    bad = jnp.where(
        (anti_rows >= 0)[:, None], anti_bad[safe_anti], False
    ).any(0)

    # existing pods' anti-affinity (:404)
    exist_cnt = jnp.take_along_axis(
        counts_exist, jnp.clip(vals_exist, 0, v - 1), axis=1
    )
    exist_bad = (vals_exist >= 0) & (exist_cnt > 0)
    blocked = (exist_match[:, None] & exist_bad).any(0)

    return aff_ok & ~bad & ~blocked


def row_node_values(
    node_value: jnp.ndarray, row_key: jnp.ndarray
) -> jnp.ndarray:
    """[R, N] per-row node values: -1 where the node lacks the row's
    topology key or the row is padding."""
    vals = node_value[jnp.clip(row_key, 0), :]
    return jnp.where(row_key[:, None] >= 0, vals, -1)


@partial(jax.jit, static_argnames=("config",))
def greedy_assign_constrained(
    allocatable: jnp.ndarray,  # [N, R] int32
    requested: jnp.ndarray,  # [N, R] int32
    nzr: jnp.ndarray,  # [N, 2] int32
    valid: jnp.ndarray,  # [N] bool
    pod_requests: jnp.ndarray,  # [B, R] int32, solve order
    pod_nzr: jnp.ndarray,  # [B, 2] int32
    mask_rows: jnp.ndarray,  # [U, N] deduplicated static-mask rows
    mask_index: jnp.ndarray,  # [B] int32
    active: jnp.ndarray,  # [B] bool
    spread: Tuple[jnp.ndarray, ...],
    affinity: Tuple[jnp.ndarray, ...],
    scoring: Tuple[jnp.ndarray, ...],
    config: GreedyConfig = GreedyConfig(),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The full constrained assignment scan: NodeResourcesFit + static
    label mask + hard topology spread (ops/topology.py) + required pod
    (anti-)affinity (ops/affinity.py) + the full default score plugin set
    (ops/scoring.py), with every constraint family's count tensors
    replayed in the scan carry so within-batch interactions match the
    sequential addNominatedPods semantics
    (interpodaffinity/filtering.go:75 updateWithPod,
    podtopologyspread/filtering.go:127 updateWithPod).

    ``spread``: (group_counts [G,V], value_valid [G,V], node_value [G,N],
    pod_groups [B,C], pod_max_skew [B,C], pod_self [B,C], pod_match [B,G])
    -- all-zero/-1 tensors make it a no-op.

    ``affinity``: the AffinityBatch arrays (ops/affinity.py docstring) --
    zero counts + all -1 rows make it a no-op.

    ``scoring``: the ScoreBatch arrays (ops/scoring.py docstring) --
    zero rows/weights make it a no-op. Normalizations (max-scale for
    preferred NodeAffinity, reversed for TaintToleration, zone-blended
    inversion for SelectorSpread, flipped-linear for soft spread) run
    per step over THAT step's feasible set, matching the reference's
    normalize-over-filtered-nodes semantics.
    """
    (sp_counts0, sp_value_valid, sp_node_value,
     sp_pod_groups, sp_pod_max_skew, sp_pod_self, sp_pod_match) = spread
    (af_node_value, af_counts_aff0, af_row_key_aff, af_pod_aff_rows,
     af_pod_self_match, af_pod_bump_aff,
     af_counts_anti0, af_row_key_anti, af_pod_anti_rows, af_pod_bump_anti,
     af_counts_exist0, af_row_key_exist, af_pod_exist_match,
     af_pod_bump_exist) = affinity
    (sc_direct, sc_nodeaff, sc_taint, sc_pod_sig,
     sc_sel_counts0, sc_zone_onehot, sc_zone_id, sc_pod_sel_group,
     sc_pod_sel_match, sc_soft_counts0, sc_soft_node_value,
     sc_pod_soft_groups, sc_pod_soft_match,
     sc_ipa_node_value, sc_ipa_counts0, sc_ipa_wcounts0,
     sc_pod_ipa_weight, sc_pod_ipa_match, sc_pod_ipa_bump,
     sc_weights) = scoring
    w_na, w_tt, w_sel, w_soft, w_ipa = (
        sc_weights[0], sc_weights[1], sc_weights[2], sc_weights[3],
        sc_weights[4],
    )
    big_soft = jnp.int32(1 << 20)
    soft_iota = jnp.arange(sc_soft_counts0.shape[0], dtype=jnp.int32)
    ipa_iota = jnp.arange(sc_ipa_counts0.shape[0], dtype=jnp.int32)
    v_ipa = sc_ipa_counts0.shape[1]
    ipa_live = (sc_ipa_node_value >= 0).any()

    static_mask = mask_rows[mask_index]
    caps = allocatable[:, :2]
    n = allocatable.shape[0]
    node_iota = jnp.arange(n, dtype=jnp.int32)
    g_count = sp_counts0.shape[0]
    group_iota = jnp.arange(g_count, dtype=jnp.int32)
    big = jnp.int32(1 << 20)
    v_aff = af_counts_aff0.shape[1]

    # per-row node values are static for the batch (rows bind to one
    # topology key each); -1 marks "node lacks the key" / padding rows
    vals_aff = row_node_values(af_node_value, af_row_key_aff)  # [Ra, N]
    vals_anti = row_node_values(af_node_value, af_row_key_anti)  # [Rt, N]
    vals_exist = row_node_values(af_node_value, af_row_key_exist)  # [Re, N]
    ra = jnp.arange(vals_aff.shape[0])
    rt = jnp.arange(vals_anti.shape[0])
    re_ = jnp.arange(vals_exist.shape[0])

    def step(carry, inputs):
        (req_state, nzr_state, sp_counts,
         counts_aff, counts_anti, counts_exist,
         sel_counts, soft_counts, ipa_counts, ipa_wcounts) = carry
        (pod_req, p_nzr, smask, is_active,
         groups, skews, selfs, match,
         aff_rows, self_match, bump_aff,
         anti_rows, bump_anti, exist_match, bump_exist,
         sig, sel_group, sel_match, soft_groups, soft_match,
         ipa_weight, ipa_match, ipa_bump) = inputs

        free = allocatable - req_state
        fits = _fits(free, pod_req)
        feasible = fits & smask & valid

        # -- topology spread (filtering.go:322 skew rule) -------------------
        def one_constraint(c):
            g = groups[c]
            safe_g = jnp.maximum(g, 0)
            counts_g = sp_counts[safe_g]
            min_v = jnp.min(jnp.where(sp_value_valid[safe_g], counts_g, big))
            vals = sp_node_value[safe_g]
            node_count = counts_g[jnp.clip(vals, 0, counts_g.shape[0] - 1)]
            ok = (vals >= 0) & (node_count + selfs[c] - min_v <= skews[c])
            return jnp.where(g >= 0, ok, jnp.ones_like(ok))

        spread_ok = jax.vmap(one_constraint)(
            jnp.arange(groups.shape[0])
        ).all(axis=0)

        aff_ok = affinity_node_ok(
            counts_aff, counts_anti, counts_exist,
            vals_aff, vals_anti, vals_exist,
            aff_rows, self_match, anti_rows, exist_match,
        )

        feasible = feasible & spread_ok & aff_ok

        score = jnp.zeros((n,), dtype=jnp.float32)
        if config.least_allocated_weight:
            score += config.least_allocated_weight * least_allocated_score(
                caps, nzr_state, p_nzr[None, :]
            )[0]
        if config.balanced_allocation_weight:
            score += (
                config.balanced_allocation_weight
                * balanced_allocation_score(caps, nzr_state, p_nzr[None, :])[0]
            )
        if config.most_allocated_weight:
            score += config.most_allocated_weight * most_allocated_score(
                caps, nzr_state, p_nzr[None, :]
            )[0]

        # -- non-resource score plugins (ops/scoring.py) --------------------
        # static direct rows (ImageLocality + NodePreferAvoidPods,
        # pre-weighted, no normalize)
        score = score + sc_direct[sig]
        # preferred NodeAffinity: max-scale normalize over the feasible set
        na_raw = sc_nodeaff[sig]
        na_max = jnp.max(jnp.where(feasible, na_raw, 0))
        score = score + jnp.where(
            na_max > 0,
            w_na * jnp.floor(
                100.0 * na_raw / jnp.maximum(na_max, 1).astype(jnp.float32)
            ),
            0.0,
        )
        # TaintToleration: reversed normalize (fewer intolerable
        # PreferNoSchedule taints => higher; max 0 => all 100)
        tt_raw = sc_taint[sig]
        tt_max = jnp.max(jnp.where(feasible, tt_raw, 0))
        tt_scaled = jnp.floor(
            100.0 * tt_raw / jnp.maximum(tt_max, 1).astype(jnp.float32)
        )
        score = score + w_tt * jnp.where(tt_max > 0, 100.0 - tt_scaled, 100.0)
        # SelectorSpread: inverted counts, zone-blended 2/3
        # (default_pod_topology_spread.go:107)
        sel_raw = sel_counts[jnp.maximum(sel_group, 0)]
        sel_feas = jnp.where(feasible, sel_raw, 0)
        sel_max_node = jnp.max(sel_feas)
        zsum = sel_feas @ sc_zone_onehot.astype(jnp.int32)  # [Z]
        have_zones = (feasible & (sc_zone_id >= 0)).any()
        sel_max_zone = jnp.max(zsum)
        f_node = jnp.where(
            sel_max_node > 0,
            100.0 * (sel_max_node - sel_raw)
            / jnp.maximum(sel_max_node, 1).astype(jnp.float32),
            100.0,
        )
        zs_n = zsum[jnp.clip(sc_zone_id, 0)]
        f_zone = jnp.where(
            sel_max_zone > 0,
            100.0 * (sel_max_zone - zs_n)
            / jnp.maximum(sel_max_zone, 1).astype(jnp.float32),
            100.0,
        )
        blended = jnp.where(
            have_zones & (sc_zone_id >= 0),
            f_node / 3.0 + (2.0 / 3.0) * f_zone,
            f_node,
        )
        score = score + jnp.where(
            sel_group >= 0, w_sel * jnp.floor(blended), 0.0
        )
        # soft topology spread: flipped-linear against (total - min) over
        # feasible eligible nodes (podtopologyspread/scoring.go:199)
        sg_safe = jnp.clip(soft_groups, 0)
        soft_nv = sc_soft_node_value[sg_safe]  # [C, N]
        soft_cnt = jnp.take_along_axis(
            soft_counts[sg_safe],
            jnp.clip(soft_nv, 0, soft_counts.shape[1] - 1),
            axis=1,
        )  # [C, N]
        rows_live = (soft_groups >= 0)[:, None]
        soft_raw = jnp.where(rows_live & (soft_nv >= 0), soft_cnt, 0).sum(0)
        soft_eligible = jnp.where(rows_live, soft_nv >= 0, True).all(0)
        has_soft = (soft_groups >= 0).any()
        dom = feasible & soft_eligible
        soft_total = jnp.sum(jnp.where(dom, soft_raw, 0))
        soft_min = jnp.where(
            dom.any(), jnp.min(jnp.where(dom, soft_raw, big_soft)), big_soft
        )
        soft_diff = (soft_total - soft_min).astype(jnp.float32)
        soft_score = jnp.where(
            soft_diff == 0,
            100.0,
            jnp.where(
                ~soft_eligible,
                0.0,
                jnp.floor(
                    100.0 * (soft_total - soft_raw)
                    / jnp.where(soft_diff == 0, 1.0, soft_diff)
                ),
            ),
        )
        score = score + jnp.where(has_soft, w_soft * soft_score, 0.0)

        # preferred inter-pod affinity (interpodaffinity/scoring.go):
        # raw(node) = sum_r weight_r * counts_r[val] (incoming terms)
        #           + sum_r match_r * wcounts_r[val] (existing pods'
        #             symmetric terms), normalized [min,max] -> [0,100]
        # over the feasible set with zero-seeded extremes (:294)
        ipa_cnt = jnp.take_along_axis(
            ipa_counts, jnp.clip(sc_ipa_node_value, 0, v_ipa - 1), axis=1
        )  # [Rp, N]
        ipa_wcnt = jnp.take_along_axis(
            ipa_wcounts, jnp.clip(sc_ipa_node_value, 0, v_ipa - 1), axis=1
        )
        row_has_val = sc_ipa_node_value >= 0
        ipa_raw = (
            jnp.where(row_has_val, ipa_cnt, 0.0) * ipa_weight[:, None]
            + jnp.where(row_has_val, ipa_wcnt, 0.0) * ipa_match[:, None]
        ).sum(0)  # [N]
        ipa_mn = jnp.minimum(
            0.0, jnp.min(jnp.where(feasible, ipa_raw, 0.0))
        )
        ipa_mx = jnp.maximum(
            0.0, jnp.max(jnp.where(feasible, ipa_raw, 0.0))
        )
        ipa_diff = ipa_mx - ipa_mn
        ipa_score = jnp.where(
            ipa_diff > 0,
            jnp.floor(100.0 * (ipa_raw - ipa_mn) / jnp.maximum(ipa_diff, 1e-9) + 1e-4),
            0.0,
        )
        score = score + jnp.where(ipa_live, w_ipa * ipa_score, 0.0)

        score = jnp.where(feasible, score, -jnp.inf)
        choice = jnp.argmax(score).astype(jnp.int32)
        placed = feasible.any() & is_active
        assignment = jnp.where(placed, choice, NO_NODE)

        chosen = (node_iota == choice) & placed
        req_state = req_state + chosen[:, None] * pod_req[None, :]
        nzr_state = nzr_state + chosen[:, None] * p_nzr[None, :]

        # spread count replay
        vals_at_choice = sp_node_value[:, choice]
        sp_bump = (
            placed & (vals_at_choice >= 0) & (match > 0)
        ).astype(jnp.int32)
        sp_counts = sp_counts.at[
            group_iota, jnp.clip(vals_at_choice, 0, sp_counts.shape[1] - 1)
        ].add(sp_bump)

        # score-family count replay
        placed_i32 = placed.astype(jnp.int32)
        sel_counts = sel_counts.at[:, choice].add(sel_match * placed_i32)
        soft_vc = sc_soft_node_value[:, choice]  # [Gt]
        soft_counts = soft_counts.at[
            soft_iota, jnp.clip(soft_vc, 0, soft_counts.shape[1] - 1)
        ].add(soft_match * (soft_vc >= 0) * placed_i32)

        # affinity count replay (updateWithPod :75 generalized)
        placed_i = placed.astype(jnp.int32)
        va = vals_aff[:, choice]
        counts_aff = counts_aff.at[ra, jnp.clip(va, 0)].add(
            bump_aff * (va >= 0) * placed_i
        )
        vt = vals_anti[:, choice]
        counts_anti = counts_anti.at[rt, jnp.clip(vt, 0)].add(
            bump_anti * (vt >= 0) * placed_i
        )
        ve = vals_exist[:, choice]
        counts_exist = counts_exist.at[re_, jnp.clip(ve, 0)].add(
            bump_exist * (ve >= 0) * placed_i
        )

        # preferred-affinity replay: the placed pod is an "existing pod"
        # for every later batch pod -- it bumps each row's match count
        # where it matches, and contributes its own terms' signed mass
        placed_f = placed.astype(jnp.float32)
        vi = sc_ipa_node_value[:, choice]  # [Rp]
        vi_ok = (vi >= 0).astype(jnp.float32)
        ipa_counts = ipa_counts.at[ipa_iota, jnp.clip(vi, 0)].add(
            ipa_match * vi_ok * placed_f
        )
        ipa_wcounts = ipa_wcounts.at[ipa_iota, jnp.clip(vi, 0)].add(
            ipa_bump * vi_ok * placed_f
        )

        carry = (req_state, nzr_state, sp_counts,
                 counts_aff, counts_anti, counts_exist,
                 sel_counts, soft_counts, ipa_counts, ipa_wcounts)
        return carry, assignment

    carry0 = (requested, nzr, sp_counts0,
              af_counts_aff0, af_counts_anti0, af_counts_exist0,
              sc_sel_counts0, sc_soft_counts0, sc_ipa_counts0,
              sc_ipa_wcounts0)
    xs = (
        pod_requests, pod_nzr, static_mask, active,
        sp_pod_groups, sp_pod_max_skew, sp_pod_self, sp_pod_match,
        af_pod_aff_rows, af_pod_self_match, af_pod_bump_aff,
        af_pod_anti_rows, af_pod_bump_anti, af_pod_exist_match,
        af_pod_bump_exist,
        sc_pod_sig, sc_pod_sel_group, sc_pod_sel_match,
        sc_pod_soft_groups, sc_pod_soft_match,
        sc_pod_ipa_weight, sc_pod_ipa_match, sc_pod_ipa_bump,
    )
    (req_out, nzr_out, *_rest), assignments = jax.lax.scan(
        step, carry0, xs, unroll=SCAN_UNROLL
    )
    return assignments, req_out, nzr_out


@partial(jax.jit, static_argnames=("config", "iters"))
def sinkhorn_assign(
    allocatable: jnp.ndarray,  # [N, R] int32
    requested: jnp.ndarray,  # [N, R] int32
    nzr: jnp.ndarray,  # [N, 2] int32
    valid: jnp.ndarray,  # [N] bool
    pod_requests: jnp.ndarray,  # [B, R] int32, solve order
    pod_nzr: jnp.ndarray,  # [B, 2] int32
    mask_rows: jnp.ndarray,  # [U, N] deduplicated static-mask rows
    mask_index: jnp.ndarray,  # [B] int32
    active: jnp.ndarray,  # [B] bool
    config: GreedyConfig = GreedyConfig(),
    iters: int = 50,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Globally-aware assignment for the churn/rebalance regime
    (BASELINE config #5): an entropic-OT transport plan over the whole
    batch (ops/sinkhorn.py) replaces the myopic per-step ranking, then the
    EXACT capacity-replay commit scan enforces feasibility step by step.
    Same signature family as greedy_assign_compact so the BatchScheduler
    can select it per profile (solver_mode="sinkhorn").

    Under a node-sharded mesh the row/column normalizations inside
    sinkhorn_plan become psum-style ICI collectives inserted by XLA
    (SURVEY.md section 2.5)."""
    from kubernetes_tpu.ops.sinkhorn import refine_scores

    sm = mask_rows[mask_index]  # [B, N]
    caps = allocatable[:, :2]

    # batch-start scores + feasibility feed the global plan; the commit
    # scan below re-checks fit exactly per step
    base = jnp.zeros(sm.shape, dtype=jnp.float32)
    if config.least_allocated_weight:
        base += config.least_allocated_weight * least_allocated_score(
            caps, nzr, pod_nzr
        )
    if config.balanced_allocation_weight:
        base += config.balanced_allocation_weight * balanced_allocation_score(
            caps, nzr, pod_nzr
        )
    if config.most_allocated_weight:
        base += config.most_allocated_weight * most_allocated_score(
            caps, nzr, pod_nzr
        )
    free = allocatable - requested
    feasible0 = jax.vmap(lambda pr: _fits(free, pr))(pod_requests)
    feasible0 = feasible0 & sm & valid[None, :]
    slots = jnp.maximum(
        (allocatable[:, _PODS_COL] - requested[:, _PODS_COL]).astype(
            jnp.float32
        ),
        0.0,
    )
    # Balance-seeking column marginals: raw free pod slots are ~110 per
    # node, so with pods << slots the capacity cap never binds and the
    # score prior concentrates mass (measured: post-churn utilization
    # std 14x worse than greedy, max node at 34% vs 2%). Capping each
    # column near the uniform share makes the transport plan spread --
    # the rebalance behavior this mode exists for -- while 2x headroom
    # keeps genuinely better nodes attractive.
    batch_mass = jnp.sum(active.astype(jnp.float32))
    # fair share is over the columns THIS batch can actually use: a
    # selector-masked batch confined to few nodes must not divide by the
    # whole cluster (that floors the cap at ~1 and starves the plan)
    usable = (slots > 0) & feasible0.any(axis=0)
    fair_share = 2.0 * batch_mass / jnp.maximum(
        jnp.sum(usable.astype(jnp.float32)), 1.0
    )
    slots = jnp.minimum(slots, jnp.maximum(fair_share, 1.0))
    refined = refine_scores(base, feasible0, slots, active, iters=iters)

    n = allocatable.shape[0]
    node_iota = jnp.arange(n, dtype=jnp.int32)

    def step(carry, inputs):
        req_state, nzr_state = carry
        pod_req, p_nzr, smask, is_active, row = inputs
        fits = _fits(allocatable - req_state, pod_req)
        feasible = fits & smask & valid
        # the plan row guides (1e4-scaled mass), but near-uniform plans
        # (identical pods x identical nodes) tie everywhere -- without
        # load feedback the argmax collapses every tie onto node 0
        # (measured: 110 pods on one node). The dynamic resource score
        # breaks ties WITH within-batch feedback, like the greedy scan.
        score_dyn = _combined_score(caps, nzr_state, p_nzr, config)
        score = jnp.where(feasible, row + score_dyn, -jnp.inf)
        choice = jnp.argmax(score).astype(jnp.int32)
        placed = feasible.any() & is_active
        assignment = jnp.where(placed, choice, NO_NODE)
        chosen = (node_iota == choice) & placed
        req_state = req_state + chosen[:, None] * pod_req[None, :]
        nzr_state = nzr_state + chosen[:, None] * p_nzr[None, :]
        return (req_state, nzr_state), assignment

    (req_out, nzr_out), assignments = jax.lax.scan(
        step, (requested, nzr), (pod_requests, pod_nzr, sm, active, refined),
        unroll=SCAN_UNROLL,
    )
    return assignments, req_out, nzr_out
