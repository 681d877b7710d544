"""Fused Pallas victim-search kernel (the device preemption hot path).

The XLA scan in ops/preemption.py re-simulates selectVictimsOnNode
(generic_scheduler.go:940) for every failed pod over every node; this
kernel restructures that into:

1. a per-CLASS prologue -- pods sharing (priority, request row,
   candidate mask) see identical per-node victim sets, so the full
   [V, N] remove-all + reprieve simulation and the 6-rule pick keys
   (pickOneNodeForPreemption, :721) are computed ONCE per class into
   VMEM scratch, not per pod;
2. a cheap per-pod step -- lexicographic narrowing over the cached
   keys (a handful of [1, N] reductions), then an INCREMENTAL fixup of
   the chosen lane only: the nomination changes one node's state, so
   only that node's victim set and keys need recomputing
   (addNominatedPods semantics, generic_scheduler.go:535). The node's
   victim columns arrive via ONE contiguous DMA from an [N, X]
   row-major copy kept in HBM (dynamic-lane extracts would cost a full
   cross-lane reduction per row), and the reprieve replays in pure
   scalar arithmetic; only the key writebacks touch [1, N] vectors.

A homogeneous preemption wave (the burst case: N identical-priority
pods) pays the full simulation once and ~O(N) per pod after that,
instead of O(V x N) per pod.

Dim specialization: fit only evaluates ``adims`` -- the union of the
wave's requested dims, nomination dims, any over-committed dims and the
pod-count dim. Dims outside that set have zero pod request and
provably non-negative free capacity (victim removal only increases
free), so skipping them is exact; a typical cpu+mem wave models 3 of
the 8 resource rows.

Differential coverage: tests/test_preemption_device.py runs the FULL
wrapper (chunk chaining, candidate dedup, bitmask reassembly) in
interpreter mode against the host oracle.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubernetes_tpu.ops.pallas_solver import COMPILER_PARAMS
from kubernetes_tpu.tensors.node_tensor import NUM_FIXED_DIMS, PODS

_BIG = 1 << 30
_IMAX = (1 << 31) - 1

# scratch key-row indices (keys_i [K_I, N] int32). Rule-5 start times
# compare as raw int32 f32-bit patterns: start_rel is non-negative
# (min-subtracted), and for non-negative IEEE floats the bit pattern is
# order-isomorphic to the value, so min/max in int space equals the
# reference's float comparisons exactly.
_K_FEAS = 0
_K_FPRIO = 1
_K_SHI = 2
_K_SLO = 3
_K_VCOUNT = 4
_K_VLO = 5
_K_VHI = 6
_K_EARLIEST = 7
_K_ROWS = 8


def _preempt_kernel(
    podreq_ref,    # SMEM [chunk*R] int32 (full R -- state carry dims)
    podprio_ref,   # SMEM [chunk] int32
    midx_ref,      # SMEM [chunk] int32 candidate-row index
    active_ref,    # SMEM [chunk] int32
    nomprio_ref,   # SMEM [M] int32 (pre-existing nominations)
    alloc_ref,     # VMEM [A, N] int32 (active dims only)
    prio_ref,      # VMEM [V, N] int32
    start_ref,     # VMEM [V, N] int32 (f32 bit patterns, see above)
    vreq_ref,      # VMEM [V*A, N] int32 (victim-major: row v*A+d)
    vreq2_ref,     # VMEM [A*V, N] int32 (dim-major: row d*V+v)
    vactive_ref,   # VMEM [V, N] int32
    cand_rows_ref,  # VMEM [U, N] int32 candidate masks (dedup)
    nomreq_ref,    # VMEM [M*A, N] int32 (nomination m's request, adims)
    cols_ref,      # HBM [N, 1, X_pad] int32 row-major victim columns
    state_in_ref,  # VMEM [R, N] int32 (aliased -> state_ref)
    chosen_ref,    # OUT SMEM [chunk] int32
    vmask_lo_ref,  # OUT SMEM [chunk] int32 victim bits 0..15
    vmask_hi_ref,  # OUT SMEM [chunk] int32 victim bits 16..31
    state_ref,     # OUT VMEM [R, N] int32 (nomination carry)
    keys_i,        # scratch VMEM [K_ROWS, N] int32
    st0_s,         # scratch VMEM [A, N] int32 (state0 on active dims)
    colrow_s,      # scratch SMEM [1, X_pad] int32 (DMA landing row)
    dma_sem,       # scratch DMA semaphore
    *,
    chunk: int,
    r: int,
    v: int,
    m: int,
    adims: Tuple[int, ...],
):
    n = alloc_ref.shape[1]
    a = len(adims)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    alloc = alloc_ref[:, :]
    prio = prio_ref[:, :]
    start = start_ref[:, :]
    vactive = vactive_ref[:, :] > 0
    imax = jnp.int32(_IMAX)
    imin = jnp.int32(-(1 << 31) + 1)

    # the nomination carry lives in the output ref; its initial value is
    # copied in here rather than left to the input/output aliasing (see
    # pallas_constrained._constrained_kernel)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        state_ref[:, :] = state_in_ref[:, :]

    def body(t, _):
        pod_prio = podprio_ref[t]
        is_active = active_ref[t] > 0

        # per-pod request on active dims as an [A, 1] column
        req_col = jnp.concatenate(
            [
                jnp.full((1, 1), podreq_ref[t * r + d], jnp.int32)
                for d in adims
            ],
            axis=0,
        )
        zero_col = req_col == 0
        pods_row = jnp.concatenate(
            [
                jnp.full((1, 1), 1 if d == PODS else 0, jnp.int32)
                for d in adims
            ],
            axis=0,
        ) > 0
        # scalar/extended dims (>= NUM_FIXED_DIMS) pass when unrequested
        # (assignment._fits / fit.go: only requested scalar resources
        # are checked, even on an over-committed node)
        scalar_skip = jnp.concatenate(
            [
                jnp.full(
                    (1, 1), 1 if d >= NUM_FIXED_DIMS else 0, jnp.int32
                )
                for d in adims
            ],
            axis=0,
        ) > 0
        all_zero = jnp.all(zero_col | pods_row)

        def fits(free):  # [A, N or 1] -> [1, same]
            ok = (req_col <= free) | (scalar_skip & zero_col)
            ok_all = jnp.min(ok.astype(jnp.int32), axis=0, keepdims=True)
            ok_pods = jnp.sum(
                jnp.where(pods_row, ok.astype(jnp.int32), 0),
                axis=0, keepdims=True,
            )
            return jnp.where(all_zero, ok_pods, ok_all) > 0

        # -- class change? (t==0, or any of prio/request/candidate-row
        # differs from the previous pod) -> rebuild the key cache ------
        same = jnp.int32(1)
        prev = jnp.maximum(t - 1, 0)
        same = same * (podprio_ref[prev] == pod_prio).astype(jnp.int32)
        same = same * (midx_ref[prev] == midx_ref[t]).astype(jnp.int32)
        for d in range(r):
            same = same * (
                podreq_ref[prev * r + d] == podreq_ref[t * r + d]
            ).astype(jnp.int32)
        rebuild = (t == 0) | (same == 0)

        @pl.when(rebuild)
        def _prologue():
            cand = cand_rows_ref[pl.ds(midx_ref[t], 1), :] > 0  # [1, N]
            eligible = vactive & (prio < pod_prio)  # [V, N]
            elig_i = eligible.astype(jnp.int32)

            # nominations with priority >= this pod's ride the state
            st0 = jnp.concatenate(
                [
                    state_ref[d:d + 1, :]
                    for d in adims
                ],
                axis=0,
            )
            for k in range(m):
                sel = (nomprio_ref[k] >= pod_prio).astype(jnp.int32)
                st0 = st0 + sel * nomreq_ref[k * a:(k + 1) * a, :]
            st0_s[:, :] = st0

            removed = jnp.concatenate(
                [
                    jnp.sum(
                        elig_i * vreq2_ref[d * v:(d + 1) * v, :],
                        axis=0, keepdims=True,
                    )
                    for d in range(a)
                ],
                axis=0,
            )  # [A, N]
            st = st0 - removed
            feas = fits(alloc - st) & cand  # [1, N]

            # reprieve in MoreImportantPod order (no PDBs on this path):
            # re-add each victim, keep it when the preemptor still fits
            victims = []
            for vi in range(v):
                sel = elig_i[vi:vi + 1, :]
                vr = vreq_ref[vi * a:(vi + 1) * a, :]  # [A, N]
                cand_state = st + sel * vr
                keep = fits(alloc - cand_state) & (sel > 0)
                st = jnp.where(keep, cand_state, st)
                victims.append((sel > 0) & ~keep)
            vic = jnp.concatenate(
                [vx.astype(jnp.int32) for vx in victims], axis=0
            )  # [V, N]
            vic_b = vic > 0

            # -- pickOneNodeForPreemption key rows -----------------------
            vcount = jnp.sum(vic, axis=0, keepdims=True)  # [1, N]
            # 2. lowest first-victim (= highest-priority victim) priority
            first_prio = None
            found = None
            for vi in range(v):
                is_first = (
                    vic_b[vi:vi + 1, :]
                    if found is None
                    else (vic_b[vi:vi + 1, :] & ~found)
                )
                p_here = jnp.where(is_first, prio[vi:vi + 1, :], 0)
                first_prio = (
                    p_here if first_prio is None else first_prio + p_here
                )
                found = (
                    vic_b[vi:vi + 1, :]
                    if found is None
                    else (found | vic_b[vi:vi + 1, :])
                )
            fprio = jnp.where(found, first_prio, imax)
            # 3. smallest sum of (prio + MaxInt32 + 1), 16-bit limbs
            tbits = jax.lax.bitcast_convert_type(
                prio, jnp.uint32
            ) ^ jnp.uint32(0x80000000)
            lo = (tbits & jnp.uint32(0xFFFF)).astype(jnp.int32)
            hi = (tbits >> 16).astype(jnp.int32)
            slo = jnp.sum(lo * vic, axis=0, keepdims=True)
            shi = jnp.sum(hi * vic, axis=0, keepdims=True)
            shi = shi + (slo >> 16)
            slo = slo & 0xFFFF
            # 5. earliest start among highest-priority victims
            vprio = jnp.where(vic_b, prio, imin)
            max_prio = jnp.max(vprio, axis=0, keepdims=True)
            at_max = vic_b & (vprio == max_prio)
            earliest = jnp.min(
                jnp.where(at_max, start, imax), axis=0, keepdims=True
            )
            # victim bitmask rows
            lo_n = jnp.zeros((1, n), jnp.int32)
            for vi in range(min(v, 16)):
                lo_n = lo_n + vic[vi:vi + 1, :] * (1 << vi)
            hi_n = jnp.zeros((1, n), jnp.int32)
            for vi in range(16, min(v, 32)):
                hi_n = hi_n + vic[vi:vi + 1, :] * (1 << (vi - 16))

            keys_i[_K_FEAS:_K_FEAS + 1, :] = feas.astype(jnp.int32)
            keys_i[_K_FPRIO:_K_FPRIO + 1, :] = fprio
            keys_i[_K_SHI:_K_SHI + 1, :] = shi
            keys_i[_K_SLO:_K_SLO + 1, :] = slo
            keys_i[_K_VCOUNT:_K_VCOUNT + 1, :] = vcount
            keys_i[_K_VLO:_K_VLO + 1, :] = lo_n
            keys_i[_K_VHI:_K_VHI + 1, :] = hi_n
            keys_i[_K_EARLIEST:_K_EARLIEST + 1, :] = earliest

        # -- per-pod pick over the cached keys --------------------------
        feas = keys_i[_K_FEAS:_K_FEAS + 1, :] > 0
        vcount = keys_i[_K_VCOUNT:_K_VCOUNT + 1, :]
        free = feas & (vcount == 0)
        any_free = jnp.any(free)

        def narrow(c, vals):
            masked = jnp.where(c, vals, imax)
            return c & (masked == jnp.min(masked))

        cand_n = feas
        cand_n = narrow(cand_n, keys_i[_K_FPRIO:_K_FPRIO + 1, :])
        cand_n = narrow(cand_n, keys_i[_K_SHI:_K_SHI + 1, :])
        cand_n = narrow(cand_n, keys_i[_K_SLO:_K_SLO + 1, :])
        cand_n = narrow(cand_n, vcount)
        r5_key = jnp.where(
            cand_n, keys_i[_K_EARLIEST:_K_EARLIEST + 1, :], imin
        )
        r5_best = jnp.max(r5_key)
        pick_r5 = jnp.min(
            jnp.where(
                cand_n & (r5_key == r5_best), col, jnp.int32(_BIG)
            )
        )
        pick_free = jnp.min(jnp.where(free, col, jnp.int32(_BIG)))
        pick = jnp.where(any_free, pick_free, pick_r5)
        choice = jnp.where(
            jnp.any(feas) & is_active, pick, jnp.int32(-1)
        )
        placed = choice >= 0
        chosen_ref[t] = choice

        onehot = ((col == choice) & placed).astype(jnp.int32)  # [1, N]
        vmask_lo_ref[t] = jnp.sum(
            keys_i[_K_VLO:_K_VLO + 1, :] * onehot
        )
        vmask_hi_ref[t] = jnp.sum(
            keys_i[_K_VHI:_K_VHI + 1, :] * onehot
        )

        # nomination carry for later (lower-priority) pods
        for d in range(r):
            state_ref[d:d + 1, :] = (
                state_ref[d:d + 1, :] + onehot * podreq_ref[t * r + d]
            )
        for j, d in enumerate(adims):
            st0_s[j:j + 1, :] = (
                st0_s[j:j + 1, :] + onehot * podreq_ref[t * r + d]
            )

        # -- incremental fixup: recompute the chosen lane's keys --------
        @pl.when(placed)
        def _fixup():
            # the node's victim columns via ONE contiguous DMA from the
            # HBM row-major copy: cols_ref[node] = [prio V | vact V |
            # start-bits V | vreq d-major A*V | alloc A]. The node axis
            # is a LEADING (untiled) dim indexed whole: a one-row slice
            # of a 2-D [N, X] operand is not aligned to its (8, 128)
            # tiling, and Mosaic refuses it once X_pad passes one lane
            # tile (v_max = 32)
            dma = pltpu.make_async_copy(
                cols_ref.at[choice], colrow_s, dma_sem
            )
            dma.start()
            # st0 lives in VMEM (updated per placement): extract its
            # [A] lane values with tiny one-hot reductions meanwhile
            st0_c = [
                jnp.sum(st0_s[j:j + 1, :] * onehot) for j in range(a)
            ]
            dma.wait()

            def ci(j):  # scalar int32 at packed column j
                return colrow_s[0, j]

            prio_c = [ci(j) for j in range(v)]
            vact_c = [ci(v + j) > 0 for j in range(v)]
            start_c = [ci(2 * v + j) for j in range(v)]
            vreq_c = [
                [ci(3 * v + d * v + vi) for vi in range(v)]
                for d in range(a)
            ]  # [A][V]
            alloc_c = [ci(3 * v + a * v + d) for d in range(a)]

            elig_c = [
                vact_c[vi] & (prio_c[vi] < pod_prio) for vi in range(v)
            ]
            req_c = [podreq_ref[t * r + d] for d in adims]
            zero_c = [req_c[j] == 0 for j in range(a)]
            st_c = list(st0_c)
            for j in range(a):
                rem = jnp.int32(0)
                for vi in range(v):
                    rem = rem + jnp.where(
                        elig_c[vi], vreq_c[j][vi], 0
                    )
                st_c[j] = st_c[j] - rem

            def fits_c(free):  # [A] scalars -> scalar bool
                ok_all = None
                ok_pods = None
                for j, d in enumerate(adims):
                    ok = req_c[j] <= free[j]
                    if d >= NUM_FIXED_DIMS:
                        ok = ok | zero_c[j]
                    ok_all = ok if ok_all is None else (ok_all & ok)
                    if d == PODS:
                        ok_pods = ok
                az = None
                for j, d in enumerate(adims):
                    if d != PODS:
                        az = (
                            zero_c[j] if az is None else (az & zero_c[j])
                        )
                if az is None:
                    return ok_pods
                return jnp.where(az, ok_pods, ok_all)

            feas_c = fits_c([alloc_c[j] - st_c[j] for j in range(a)])
            vic_c = []
            for vi in range(v):
                cand_state = [
                    st_c[j]
                    + jnp.where(elig_c[vi], vreq_c[j][vi], 0)
                    for j in range(a)
                ]
                keep = (
                    fits_c(
                        [alloc_c[j] - cand_state[j] for j in range(a)]
                    )
                    & elig_c[vi]
                )
                st_c = [
                    jnp.where(keep, cand_state[j], st_c[j])
                    for j in range(a)
                ]
                vic_c.append(elig_c[vi] & ~keep)

            vcount_c = jnp.int32(0)
            for vi in range(v):
                vcount_c = vcount_c + vic_c[vi].astype(jnp.int32)
            first_prio = jnp.int32(0)
            found = vic_c[0] & False
            for vi in range(v):
                is_first = vic_c[vi] & ~found
                first_prio = first_prio + jnp.where(
                    is_first, prio_c[vi], 0
                )
                found = found | vic_c[vi]
            fprio_c = jnp.where(found, first_prio, imax)
            slo_c = jnp.int32(0)
            shi_c = jnp.int32(0)
            for vi in range(v):
                # (prio ^ 0x80000000) without scalar bitcast: adding
                # 2^31 in two's complement flips the sign bit, i.e.
                # tb = prio + INT_MIN viewed as unsigned -- its low/high
                # 16-bit limbs are computable in int space
                tb = prio_c[vi] ^ jnp.int32(-(1 << 31))
                sel = vic_c[vi].astype(jnp.int32)
                slo_c = slo_c + sel * (tb & jnp.int32(0xFFFF))
                shi_c = shi_c + sel * ((tb >> 16) & jnp.int32(0xFFFF))
            shi_c = shi_c + (slo_c >> 16)
            slo_c = slo_c & 0xFFFF
            maxp_c = jnp.int32(imin)
            for vi in range(v):
                maxp_c = jnp.maximum(
                    maxp_c, jnp.where(vic_c[vi], prio_c[vi], imin)
                )
            earliest_c = imax
            for vi in range(v):
                at_max = vic_c[vi] & (prio_c[vi] == maxp_c)
                earliest_c = jnp.minimum(
                    earliest_c,
                    jnp.where(at_max, start_c[vi], imax),
                )
            lo_bits = jnp.int32(0)
            for vi in range(min(v, 16)):
                lo_bits = lo_bits + vic_c[vi].astype(jnp.int32) * (
                    1 << vi
                )
            hi_bits = jnp.int32(0)
            for vi in range(16, min(v, 32)):
                hi_bits = hi_bits + vic_c[vi].astype(jnp.int32) * (
                    1 << (vi - 16)
                )

            def put_i(row, val):
                keys_i[row:row + 1, :] = jnp.where(
                    onehot > 0, val, keys_i[row:row + 1, :]
                )

            put_i(_K_FEAS, feas_c.astype(jnp.int32))
            put_i(_K_FPRIO, fprio_c)
            put_i(_K_SHI, shi_c)
            put_i(_K_SLO, slo_c)
            put_i(_K_VCOUNT, vcount_c)
            put_i(_K_VLO, lo_bits)
            put_i(_K_VHI, hi_bits)
            put_i(_K_EARLIEST, earliest_c)
        return 0

    jax.lax.fori_loop(0, chunk, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "adims"))
def pallas_preempt_solve(
    alloc: jnp.ndarray,       # [N, A] int32 (active dims, pre-sliced)
    base_requested: jnp.ndarray,  # [N, R] int32 (FULL dims: state carry)
    prio: jnp.ndarray,        # [N, V] int32
    start_rel: jnp.ndarray,   # [N, V] f32
    req: jnp.ndarray,         # [N, V, A] int32 (active dims, pre-sliced)
    active: jnp.ndarray,      # [N] int32, bit v = victim slot v active
    nom_req: jnp.ndarray,     # [M, R] int32
    nom_prio: jnp.ndarray,    # [M] int32
    nom_node: jnp.ndarray,    # [M] int32 (-1 inactive)
    pods_req: jnp.ndarray,    # [B, R] int32
    pods_prio: jnp.ndarray,   # [B] int32
    cand_rows: jnp.ndarray,   # [U, N] bool (dedup candidate masks)
    cand_index: jnp.ndarray,  # [B] int32
    pods_active: jnp.ndarray,  # [B] bool
    interpret: bool = False,
    adims: Tuple[int, ...] = (),
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (packed [3, B] = chosen/vmask_lo/vmask_hi,
    state' [N, R]). ``adims`` names the active resource dims the
    pre-sliced alloc/req carry (ops/preemption.upload_pack slims the
    transfer to them); the fit skips other dims, which is exact -- see
    module docstring."""
    n, r = base_requested.shape
    v = prio.shape[1]
    b = pods_req.shape[0]
    m = nom_prio.shape[0]
    if not adims:
        adims = tuple(range(r))
    a = len(adims)
    assert alloc.shape[1] == a and req.shape[2] == a
    adims_arr = jnp.asarray(adims, dtype=jnp.int32)
    chunk = min(b, 1024)
    assert b % chunk == 0
    grid = (b // chunk,)

    # unpack the bit-per-victim active flags (1 int32 per node rides the
    # link instead of [N, V])
    act_vn = (
        (active[None, :] >> jnp.arange(v, dtype=jnp.int32)[:, None]) & 1
    )  # [V, N] int32
    act_nv = jnp.swapaxes(act_vn, 0, 1)  # [N, V]

    # node-space nomination requests on active dims: nomination m
    # contributes its request only at its node's lane
    node_oh = (
        jnp.arange(n)[None, :] == nom_node[:, None]
    ).astype(jnp.int32)  # [M, N]
    nomreq_node = (
        nom_req[:, adims_arr][:, :, None] * node_oh[:, None, :]
    ).reshape(m * a, n)

    kernel = functools.partial(
        _preempt_kernel, chunk=chunk, r=r, v=v, m=m, adims=adims
    )

    def chunk_1d(i):
        return (i,)

    def whole(i):
        return (0, 0)

    def whole_1d(i):
        return (0,)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    vreq_vmajor = jnp.transpose(req, (1, 2, 0)).reshape(v * a, n)
    vreq_dmajor = jnp.transpose(req, (2, 1, 0)).reshape(a * v, n)

    # row-major [N, X] victim-column pack for the fixup DMA: one
    # contiguous row per node = [prio V | vact V | start-bits V |
    # vreq d-major A*V | alloc A], lane-padded for clean copies
    x = 3 * v + a * v + a
    x_pad = 128 * -(-x // 128)
    cols = jnp.concatenate(
        [
            prio.astype(jnp.int32),                      # [N, V]
            act_nv,                                      # [N, V]
            jax.lax.bitcast_convert_type(
                start_rel.astype(jnp.float32), jnp.int32
            ),                                           # [N, V]
            jnp.transpose(req, (0, 2, 1)).reshape(n, a * v),  # [N, A*V]
            alloc,                                       # [N, A]
        ],
        axis=1,
    )
    cols = jnp.pad(cols, ((0, 0), (0, x_pad - x)))[:, None, :]

    chosen, vlo, vhi, state_out = pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=(
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((r, n), jnp.int32),
        ),
        in_specs=[
            smem((chunk * r,), chunk_1d),
            smem((chunk,), chunk_1d),
            smem((chunk,), chunk_1d),
            smem((chunk,), chunk_1d),
            smem((m,), whole_1d),
            vmem((a, n), whole),
            vmem((v, n), whole),
            vmem((v, n), whole),
            vmem((v * a, n), whole),
            vmem((a * v, n), whole),
            vmem((v, n), whole),
            vmem(cand_rows.shape, whole),
            vmem((m * a, n), whole),
            pl.BlockSpec(memory_space=pltpu.HBM),
            vmem((r, n), whole),
        ],
        out_specs=(
            smem((chunk,), chunk_1d),
            smem((chunk,), chunk_1d),
            smem((chunk,), chunk_1d),
            vmem((r, n), whole),
        ),
        scratch_shapes=[
            pltpu.VMEM((_K_ROWS, n), jnp.int32),
            pltpu.VMEM((a, n), jnp.int32),
            pltpu.SMEM((1, x_pad), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={14: 3},
        compiler_params=COMPILER_PARAMS,
        # stable device-trace name (see pallas_greedy_solve)
        name="pallas_preempt_solve",
        interpret=interpret,
    )(
        pods_req.astype(jnp.int32).reshape(-1),
        pods_prio.astype(jnp.int32),
        cand_index.astype(jnp.int32),
        pods_active.astype(jnp.int32),
        nom_prio.astype(jnp.int32),
        alloc.T,
        jnp.swapaxes(prio, 0, 1),
        jax.lax.bitcast_convert_type(
            jnp.swapaxes(start_rel, 0, 1).astype(jnp.float32), jnp.int32
        ),
        vreq_vmajor,
        vreq_dmajor,
        act_vn,
        cand_rows.astype(jnp.int32),
        nomreq_node,
        cols,
        base_requested.T,
    )
    # ONE downloadable array instead of three separate output fetches
    # (a choice made on an earlier machine; the cost of a fetch on this
    # one is not re-measured). state_out stays device-side: a >512-pod wave
    # chains fixed-size kernel calls through it, keeping ONE compiled
    # variant for every wave size.
    packed = jnp.stack([chosen, vlo, vhi])
    return packed, state_out.T
