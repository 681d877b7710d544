"""Device victim search: the TPU stage-7 preemption path (SURVEY.md
build-plan stage 7).

Reference semantics replicated exactly from
/root/reference/pkg/scheduler/core/generic_scheduler.go:
- selectVictimsOnNode (:940): remove every lower-priority pod, check the
  preemptor fits, then "reprieve" victims in MoreImportantPod order --
  PDB-violating pods first -- re-adding each and keeping it unless the
  preemptor stops fitting.
- filterPodsWithPDBViolation (:884): greedy per-PDB DisruptionsAllowed
  budget spend over the sorted potential-victim list.
- addNominatedPods (:535): nominated pods with priority >= the preemptor
  are virtually added before the fit check.

The expensive part -- the reprieve simulation over every candidate node x
every potential victim -- runs as one jitted scan over the victim axis
with all candidate nodes vectorized per step (the device analogue of
ParallelizeUntil(16) at :850). Pod-side string work (MoreImportantPod
sort, PDB label matching, owner lookups) happens once per snapshot in
pack_preemption_state and is cached by the Preemptor, so a burst of
failed pods shares one pack.

Only the resource-fit + static-mask filter family is modeled on device;
the Preemptor gates this path to pods/clusters where that set is exact
(plain pods, no required anti-affinity in the cluster, no interested
extenders) and falls back to the host oracle otherwise
(scheduler/preemption.py).

The final 6-rule pickOneNodeForPreemption (:721) runs as a vectorized
int64 lexicographic narrowing on the downloaded flags: exact integer
arithmetic (rule 3's priority sum overflows int32/f32) at O(N) numpy
cost, which profiling puts far below one device round trip.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from kubernetes_tpu.api.selectors import labels_match_selector
from kubernetes_tpu.api.types import Pod, PodDisruptionBudget
from kubernetes_tpu.ops.assignment import _fits
from kubernetes_tpu.tensors.node_tensor import NodeTensor

_INT_MIN = -(1 << 31)

#: test hook: run the Pallas preemption path in interpreter mode off-TPU
#: so the FULL wrapper (chunk-to-chunk state chaining, candidate dedup,
#: bitmask reassembly) gets differential coverage, not just the kernel
FORCE_PALLAS_INTERPRET = False


class PreemptionPack:
    """Per-snapshot tensors for the device victim search (cached by the
    Preemptor keyed on snapshot generation + PDB resource version)."""

    __slots__ = (
        "node_names", "node_index", "pods_by_node", "alloc",
        "base_requested", "prio", "start_rel", "req", "active",
        "pdb_match", "pdb_allowed", "v_max", "generation", "dev",
        "last_adims", "made", "why", "nodes_kept", "nodes_repacked",
    )


def pack_preemption_state(
    snapshot,
    nt: NodeTensor,
    pdbs: List[PodDisruptionBudget],
) -> PreemptionPack:
    """Sort every node's pods by MoreImportantPod (priority desc, start
    asc -- util/utils.go:76) and pack the per-victim tensors. The
    priority cutoff (which pods are eligible victims for a given
    preemptor) is applied ON DEVICE as a suffix mask over this sorted
    order, so one pack serves preemptors of any priority."""
    node_infos = [
        ni for ni in snapshot.list_node_infos() if ni.node is not None
    ]
    n = len(node_infos)
    now = time.time()
    # MoreImportantPod order per node via ONE np.lexsort over the whole
    # cluster (5k Python sorts of pod lists measured ~half the pack)
    all_pods: List[Pod] = []
    node_of: List[int] = []
    for i, ni in enumerate(node_infos):
        all_pods.extend(ni.pods)
        node_of.extend([i] * len(ni.pods))
    if all_pods:
        node_arr = np.asarray(node_of, dtype=np.int64)
        prio_arr = np.array(
            [p.spec.priority for p in all_pods], dtype=np.int64
        )
        start_arr = np.array(
            [
                p.status.start_time
                if p.status.start_time is not None else now
                for p in all_pods
            ],
            dtype=np.float64,
        )
        order = np.lexsort((start_arr, -prio_arr, node_arr))
        counts_per_node = np.bincount(node_arr, minlength=n)
        sorted_pods = [[] for _ in range(n)]
        for j in order:
            sorted_pods[node_of[j]].append(all_pods[j])
    else:
        counts_per_node = np.zeros(n, dtype=np.int64)
        sorted_pods = [[] for _ in range(n)]
    v_max = int(counts_per_node.max()) if n else 0
    # power-of-two victim-axis buckets: pod churn moves the per-node max
    # constantly, and every new v_max forks a ~3s kernel compile
    v_max = max(8, 1 << (v_max - 1).bit_length() if v_max > 1 else 8)
    r = nt.dims.num_dims
    p_count = len(pdbs)

    prio = np.full((n, v_max), _INT_MIN, dtype=np.int64)
    start_rel = np.zeros((n, v_max), dtype=np.float64)
    req = np.zeros((n, v_max, r), dtype=np.int32)
    active = np.zeros((n, v_max), dtype=bool)
    pdb_match = np.zeros((n, v_max, max(p_count, 1)), dtype=bool)

    from kubernetes_tpu.tensors import pack_pod_batch

    from kubernetes_tpu.api.selectors import labels_match_mask

    # one vectorized pass over ALL victims: flatten (node, slot) -> one
    # pack_pod_batch call + scatters (the per-node pack loop was ~0.35s
    # per wave at 5k nodes x 50k pods -- pure Python dispatch)
    rows = np.array(
        [nt.row(ni.node_name) for ni in node_infos], dtype=np.int64
    )
    alloc = (
        nt.allocatable[rows].astype(np.int32)
        if n else np.zeros((0, r), dtype=np.int32)
    )
    base_requested = (
        nt.requested[rows].astype(np.int32)
        if n else np.zeros((0, r), dtype=np.int32)
    )
    if all_pods:
        flat_pods = [all_pods[j] for j in order]
        flat_node = node_arr[order]
        starts = np.zeros(n, dtype=np.int64)
        starts[1:] = np.cumsum(counts_per_node)[:-1]
        flat_slot = (
            np.arange(len(all_pods), dtype=np.int64) - starts[flat_node]
        )
        batch = pack_pod_batch(flat_pods, nt.dims)
        req[flat_node, flat_slot] = batch.requests
        prio[flat_node, flat_slot] = prio_arr[order]
        start_rel[flat_node, flat_slot] = start_arr[order]
        active[flat_node, flat_slot] = True
        if pdbs:
            labels_list = [p.metadata.labels for p in flat_pods]
            ns_arr = np.array(
                [p.metadata.namespace for p in flat_pods], dtype=object
            )
            has_labels = np.array(
                [bool(p.metadata.labels) for p in flat_pods], dtype=bool
            )
            for k, pdb in enumerate(pdbs):
                if pdb.selector is None:
                    continue
                mask = np.frombuffer(
                    labels_match_mask(labels_list, pdb.selector),
                    dtype=np.uint8,
                ).astype(bool)
                mask &= has_labels
                mask &= ns_arr == pdb.metadata.namespace
                pdb_match[flat_node, flat_slot, k] = mask

    # relative start times keep f32 exact for realistic spans (absolute
    # epoch seconds lose ~64s of precision in f32)
    if active.any():
        start_rel -= start_rel[active].min()

    pack = PreemptionPack()
    pack.node_names = [ni.node_name for ni in node_infos]
    pack.node_index = {
        name: i for i, name in enumerate(pack.node_names)
    }
    pack.pods_by_node = sorted_pods
    pack.alloc = alloc
    pack.base_requested = base_requested
    pack.prio = prio
    pack.start_rel = start_rel
    pack.req = req
    pack.active = active
    pack.pdb_match = pdb_match
    pack.pdb_allowed = np.array(
        [pdb.status.disruptions_allowed for pdb in pdbs] or [0],
        dtype=np.int32,
    )
    pack.v_max = v_max
    pack.generation = getattr(snapshot, "generation", 0)
    pack.dev = {}
    pack.last_adims = None
    return pack


@partial(jax.jit, static_argnames=("shapes",))
def _split_pack_buffer(buf, shapes):
    out = []
    off = 0
    for shp in shapes:
        size = 1
        for d in shp:
            size *= d
        out.append(buf[off:off + size].reshape(shp))
        off += size
    return tuple(out)


def upload_pack(pack: PreemptionPack, adims: Tuple[int, ...]) -> tuple:
    """Slimmed per-adims device upload of the pack, cached on it. Only
    the active resource dims ride the link and the victim-active flags
    pack into one bit per victim: ~1.6MB instead of ~5.5MB at 5k nodes.
    jax transfers are async, so callers that upload EARLY (the prewarm
    path) overlap the link time with host work."""
    dev = pack.dev.get(adims)
    if dev is None:
        ad = list(adims)
        active_bits = np.zeros(pack.active.shape[0], dtype=np.int32)
        for vi in range(pack.active.shape[1]):
            active_bits |= pack.active[:, vi].astype(np.int32) << vi
        pieces = (
            np.ascontiguousarray(pack.alloc[:, ad]),
            np.clip(
                pack.prio, _INT_MIN, (1 << 31) - 2
            ).astype(np.int32),
            np.ascontiguousarray(
                pack.start_rel.astype(np.float32)
            ).view(np.int32),
            np.ascontiguousarray(pack.req[:, :, ad]),
            active_bits,
        )
        # ONE transfer: the five arrays ride one int32 buffer and
        # split on device (same single-buffer choice as
        # ops/assignment.solve_packed)
        shapes = tuple(a.shape for a in pieces)
        buf = jax.device_put(
            np.concatenate([a.ravel() for a in pieces])
        )
        dev = list(_split_pack_buffer(buf, shapes=shapes))
        dev[2] = jax.lax.bitcast_convert_type(dev[2], jnp.float32)
        dev = tuple(dev)
        pack.dev[adims] = dev
    return dev


def _device_pick(feasible, victims, victims_viol, prio, start_rel):
    """pickOneNodeForPreemption (:721) fully on device. Rules 1-4 are
    exact integer narrowing; rule 3's priority sum (each term is
    prio + MaxInt32 + 1, up to 2^32, summed over victims) is carried in
    two 16-bit limbs so the 48-bit compare stays exact without int64.
    Returns the chosen node index, or -1 when nothing is feasible."""
    n = feasible.shape[0]
    vcount = (victims.sum(axis=1)).astype(jnp.int32)
    nviol = victims_viol.sum(axis=1).astype(jnp.int32)

    def narrow(cand, vals):
        masked = jnp.where(cand, vals, jnp.int32((1 << 31) - 1))
        return cand & (masked == masked.min())

    cand = feasible
    # free lunch: a feasible node needing no victims wins immediately
    free = cand & (vcount == 0)
    any_free = free.any()

    cand = narrow(cand, nviol)  # 1. fewest PDB violations
    # 2. lowest first-victim priority (reference Victims.Pods[0]:
    # victims are appended violating-first)
    has_viol = victims_viol.any(axis=1)
    first_any = jnp.argmax(victims, axis=1)
    first_viol = jnp.argmax(victims_viol, axis=1)
    fi = jnp.where(has_viol, first_viol, first_any)
    fprio = prio[jnp.arange(n), fi]
    cand = narrow(cand, fprio)
    # 3. smallest sum of (prio + MaxInt32 + 1): the two's-complement sign
    # flip maps int32 prio to EXACTLY prio + 2^31 = prio + MaxInt32 + 1
    # as uint32; split into 16-bit limbs whose sums fit int32 exactly
    t = jax.lax.bitcast_convert_type(prio, jnp.uint32) ^ jnp.uint32(
        0x80000000
    )
    lo = (t & jnp.uint32(0xFFFF)).astype(jnp.int32)
    hi = (t >> 16).astype(jnp.int32)
    vic_i = victims.astype(jnp.int32)
    slo = (lo * vic_i).sum(axis=1)
    shi = (hi * vic_i).sum(axis=1)
    shi = shi + (slo >> 16)
    slo = slo & 0xFFFF
    cand = narrow(cand, shi)
    cand = narrow(cand, slo)
    cand = narrow(cand, vcount)  # 4. fewest victims
    # 5. latest earliest-start among each node's highest-priority victims
    vprio = jnp.where(victims, prio, jnp.int32(-(1 << 31)))
    max_prio = vprio.max(axis=1)
    at_max = victims & (vprio == max_prio[:, None])
    earliest = jnp.where(at_max, start_rel, jnp.inf).min(axis=1)
    pick_r5 = jnp.argmax(jnp.where(cand, earliest, -jnp.inf)).astype(
        jnp.int32
    )
    pick = jnp.where(any_free, jnp.argmax(free).astype(jnp.int32), pick_r5)
    return jnp.where(feasible.any(), pick, jnp.int32(-1))


@partial(jax.jit, static_argnames=("num_pdbs",))
def _preempt_batch_kernel(
    alloc: jnp.ndarray,  # [N, R] int32
    base_requested: jnp.ndarray,  # [N, R] int32 (all pods incl. victims)
    prio: jnp.ndarray,  # [N, V] int32
    start_rel: jnp.ndarray,  # [N, V] float32
    req: jnp.ndarray,  # [N, V, R] int32
    active: jnp.ndarray,  # [N, V] bool
    pdb_match: jnp.ndarray,  # [N, V, P] bool
    pdb_allowed: jnp.ndarray,  # [P] int32
    nom_req: jnp.ndarray,  # [M, R] int32 pre-existing nominated pods
    nom_prio: jnp.ndarray,  # [M] int32
    nom_node: jnp.ndarray,  # [M] int32 node index (-1 inactive)
    pods_req: jnp.ndarray,  # [B, R] int32, priority-desc order
    pods_prio: jnp.ndarray,  # [B] int32
    candidate: jnp.ndarray,  # [B, N] bool
    pods_active: jnp.ndarray,  # [B] bool
    num_pdbs: int,
):
    """The whole failed-pod group's preemption in ONE device program: a
    scan over pods (priority-desc, the activeQ order) whose carry is the
    node-state WITH every earlier pod's nomination added -- exactly the
    view addNominatedPods gives each subsequent scheduling cycle (all
    in-scan nominations have priority >= any later pod's). Victims stay
    in the state (the reference's stale-snapshot semantics: deletions
    land asynchronously) and each pod gets fresh PDB budgets (the
    disruption controller hasn't observed earlier evictions yet).

    Returns (chosen [B] node index or -1, victims [B, V] on the chosen
    node, victims_violating [B, V], num_violating [B])."""
    n, v = prio.shape
    node_iota = jnp.arange(n, dtype=jnp.int32)

    def one_pod(node_state, inputs):
        pod_req, pod_prio, cand_row, is_active = inputs

        eligible = active & (prio < pod_prio)  # [N, V]
        nom_sel = (nom_prio >= pod_prio) & (nom_node >= 0)
        nom_add = jnp.zeros_like(node_state).at[
            jnp.clip(nom_node, 0)
        ].add(nom_req * nom_sel[:, None].astype(jnp.int32))
        removed = (req * eligible[:, :, None].astype(jnp.int32)).sum(axis=1)
        state0 = node_state + nom_add - removed
        feasible = _fits(alloc - state0, pod_req) & cand_row & is_active

        # PDB budget spend in sorted order (filterPodsWithPDBViolation)
        if num_pdbs:
            def pdb_step(budgets, step_in):
                match_v, elig_v = step_in  # [N, P], [N]
                violated = jnp.zeros(elig_v.shape, dtype=bool)
                broken = jnp.zeros(elig_v.shape, dtype=bool)
                for p in range(num_pdbs):
                    m = match_v[:, p] & elig_v & ~broken
                    viol_p = m & (budgets[:, p] <= 0)
                    violated = violated | viol_p
                    broken = broken | viol_p
                    budgets = budgets.at[:, p].add(
                        -(m & ~viol_p).astype(jnp.int32)
                    )
                return budgets, violated

            budgets0 = jnp.broadcast_to(
                pdb_allowed[None, :], (n, pdb_allowed.shape[0])
            ).astype(jnp.int32)
            _, violating_t = jax.lax.scan(
                pdb_step,
                budgets0,
                (jnp.swapaxes(pdb_match, 0, 1), eligible.T),
            )
            violating = violating_t.T
        else:
            violating = jnp.zeros(eligible.shape, dtype=bool)

        # reprieve: violating first, then the rest, in sorted order
        def reprieve_pass(state, sel_mask):
            def step(st, step_in):
                vreq, sel = step_in
                cand_state = st + vreq * sel[:, None].astype(jnp.int32)
                keep = _fits(alloc - cand_state, pod_req) & sel
                st = jnp.where(keep[:, None], cand_state, st)
                return st, sel & ~keep

            # V is small (pods-per-node, bucketed by 8): full unroll
            # collapses the inner while loop into one fused block,
            # removing the per-step lowering overhead that dominated the
            # preemption wave (~0.17ms per scan step on device)
            state, victims_t = jax.lax.scan(
                step, state, (jnp.swapaxes(req, 0, 1), sel_mask.T)
            )
            return state, victims_t.T

        st, victims_viol = reprieve_pass(state0, eligible & violating)
        _, victims_rest = reprieve_pass(st, eligible & ~violating)
        victims = victims_viol | victims_rest

        choice = _device_pick(feasible, victims, victims_viol, prio, start_rel)
        placed = choice >= 0
        safe = jnp.clip(choice, 0)
        # nominate: later (lower-priority) pods see this pod's request
        node_state = node_state + (
            (node_iota == safe) & placed
        )[:, None].astype(jnp.int32) * pod_req[None, :]
        out = (
            choice,
            victims[safe] & placed,
            victims_viol[safe] & placed,
            (victims_viol[safe] & placed).sum().astype(jnp.int32),
        )
        return node_state, out

    _, (chosen, victims_b, viol_b, nviol_b) = jax.lax.scan(
        one_pod,
        base_requested,
        (pods_req, pods_prio, candidate, pods_active),
    )
    return chosen, victims_b, viol_b, nviol_b


@partial(jax.jit, static_argnames=("num_pdbs",))
def _preempt_batch_kernel_packed(*args, num_pdbs: int):
    """_preempt_batch_kernel with the four results packed into one
    int32 [B, 2V+2] array (column 0 chosen, 1 num_violating, then
    victims and violating masks) so the host pays ONE download."""
    chosen, victims, viol, nviol = _preempt_batch_kernel(
        *args, num_pdbs=num_pdbs
    )
    return jnp.concatenate(
        [
            chosen[:, None],
            nviol[:, None],
            victims.astype(jnp.int32),
            viol.astype(jnp.int32),
        ],
        axis=1,
    )


def wave_pallas_eligible(pack: PreemptionPack, num_pdbs: int) -> bool:
    """True when the fused Pallas tier can run this wave: no PDB
    modeling (the Pallas kernel has none -- PDB waves take the jnp
    twin), a victim axis that fits the 32-bit result masks, the env
    kill-switch off, and a TPU backend (or the interpret-mode test
    hook). The wave ladder (scheduler/preemption.py) consults this to
    decide whether to offer the pallas tier at all."""
    import os as _os

    import jax as _jax

    return (
        num_pdbs == 0
        and pack.v_max <= 32
        and _os.environ.get("KTPU_PALLAS", "1") != "0"
        and (
            _jax.default_backend() == "tpu" or FORCE_PALLAS_INTERPRET
        )
    )


def pack_num_pdbs(pack: PreemptionPack) -> int:
    """The PDB-count the kernels are specialized on: zero when no victim
    matches any budget (the common case compiles the budget loop away)."""
    return int(pack.pdb_allowed.shape[0]) if pack.pdb_match.any() else 0


def preempt_batch_device(
    pack: PreemptionPack,
    pods_req: np.ndarray,  # [B, R]
    pods_prio: np.ndarray,  # [B]
    candidate: Optional[np.ndarray],  # [B, N], or None with cand_dedup
    nom_req: np.ndarray,  # [M, R]
    nom_prio: np.ndarray,  # [M]
    nom_node: np.ndarray,  # [M]
    cand_dedup: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    tier: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """One device round trip for a whole failed-pod group. Returns host
    arrays (chosen [B], victims [B, V], victims_violating [B, V],
    num_violating [B]).

    ``cand_dedup``: optional pre-deduplicated (rows [U, N], index [B])
    candidate masks. The caller usually KNOWS the dedup structure (a
    wave shares a handful of static-mask rows x potential-node lists),
    and np.unique over a materialized [B, N] matrix measured ~1.1s at
    1000x5000 -- half the preemption wave.

    ``tier``: None = legacy auto-pick; "pallas" = the fused kernel (the
    caller must have checked ``wave_pallas_eligible``); "xla" = the
    bit-identical jnp twin, unconditionally. The wave ladder forces the
    tier so a breaker-routed fallback re-runs the SAME wave on the twin
    instead of re-deciding."""
    num_pdbs = pack_num_pdbs(pack)
    b = pods_req.shape[0]
    # power-of-two group buckets: preemption waves arrive at arbitrary
    # sizes, and per-size jit variants each pay a multi-second compile
    # (measured: EVERY wave of the preemption bench recompiled)
    pad_b = max(64, 1 << (b - 1).bit_length() if b > 1 else 64)
    m = nom_req.shape[0]
    pad_m = max(8, 8 * -(-m // 8)) if m else 8
    nr = np.zeros((pad_m, pods_req.shape[1]), dtype=np.int32)
    npi = np.full(pad_m, _INT_MIN + 1, dtype=np.int32)
    nn = np.full(pad_m, -1, dtype=np.int32)
    if m:
        nr[:m] = nom_req
        npi[:m] = nom_prio
        nn[:m] = nom_node

    if tier is None:
        use_pallas = wave_pallas_eligible(pack, num_pdbs)
    elif tier == "pallas":
        assert wave_pallas_eligible(pack, num_pdbs), (
            "pallas tier forced for an ineligible wave"
        )
        use_pallas = True
    else:
        assert tier == "xla", f"unknown preemption tier {tier!r}"
        use_pallas = False
    if use_pallas:
        from kubernetes_tpu.ops.pallas_preempt import pallas_preempt_solve
        from kubernetes_tpu.tensors.node_tensor import PODS

        # active fit dims for the wave (see pallas_preempt docstring):
        # the pods' requested dims + nomination dims + any over-committed
        # dims + the pod-count dim. Dims outside this set have zero pod
        # request and provably non-negative free capacity, so the kernel
        # skips them exactly.
        adims_set = set(np.flatnonzero(pods_req.any(axis=0)).tolist())
        if m:
            adims_set |= set(np.flatnonzero(nom_req.any(axis=0)).tolist())
        adims_set |= set(
            np.flatnonzero(
                (pack.base_requested > pack.alloc).any(axis=0)
            ).tolist()
        )
        adims_set.add(PODS)
        adims = tuple(sorted(adims_set))

        # dedup candidate rows (a wave of identical pods shares one row)
        if cand_dedup is not None:
            rows, inverse = cand_dedup
        else:
            rows, inverse = np.unique(
                candidate, axis=0, return_inverse=True
            )
        n_nodes = rows.shape[1]
        u_pad = 8 * -(-rows.shape[0] // 8)
        rows_p = np.zeros((u_pad, n_nodes), dtype=bool)
        rows_p[: rows.shape[0]] = rows
        # fixed-size kernel calls chained through the nomination-state
        # output: ONE compiled variant serves every wave size (per-size
        # variants each paid a multi-second in-window compile), and the
        # chain stays on device (no host sync between chunks)
        chunk_b = 512
        total = chunk_b * -(-b // chunk_b)
        pr2 = np.zeros((total, pods_req.shape[1]), dtype=np.int32)
        pr2[:b] = pods_req
        ci2 = np.zeros(total, dtype=np.int32)
        ci2[:b] = inverse.reshape(-1)
        # one slim upload per (pack, adims), not per chunk call; the
        # prewarm path usually did this long before the wave
        if not hasattr(pack, "dev") or pack.dev is None:
            pack.dev = {}
        alloc_d, prio_d, start_d, req_d, active_d = upload_pack(
            pack, adims
        )
        pack.last_adims = adims
        # Pre-existing nominations fold into the STATE host-side, per
        # priority group (pods arrive priority-desc): a nomination
        # counts only against preemptors with prio <= its own
        # (addNominatedPods, generic_scheduler.go:535), and within one
        # group that set is FIXED, so the in-kernel per-nomination loop
        # -- whose padded M forked a fresh ~2.5s kernel compile per
        # nomination-count bucket mid-burst -- goes away entirely; the
        # kernel always compiles with the empty-nominations shape.
        nr0 = np.zeros((8, pods_req.shape[1]), dtype=np.int32)
        npi0 = np.full(8, _INT_MIN + 1, dtype=np.int32)
        nn0 = np.full(8, -1, dtype=np.int32)
        state = pack.base_requested
        parts = []
        prev_mask = np.zeros(m, dtype=bool) if m else None
        if m:
            # the monotonic nomination fold below requires priority-desc
            # wave order (the callers sort; a violation would silently
            # double-count nominations into the carried state)
            assert (pods_prio[:-1] >= pods_prio[1:]).all(), (
                "preemption wave must be priority-descending"
            )
            group_starts = [0] + [
                k for k in range(1, b)
                if pods_prio[k] != pods_prio[k - 1]
            ] + [b]
        else:
            # no pre-existing nominations: one chained span regardless
            # of priority mix (the kernel's class-change prologue
            # handles mixed priorities; splitting would multiply the
            # 512-slot padding per distinct priority)
            group_starts = [0, b]
        spans = [
            (group_starts[gi], group_starts[gi + 1])
            for gi in range(len(group_starts) - 1)
        ]
        for g0, g1 in spans:
            if m:
                gmask = nom_prio >= pods_prio[g0]
                delta_idx = np.flatnonzero(gmask & ~prev_mask)
                if delta_idx.size:
                    delta = np.zeros(
                        (pack.base_requested.shape[0],
                         pack.base_requested.shape[1]),
                        dtype=np.int32,
                    )
                    np.add.at(
                        delta, nom_node[delta_idx], nom_req[delta_idx]
                    )
                    state = state + delta  # device add after 1st chunk
                prev_mask = gmask
            gtotal = chunk_b * -(-(g1 - g0) // chunk_b)
            grp_req = np.zeros((gtotal, pods_req.shape[1]), np.int32)
            grp_req[: g1 - g0] = pr2[g0:g1]
            grp_prio = np.full(gtotal, pods_prio[g0], np.int32)
            grp_prio[: g1 - g0] = pods_prio[g0:g1]
            grp_act = np.zeros(gtotal, bool)
            grp_act[: g1 - g0] = True
            grp_ci = np.zeros(gtotal, np.int32)
            grp_ci[: g1 - g0] = ci2[g0:g1]
            for off in range(0, gtotal, chunk_b):
                packed_j, state = pallas_preempt_solve(
                    alloc_d,
                    state,
                    prio_d,
                    start_d,
                    req_d,
                    active_d,
                    nr0, npi0, nn0,
                    grp_req[off:off + chunk_b],
                    grp_prio[off:off + chunk_b],
                    rows_p,
                    grp_ci[off:off + chunk_b],
                    grp_act[off:off + chunk_b],
                    interpret=FORCE_PALLAS_INTERPRET,
                    adims=adims,
                )
                # device slicing would compile per shape: keep the full
                # chunk, slice after download
                parts.append(
                    (packed_j, min(chunk_b, g1 - g0 - off))
                )
        # overlapped downloads: start every chunk's host copy first so
        # the per-chunk link round trips overlap (a device-side
        # jnp.concatenate would compile a fresh program per wave shape
        # -- measured ~1s of compile inside the first measured wave)
        for part, _valid in parts:
            part.copy_to_host_async()
        packed = np.concatenate(
            [np.asarray(p)[:, :valid] for p, valid in parts], axis=1
        )
        chosen = packed[0, :b]
        vlo = packed[1, :b]
        vhi = packed[2, :b]
        vbits = (
            vlo.astype(np.uint32) | (vhi.astype(np.uint32) << 16)
        )
        vmask = (
            (vbits[:, None] >> np.arange(pack.v_max)[None, :]) & 1
        ).astype(bool)
        viol = np.zeros_like(vmask)
        return chosen, vmask, viol, np.zeros(b, dtype=np.int32)

    if candidate is None:
        rows_d, inverse_d = cand_dedup
        candidate = rows_d[inverse_d.reshape(-1)]
    pr = np.zeros((pad_b, pods_req.shape[1]), dtype=np.int32)
    pr[:b] = pods_req
    pp = np.zeros(pad_b, dtype=np.int32)
    pp[:b] = pods_prio
    pa = np.zeros(pad_b, dtype=bool)
    pa[:b] = True
    cd = np.zeros((pad_b, candidate.shape[1]), dtype=bool)
    cd[:b] = candidate
    packed = _preempt_batch_kernel_packed(
        pack.alloc,
        pack.base_requested,
        np.clip(pack.prio, _INT_MIN, (1 << 31) - 2).astype(np.int32),
        pack.start_rel.astype(np.float32),
        pack.req,
        pack.active,
        pack.pdb_match,
        pack.pdb_allowed,
        nr, npi, nn,
        pr, pp, cd, pa,
        num_pdbs=num_pdbs,
    )
    # ONE downloadable array instead of four separate fetches
    packed = np.asarray(packed)
    v = pack.req.shape[1]
    return (
        packed[:b, 0],
        packed[:b, 2:2 + v].astype(bool),
        packed[:b, 2 + v:2 + 2 * v].astype(bool),
        packed[:b, 1],
    )


def victims_for_node(
    pack: PreemptionPack,
    idx: int,
    victims_row: np.ndarray,
    violating_row: np.ndarray,
) -> List[Pod]:
    """Materialize the chosen node's victims in reprieve order
    (PDB-violating first, then the rest -- the order the reference
    appends them)."""
    pods = pack.pods_by_node[idx]
    out = [
        pods[v] for v in range(len(pods))
        if victims_row[v] and violating_row[v]
    ]
    out += [
        pods[v] for v in range(len(pods))
        if victims_row[v] and not violating_row[v]
    ]
    return out
