"""Fused Pallas TPU kernel for the CONSTRAINED assignment scan.

The XLA lowering of ops/assignment.greedy_assign_constrained executes a
large fused-op chain per pod step (spread skew checks, three affinity
count families, five score families with per-step normalizes), most of
it per-op dispatch (VERDICT r3 weak #2: PodAntiAffinity far slower than
basic). This kernel fuses the whole constrained step
into one pallas_call: every count tensor lives in VMEM for the whole
batch, and a fori_loop runs fit + spread + affinity + all score families
+ masked argmax + every replay update with no per-op dispatch, one step
a pod up to the batch's last active slot (pallas_solver.live_steps) and
none for the padding behind it.

Key design moves (vs the value-space XLA formulation):

- **Node-space counts.** Mosaic has no per-lane gather, so every
  ``counts[row, node_value[row, n]]`` gather becomes a VMEM-resident
  ``[rows, N]`` NODE-space count matrix, updated on placement by the
  vector op ``counts += bump * (node_value == value_at_choice)`` --
  gather-free and exactly equivalent (nodes sharing the chosen node's
  topology value all advance). Value-space side states are kept only
  where the semantics need them (the spread global-min runs over
  VALUES, and the affinity first-pod escape needs per-row totals).
- **One-hot matmul extracts.** Per-pod ROW-vector params (bump masks,
  per-group skew limits, weights) ride one fat ``[X, B]`` matrix; step t
  reads its column with one ``[X, chunk] @ [chunk, 1]`` dot against a
  sublane one-hot -- the dynamic-lane slice Mosaic can't lower, done on
  the MXU instead. Value-at-choice extracts use the same trick over the
  node axis.
- **Aliased count states.** Initial count matrices are inputs aliased to
  the output refs (input_output_aliases), so each tensor is resident
  once.
- **Family specialization (the VMEM-cap breaker).** The kernel is a
  template over per-family row caps ``Caps``: a family the batch does
  not use contributes ZERO refs, zero VMEM and zero per-step work, and
  active families are sliced to a bucketed row count instead of the
  packer maximum. A spread-only 20k-node batch carries ~100 node-sized
  rows instead of ~500, so the fused kernel -- not the XLA scan -- runs
  far past the old ~5.6k-node all-family ceiling. The caller
  (ops/assignment.solve_packed) picks caps from the packed batch and
  gates on an explicit VMEM estimate (constrained_vmem_bytes).

Semantics are the constrained scan's, family by family (citations in
ops/assignment.py greedy_assign_constrained); the differential tests
(tests/test_pallas_constrained.py) run this kernel in interpreter mode
against the XLA path on randomized constrained batches, at full and at
reduced caps.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubernetes_tpu.ops.assignment import GreedyConfig, row_node_values
from kubernetes_tpu.ops.pallas_solver import (
    COMPILER_PARAMS,
    chunk_steps,
    live_steps,
    no_node_behind,
)
from kubernetes_tpu.ops.scores import MAX_NODE_SCORE, _EPS
from kubernetes_tpu.tensors.node_tensor import NUM_FIXED_DIMS, PODS

_BIG = 1 << 30
_BIG_SOFT = float(1 << 20)

# Packer maximums (ops/topology.py, ops/affinity.py, ops/scoring.py);
# the wrapper asserts the incoming shapes still match, then slices each
# family down to the requested caps.
_G_SP = 16      # topology.MAX_GROUPS
_RA = 16        # affinity.MAX_AFF_ROWS
_RT = 16        # affinity.MAX_ANTI_ROWS
_RE = 64        # affinity.MAX_EXIST_ROWS
_GT = 16        # scoring.MAX_SOFT_GROUPS
_RP = 16        # scoring.MAX_IPA_ROWS
_G_SEL = 8      # scoring.MAX_SEL_GROUPS
# the score packer's wide shape (scoring.WIDE_IPA_ROWS, WIDE_SEL_GROUPS):
# a batch packed at it runs the specialization that holds all of it
_RP_WIDE = 64
_G_SEL_WIDE = 64


class Caps(NamedTuple):
    """Static per-family row caps for one kernel specialization. A zero
    drops the family from the kernel entirely."""

    g_sp: int = _G_SP   # hard-spread groups
    ra: int = _RA       # incoming-affinity rows
    rt: int = _RT       # incoming-anti-affinity rows
    re: int = _RE       # existing-pod anti-affinity rows
    gt: int = _GT       # soft-spread groups
    rp: int = _RP       # preferred inter-pod affinity rows
    g_sel: int = _G_SEL  # selector-spread groups


FULL_CAPS = Caps()

#: fixed row caps for a LIVE family: caps are tied to the three packer
#: families (spread / affinity / scoring) rather than sized per batch,
#: so the whole specialization space is 2^3 combos (all warmable by
#: BatchScheduler.warmup) plus a rare escalated variant per family when
#: a batch's row usage exceeds these defaults
DEFAULT_LIVE = Caps(g_sp=8, ra=8, rt=8, re=16, gt=8, rp=8, g_sel=8)


def live_caps(
    sp_present: bool,
    af_present: bool,
    sc_present: bool,
    sp_used: int = 0,
    af_used: Tuple[int, int, int] = (0, 0, 0),
    sc_used: Tuple[int, int, int] = (0, 0, 0),
    sc_wide: bool = False,
) -> Caps:
    """Caps for a batch: per packer family, absent -> 0 rows, present ->
    the DEFAULT_LIVE sizes, escalated to the packer maxima when usage
    exceeds them (usage beyond the maxima never reaches the solver --
    the packers route such pods to the host path). ``sc_wide``: the
    score family came packed at its wide shape, which the score packer
    chooses where the selector groups or the preferred-affinity rows are
    past the maxima; those two then take the whole of it, and the soft
    groups are sized by their own usage."""
    d = DEFAULT_LIVE
    if not sp_present:
        g_sp = 0
    else:
        g_sp = d.g_sp if sp_used <= d.g_sp else _G_SP
    if not af_present:
        ra = rt = re = 0
    elif (
        af_used[0] <= d.ra and af_used[1] <= d.rt and af_used[2] <= d.re
    ):
        ra, rt, re = d.ra, d.rt, d.re
    else:
        ra, rt, re = _RA, _RT, _RE
    if not sc_present:
        gt = rp = g_sel = 0
    elif sc_wide:
        gt = d.gt if sc_used[0] <= d.gt else _GT
        rp, g_sel = _RP_WIDE, _G_SEL_WIDE
    elif (
        sc_used[0] <= d.gt and sc_used[1] <= d.rp
        and sc_used[2] <= d.g_sel
    ):
        gt, rp, g_sel = d.gt, d.rp, d.g_sel
    else:
        gt, rp, g_sel = _GT, _RP, _G_SEL
    return Caps(g_sp, ra, rt, re, gt, rp, g_sel)


def _pp_layout(caps: Caps) -> Tuple[dict, int]:
    """Per-pod param matrix row layout for one specialization: offsets
    into the fat [PP_PAD, B] matrix, sized by the active caps only."""
    off = {}
    cur = 0
    for name, size in (
        ("sp_limit", caps.g_sp),
        ("sp_match", caps.g_sp),
        ("aff_act", caps.ra),
        ("aff_bump", caps.ra),
        ("anti_act", caps.rt),
        ("anti_bump", caps.rt),
        ("exist_match", caps.re),
        ("exist_bump", caps.re),
        ("soft_w", caps.gt),
        ("soft_match", caps.gt),
        ("ipa_w", caps.rp),
        ("ipa_match", caps.rp),
        ("ipa_bump", caps.rp),
        ("sel_match", caps.g_sel),
    ):
        if size:
            off[name] = cur
            cur += size
    pad = max(((cur + 7) // 8) * 8, 8)
    return off, pad


def _col(pp_block, t, chunk):
    """[X, 1] column t of the per-pod param block: one-hot multiply +
    lane-axis reduce. Pure VPU and EXACT -- an MXU one-hot matmul would
    route f32 through bf16 passes, rounding integer node values > 256
    (8-bit mantissa), which silently corrupts index extracts."""
    io = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    onehot = (io == t).astype(jnp.float32)
    return jnp.sum(pp_block * onehot, axis=1, keepdims=True)


def _at_choice(mat_f32, onehot_lane):
    """[X, 1] value-at-chosen-node extract: [X, N] * [1, N] one-hot,
    lane-axis reduce (exact, see _col)."""
    return jnp.sum(mat_f32 * onehot_lane, axis=1, keepdims=True)


def _constrained_kernel(
    *refs,
    chunk: int,
    r: int,
    caps: Caps,
    iidx: Tuple[Tuple[str, int], ...],
    oidx: Tuple[Tuple[str, int], ...],
    nin: int,
    w_least: int,
    w_balanced: int,
    w_most: int,
):
    ii = dict(iidx)
    oi = dict(oidx)

    def I(name):  # noqa: E743 - deliberate short ref accessor
        return refs[ii[name]]

    def O(name):
        return refs[nin + oi[name]]

    pp_off, _ = _pp_layout(caps)
    g_sp, ra, rt, re, gt, rp, g_sel = caps

    alloc_ref = I("alloc")
    n = alloc_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    alloc = alloc_ref[:, :]
    caps_rows = alloc[:2, :].astype(jnp.float32)
    cap_safe = jnp.maximum(caps_rows, 1.0)
    valid = I("valid")[0:1, :] > 0
    rows_ref = I("rows")
    pp_ref = I("pp")
    midx_ref = I("midx")
    podreq_ref = I("podreq")
    podnzr_ref = I("podnzr")
    active_ref = I("active")
    sig_ref = I("sig")
    flags_ref = I("flags")
    req_ref = O("req")
    nzr_ref = O("nzr")
    asg_ref = O("asg")

    if g_sp:
        sp_nv = I("sp_nv")[:, :]
        sp_vvalid = I("sp_vvalid")[:, :] > 0
        sp_node_ref = O("sp_node")
        sp_val_ref = O("sp_val")
        v = sp_val_ref.shape[1]
        val_iota = jax.lax.broadcasted_iota(jnp.int32, (g_sp, v), 1)
    if ra:
        vals_aff = I("vals_aff")[:, :]
        aff_node_ref = O("aff_node")
        aff_tot_ref = O("aff_tot")
        selfm_ref = I("selfm")
    if rt:
        vals_anti = I("vals_anti")[:, :]
        anti_ref = O("anti")
    if re:
        vals_exist = I("vals_exist")[:, :]
        exist_ref = O("exist")
    direct_ref = I("direct")
    nodeaff_ref = I("nodeaff")
    taint_ref = I("taint")
    if g_sel:
        zone_oh = I("zone_oh")[:, :]
        zone_id = I("zone_id")[0:1, :]
        sel_ref = O("sel")
        selg_ref = I("selg")
    if gt:
        soft_nv = I("soft_nv")[:, :]
        soft_ref = O("soft")
    if rp:
        ipa_nv = I("ipa_nv")[:, :]
        ipa_ref = O("ipa")
        ipaw_ref = O("ipaw")
    # Every state lives in its OUTPUT ref across the batch. The initial
    # states are inputs aliased to those outputs, but the copy is made
    # here, not left to the aliasing: read through the output ref alone,
    # the spread+affinity specialization returned garbage that changed
    # from call to call at some (n, b) on the v5e (PERF.md, PR 21)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for name in oi:
            if name != "asg":
                O(name)[:, :] = I(name + "0")[:, :]

    w_na = flags_ref[0].astype(jnp.float32)
    w_tt = flags_ref[1].astype(jnp.float32)
    w_sel = flags_ref[2].astype(jnp.float32)
    w_soft = flags_ref[3].astype(jnp.float32)
    w_ipa = flags_ref[4].astype(jnp.float32)
    ipa_live = flags_ref[5] > 0
    big = jnp.float32(1 << 20)

    def body(t, _):
        is_active = active_ref[t] > 0
        smask = rows_ref[pl.ds(midx_ref[t], 1), :] > 0

        req_state = req_ref[:, :]
        nzr_state = nzr_ref[:, :]
        free = alloc - req_state

        pcol = _col(pp_ref[:, :], t, chunk)  # [PP_PAD, 1] f32

        # -- fit (assignment._fits) -------------------------------------
        fits_all = None
        fits_pods = None
        all_zero = None
        for d in range(r):
            s = podreq_ref[t * r + d]
            ok = s <= free[d:d + 1, :]
            if d >= NUM_FIXED_DIMS:
                ok = ok | (s == 0)
            fits_all = ok if fits_all is None else (fits_all & ok)
            if d == PODS:
                fits_pods = ok
            else:
                zero_d = s == 0
                all_zero = (
                    zero_d if all_zero is None else (all_zero & zero_d)
                )
        fits = jnp.where(
            all_zero,
            fits_pods.astype(jnp.int32),
            fits_all.astype(jnp.int32),
        ) > 0
        feasible = fits & smask & valid

        # -- hard topology spread (filtering.go:322) --------------------
        if g_sp:
            sp_limit = pcol[pp_off["sp_limit"]:pp_off["sp_limit"] + g_sp]
            sp_act = sp_limit < big
            min_v = jnp.min(
                jnp.where(
                    sp_vvalid, sp_val_ref[:, :].astype(jnp.float32), big
                ),
                axis=1, keepdims=True,
            )  # [G, 1]
            sp_cnt = sp_node_ref[:, :].astype(jnp.float32)
            sp_ok_g = (sp_nv >= 0) & (sp_cnt - min_v <= sp_limit)
            spread_bad = (sp_act & ~sp_ok_g).astype(jnp.int32).max(
                axis=0, keepdims=True
            ) > 0
            feasible = feasible & ~spread_bad

        # -- required (anti-)affinity (filtering.go:404-516) ------------
        if ra:
            aff_act = pcol[pp_off["aff_act"]:pp_off["aff_act"] + ra] > 0
            aff_pos = (vals_aff >= 0) & (aff_node_ref[:, :] > 0)
            aff_all = (aff_act & ~aff_pos).astype(jnp.int32).max(
                axis=0, keepdims=True
            ) == 0
            row_tot = aff_tot_ref[:, 0:1]  # [RA, 1] f32
            total = jnp.sum(jnp.where(aff_act, row_tot, 0.0))
            self_match = selfm_ref[t] > 0
            aff_ok = aff_all | ((total == 0.0) & self_match)
            feasible = feasible & aff_ok

        if rt:
            anti_act = pcol[pp_off["anti_act"]:pp_off["anti_act"] + rt] > 0
            anti_bad_rows = (vals_anti >= 0) & (anti_ref[:, :] > 0)
            anti_bad = (anti_act & anti_bad_rows).astype(jnp.int32).max(
                axis=0, keepdims=True
            ) > 0
            feasible = feasible & ~anti_bad

        if re:
            exist_match = (
                pcol[pp_off["exist_match"]:pp_off["exist_match"] + re] > 0
            )
            exist_bad_rows = (vals_exist >= 0) & (exist_ref[:, :] > 0)
            exist_bad = (exist_match & exist_bad_rows).astype(
                jnp.int32
            ).max(axis=0, keepdims=True) > 0
            feasible = feasible & ~exist_bad

        # -- resource scores (ops/scores.py arithmetic) -----------------
        p0 = podnzr_ref[t * 2].astype(jnp.float32)
        p1 = podnzr_ref[t * 2 + 1].astype(jnp.float32)
        req_tot = nzr_state.astype(jnp.float32) + jnp.concatenate(
            [
                jnp.full((1, n), 0.0, jnp.float32) + p0,
                jnp.full((1, n), 0.0, jnp.float32) + p1,
            ],
            axis=0,
        )
        score = jnp.zeros((1, n), dtype=jnp.float32)
        if w_least:
            raw = jnp.floor(
                (caps_rows - req_tot) * MAX_NODE_SCORE / cap_safe + _EPS
            )
            per_dim = jnp.where(
                (caps_rows == 0) | (req_tot > caps_rows), 0.0, raw
            )
            score += w_least * jnp.floor(
                jnp.sum(per_dim, axis=0)[None] / 2.0 + _EPS
            )
        if w_balanced:
            frac = jnp.where(caps_rows == 0, 1.0, req_tot / cap_safe)
            diff = jnp.abs(frac[0:1, :] - frac[1:2, :])
            ba = jnp.trunc((1.0 - diff) * MAX_NODE_SCORE + _EPS)
            ba = jnp.where(
                (frac[0:1, :] >= 1.0) | (frac[1:2, :] >= 1.0), 0.0, ba
            )
            score += w_balanced * ba
        if w_most:
            raw = jnp.floor(req_tot * MAX_NODE_SCORE / cap_safe + _EPS)
            per_dim = jnp.where(
                (caps_rows == 0) | (req_tot > caps_rows), 0.0, raw
            )
            score += w_most * jnp.floor(
                jnp.sum(per_dim, axis=0)[None] / 2.0 + _EPS
            )

        # -- non-resource score families (assignment.py :627-739) -------
        feas_f = feasible.astype(jnp.float32)
        sig = sig_ref[t]
        score = score + direct_ref[pl.ds(sig, 1), :]

        na_raw = nodeaff_ref[pl.ds(sig, 1), :]
        na_max = jnp.max(na_raw * feas_f)
        score = score + jnp.where(
            na_max > 0,
            w_na * jnp.floor(100.0 * na_raw / jnp.maximum(na_max, 1.0)),
            0.0,
        )

        tt_raw = taint_ref[pl.ds(sig, 1), :]
        tt_max = jnp.max(tt_raw * feas_f)
        tt_scaled = jnp.floor(100.0 * tt_raw / jnp.maximum(tt_max, 1.0))
        score = score + w_tt * jnp.where(
            tt_max > 0, 100.0 - tt_scaled, 100.0
        )

        # SelectorSpread (default_pod_topology_spread.go:107)
        if g_sel:
            selg = selg_ref[t]
            sel_raw = sel_ref[pl.ds(jnp.maximum(selg, 0), 1), :].astype(
                jnp.float32
            )
            sel_feas = sel_raw * feas_f  # [1, N]
            sel_max_node = jnp.max(sel_feas)
            zsum = jnp.sum(
                zone_oh * sel_feas, axis=1, keepdims=True
            )  # [Z, 1]
            have_zones = jnp.max(feas_f * (zone_id >= 0)) > 0
            sel_max_zone = jnp.max(zsum)
            f_node = jnp.where(
                sel_max_node > 0,
                100.0 * (sel_max_node - sel_raw)
                / jnp.maximum(sel_max_node, 1.0),
                100.0,
            )
            zs_n = jnp.sum(zone_oh * zsum, axis=0, keepdims=True)  # [1, N]
            f_zone = jnp.where(
                sel_max_zone > 0,
                100.0 * (sel_max_zone - zs_n)
                / jnp.maximum(sel_max_zone, 1.0),
                100.0,
            )
            blended = jnp.where(
                have_zones & (zone_id >= 0),
                f_node / 3.0 + (2.0 / 3.0) * f_zone,
                f_node,
            )
            score = score + jnp.where(
                selg >= 0, w_sel * jnp.floor(blended), 0.0
            )

        # soft topology spread (podtopologyspread/scoring.go:199)
        if gt:
            soft_w = pcol[pp_off["soft_w"]:pp_off["soft_w"] + gt]
            soft_cnt = soft_ref[:, :].astype(jnp.float32)
            soft_raw = jnp.sum(
                jnp.where((soft_nv >= 0), soft_w * soft_cnt, 0.0),
                axis=0, keepdims=True,
            )  # [1, N]
            soft_inel = ((soft_w > 0) & (soft_nv < 0)).astype(
                jnp.int32
            ).max(axis=0, keepdims=True) > 0
            soft_eligible = ~soft_inel
            has_soft = jnp.max(soft_w) > 0
            dom = feasible & soft_eligible
            dom_f = dom.astype(jnp.float32)
            soft_total = jnp.sum(soft_raw * dom_f)
            soft_min = jnp.where(
                jnp.max(dom_f) > 0,
                jnp.min(jnp.where(dom, soft_raw, _BIG_SOFT)),
                _BIG_SOFT,
            )
            soft_diff = soft_total - soft_min
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

        # preferred inter-pod affinity (interpodaffinity/scoring.go)
        if rp:
            ipa_w = pcol[pp_off["ipa_w"]:pp_off["ipa_w"] + rp]
            ipa_m = pcol[pp_off["ipa_match"]:pp_off["ipa_match"] + rp]
            row_has_val = ipa_nv >= 0
            ipa_raw = jnp.sum(
                jnp.where(row_has_val, ipa_ref[:, :], 0.0) * ipa_w
                + jnp.where(row_has_val, ipaw_ref[:, :], 0.0) * ipa_m,
                axis=0, keepdims=True,
            )  # [1, N]
            ipa_mn = jnp.minimum(0.0, jnp.min(ipa_raw * feas_f))
            ipa_mx = jnp.maximum(0.0, jnp.max(ipa_raw * feas_f))
            ipa_diff = ipa_mx - ipa_mn
            ipa_score = jnp.where(
                ipa_diff > 0,
                jnp.floor(
                    100.0 * (ipa_raw - ipa_mn)
                    / jnp.maximum(ipa_diff, 1e-9) + 1e-4
                ),
                0.0,
            )
            score = score + jnp.where(ipa_live, w_ipa * ipa_score, 0.0)

        # -- masked argmax, lowest index wins ---------------------------
        masked = jnp.where(feasible, score, -jnp.inf)
        best = jnp.max(masked)
        choice = jnp.min(jnp.where(masked == best, col, jnp.int32(_BIG)))
        placed = jnp.any(feasible) & is_active
        asg_ref[t] = jnp.where(placed, choice, -1)

        # -- state updates ----------------------------------------------
        onehot = ((col == choice) & placed).astype(jnp.int32)  # [1, N]
        onehot_n = onehot.astype(jnp.float32)  # [1, N] (zero when skipped)
        placed_f = placed.astype(jnp.float32)
        for d in range(r):
            req_ref[d:d + 1, :] = (
                req_state[d:d + 1, :] + onehot * podreq_ref[t * r + d]
            )
        for d in range(2):
            nzr_ref[d:d + 1, :] = (
                nzr_state[d:d + 1, :] + onehot * podnzr_ref[t * 2 + d]
            )

        # spread replay (value-at-choice via one-hot matmul)
        if g_sp:
            sp_match = pcol[pp_off["sp_match"]:pp_off["sp_match"] + g_sp]
            sp_vc = _at_choice(sp_nv.astype(jnp.float32), onehot_n)
            sp_bump = (
                (sp_match > 0) & (sp_vc >= 0)
            ).astype(jnp.float32) * placed_f
            sp_node_ref[:, :] = sp_node_ref[:, :] + (
                sp_bump * (sp_nv == sp_vc.astype(jnp.int32))
            ).astype(jnp.int32)
            sp_val_ref[:, :] = sp_val_ref[:, :] + (
                sp_bump * (val_iota == sp_vc.astype(jnp.int32))
            ).astype(jnp.int32)

        # affinity replays
        if ra:
            aff_bump = pcol[pp_off["aff_bump"]:pp_off["aff_bump"] + ra]
            va = _at_choice(vals_aff.astype(jnp.float32), onehot_n)
            a_b = aff_bump * (va >= 0) * placed_f
            aff_node_ref[:, :] = aff_node_ref[:, :] + (
                a_b * (vals_aff == va.astype(jnp.int32))
            ).astype(jnp.int32)
            aff_tot_ref[:, :] = aff_tot_ref[:, :] + a_b

        if rt:
            anti_bump = pcol[pp_off["anti_bump"]:pp_off["anti_bump"] + rt]
            vt = _at_choice(vals_anti.astype(jnp.float32), onehot_n)
            anti_ref[:, :] = anti_ref[:, :] + (
                anti_bump * (vt >= 0) * placed_f
                * (vals_anti == vt.astype(jnp.int32))
            ).astype(jnp.int32)

        if re:
            exist_bump = (
                pcol[pp_off["exist_bump"]:pp_off["exist_bump"] + re]
            )
            ve = _at_choice(vals_exist.astype(jnp.float32), onehot_n)
            exist_ref[:, :] = exist_ref[:, :] + (
                exist_bump * (ve >= 0) * placed_f
                * (vals_exist == ve.astype(jnp.int32))
            ).astype(jnp.int32)

        # score-family replays
        if g_sel:
            sel_match = (
                pcol[pp_off["sel_match"]:pp_off["sel_match"] + g_sel]
            )
            sel_ref[:, :] = sel_ref[:, :] + (
                sel_match * placed_f * onehot.astype(jnp.float32)
            ).astype(jnp.int32)

        if gt:
            soft_match = (
                pcol[pp_off["soft_match"]:pp_off["soft_match"] + gt]
            )
            svc = _at_choice(soft_nv.astype(jnp.float32), onehot_n)
            soft_ref[:, :] = soft_ref[:, :] + (
                soft_match * (svc >= 0) * placed_f
                * (soft_nv == svc.astype(jnp.int32))
            ).astype(jnp.int32)

        if rp:
            ipa_bump = pcol[pp_off["ipa_bump"]:pp_off["ipa_bump"] + rp]
            vi = _at_choice(ipa_nv.astype(jnp.float32), onehot_n)
            vi_ok = (vi >= 0).astype(jnp.float32) * placed_f
            same_v = (ipa_nv == vi.astype(jnp.int32)).astype(jnp.float32)
            ipa_ref[:, :] = ipa_ref[:, :] + ipa_m * vi_ok * same_v
            ipaw_ref[:, :] = ipaw_ref[:, :] + ipa_bump * vi_ok * same_v
        return 0

    # flags[6]: live_steps of the whole batch
    jax.lax.fori_loop(0, chunk_steps(flags_ref[6], chunk), body, 0)


def _dense_limit(slot_groups, slot_skew, slot_self, g_cap):
    """[B, C] slot arrays -> [B, G] per-group limit (min over slots of
    skew - self; big when no slot targets the group)."""
    b = slot_groups.shape[0]
    big = jnp.int32(1 << 20)
    limit = jnp.full((b, g_cap), big, dtype=jnp.int32)
    rows = jnp.arange(b)
    for c in range(slot_groups.shape[1]):
        g = slot_groups[:, c]
        val = jnp.where(g >= 0, slot_skew[:, c] - slot_self[:, c], big)
        limit = limit.at[rows, jnp.clip(g, 0)].min(val)
    return limit


def _dense_act(slot_rows, r_cap):
    """[B, C] slot row-indices -> [B, R] 0/1 activation mask."""
    b = slot_rows.shape[0]
    act = jnp.zeros((b, r_cap), dtype=jnp.int32)
    rows = jnp.arange(b)
    for c in range(slot_rows.shape[1]):
        g = slot_rows[:, c]
        act = act.at[rows, jnp.clip(g, 0)].max(
            (g >= 0).astype(jnp.int32)
        )
    return act


def _dense_weight(slot_groups, g_cap):
    """[B, C] slot group-indices -> [B, G] slot multiplicity (soft
    spread sums per SLOT, so duplicate groups count twice)."""
    b = slot_groups.shape[0]
    w = jnp.zeros((b, g_cap), dtype=jnp.int32)
    rows = jnp.arange(b)
    for c in range(slot_groups.shape[1]):
        g = slot_groups[:, c]
        w = w.at[rows, jnp.clip(g, 0)].add((g >= 0).astype(jnp.int32))
    return w


def _node_counts(counts, node_value):
    """Value-space [R, V] counts -> node-space [R, N] (the per-batch
    one-time gather XLA does well; the kernel then never gathers)."""
    v = counts.shape[1]
    return jnp.take_along_axis(
        counts, jnp.clip(node_value, 0, v - 1), axis=1
    )


def constrained_vmem_bytes(
    n: int,
    r: int,
    u: int,
    s: int,
    z: int,
    v_sp: int,
    caps: Caps,
    chunk: int = 1024,
) -> int:
    """Estimated VMEM residency of one specialization: every node-sized
    (and spread value-space) matrix the kernel keeps live, plus the
    per-pod param block (double-buffered) and a temporaries margin. The
    use_pallas gate compares this against the budget instead of the old
    blanket node-count cap (a high-signature-diversity batch can blow
    VMEM through U or S alone -- ADVICE r4)."""
    rows_n = (
        r + 1 + u          # alloc, valid, mask rows
        + 3 * s            # direct / nodeaff / taint
        + 2 * caps.g_sp    # sp_nv + sp_node state
        + 2 * caps.ra      # vals_aff + aff_node state
        + 2 * caps.rt
        + 2 * caps.re
        + 2 * caps.gt      # soft_nv + soft state
        + 3 * caps.rp      # ipa_nv + ipa + ipaw states
        + r + 2            # req + nzr states
    )
    if caps.g_sel:
        rows_n += caps.g_sel + z + 1  # sel state + zone_oh + zone_id
    bytes_n = 4 * n * rows_n
    if caps.g_sp:
        bytes_n += 4 * v_sp * 2 * caps.g_sp  # sp_val state + sp_vvalid
    if caps.ra:
        bytes_n += 4 * 128 * caps.ra  # aff_tot
    _, pp_pad = _pp_layout(caps)
    bytes_n += 4 * pp_pad * chunk * 2  # pp block, double-buffered
    # temporaries: a handful of [1, N] f32 intermediates per family plus
    # Mosaic working space
    bytes_n += 4 * n * 24 + (1 << 20)
    return bytes_n


#: the constrained kernel's gate on constrained_vmem_bytes. The kernel
#: compiles under pallas_solver.VMEM_LIMIT_BYTES; on the v5e every
#: specialization compiled and agreed with the XLA scan up to TWICE
#: this budget (PERF.md; tools/kernel_parity.py re-runs the sweep)
VMEM_BUDGET = 13 * (1 << 20)


def _spec_plan(caps: Caps, shapes: dict, chunk: int):
    """Build the pallas_call plumbing for one specialization: ordered
    input specs, output shapes/specs, io aliases and name->position
    maps. ``shapes`` carries the dynamic dims: r, n, u, s, z, v_sp."""
    r, n = shapes["r"], shapes["n"]
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    def chunk_1d(i):
        return (i,)

    def whole(i):
        return (0, 0)

    def whole_1d(i):
        return (0,)

    in_specs = []
    iidx = {}

    def add_in(name, spec):
        iidx[name] = len(in_specs)
        in_specs.append(spec)

    add_in("midx", smem((chunk,), chunk_1d))
    add_in("podreq", smem((chunk * r,), chunk_1d))
    add_in("podnzr", smem((chunk * 2,), chunk_1d))
    add_in("active", smem((chunk,), chunk_1d))
    add_in("sig", smem((chunk,), chunk_1d))
    if caps.g_sel:
        add_in("selg", smem((chunk,), chunk_1d))
    if caps.ra:
        add_in("selfm", smem((chunk,), chunk_1d))
    add_in("flags", smem((8,), whole_1d))
    add_in("alloc", vmem((r, n), whole))
    add_in("valid", vmem((1, n), whole))
    add_in("rows", vmem((shapes["u"], n), whole))
    _, pp_pad = _pp_layout(caps)
    add_in("pp", vmem((pp_pad, chunk), lambda i: (0, i)))
    if caps.g_sp:
        add_in("sp_nv", vmem((caps.g_sp, n), whole))
        add_in("sp_vvalid", vmem((caps.g_sp, shapes["v_sp"]), whole))
    if caps.ra:
        add_in("vals_aff", vmem((caps.ra, n), whole))
    if caps.rt:
        add_in("vals_anti", vmem((caps.rt, n), whole))
    if caps.re:
        add_in("vals_exist", vmem((caps.re, n), whole))
    add_in("direct", vmem((shapes["s"], n), whole))
    add_in("nodeaff", vmem((shapes["s"], n), whole))
    add_in("taint", vmem((shapes["s"], n), whole))
    if caps.g_sel:
        add_in("zone_oh", vmem((shapes["z"], n), whole))
        add_in("zone_id", vmem((1, n), whole))
    if caps.gt:
        add_in("soft_nv", vmem((caps.gt, n), whole))
    if caps.rp:
        add_in("ipa_nv", vmem((caps.rp, n), whole))

    # aliased state inputs (order mirrors the outputs after asg)
    out_shapes = [jax.ShapeDtypeStruct((chunk * (shapes["grid"]),), jnp.int32)]
    out_specs = [smem((chunk,), chunk_1d)]
    oidx = {"asg": 0}
    aliases = {}

    def add_state(name, shape, dtype):
        iidx[name + "0"] = len(in_specs)
        in_specs.append(vmem(shape, whole))
        oidx[name] = len(out_shapes)
        out_shapes.append(jax.ShapeDtypeStruct(shape, dtype))
        out_specs.append(vmem(shape, whole))

    add_state("req", (r, n), jnp.int32)
    add_state("nzr", (2, n), jnp.int32)
    if caps.g_sp:
        add_state("sp_node", (caps.g_sp, n), jnp.int32)
        add_state("sp_val", (caps.g_sp, shapes["v_sp"]), jnp.int32)
    if caps.ra:
        add_state("aff_node", (caps.ra, n), jnp.int32)
        add_state("aff_tot", (caps.ra, 128), jnp.float32)
    if caps.rt:
        add_state("anti", (caps.rt, n), jnp.int32)
    if caps.re:
        add_state("exist", (caps.re, n), jnp.int32)
    if caps.g_sel:
        add_state("sel", (caps.g_sel, n), jnp.int32)
    if caps.gt:
        add_state("soft", (caps.gt, n), jnp.int32)
    if caps.rp:
        add_state("ipa", (caps.rp, n), jnp.float32)
        add_state("ipaw", (caps.rp, n), jnp.float32)

    for name, out_pos in oidx.items():
        key = name + "0"
        if key in iidx:
            aliases[iidx[key]] = out_pos
    return in_specs, out_shapes, out_specs, iidx, oidx, aliases


@functools.partial(
    jax.jit, static_argnames=("config", "interpret", "caps")
)
def pallas_constrained_solve(
    allocatable: jnp.ndarray,  # [N, R] int32
    requested: jnp.ndarray,  # [N, R] int32
    nzr: jnp.ndarray,  # [N, 2] int32
    valid: jnp.ndarray,  # [N] bool
    pod_requests: jnp.ndarray,  # [B, R] int32, solve order
    pod_nzr: jnp.ndarray,  # [B, 2] int32
    mask_rows: jnp.ndarray,  # [U, N] bool
    mask_index: jnp.ndarray,  # [B] int32
    active: jnp.ndarray,  # [B] bool
    spread: Tuple[jnp.ndarray, ...],
    affinity: Tuple[jnp.ndarray, ...],
    scoring: Tuple[jnp.ndarray, ...],
    config: GreedyConfig = GreedyConfig(),
    interpret: bool = False,
    caps: Optional[Caps] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Drop-in for ops/assignment.greedy_assign_constrained, fused into
    one Pallas kernel. Same family tuples, same return shape. ``caps``
    selects the family specialization (None = the packer maximums)."""
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

    if caps is None:
        # every row of the score family as it came packed
        caps = FULL_CAPS._replace(
            rp=sc_ipa_counts0.shape[0], g_sel=sc_sel_counts0.shape[0]
        )
    b, r = pod_requests.shape
    n = allocatable.shape[0]
    assert sp_counts0.shape[0] == _G_SP, "spread group cap drifted"
    assert af_counts_aff0.shape[0] == _RA
    assert af_counts_anti0.shape[0] == _RT
    assert af_counts_exist0.shape[0] == _RE
    assert sc_soft_counts0.shape[0] == _GT
    assert (sc_ipa_counts0.shape[0], sc_sel_counts0.shape[0]) in (
        (_RP, _G_SEL), (_RP_WIDE, _G_SEL_WIDE)
    ), "score family shape drifted"
    assert caps.rp <= sc_ipa_counts0.shape[0]
    assert caps.g_sel <= sc_sel_counts0.shape[0]

    # -- prologue (XLA): node-space initial counts + dense pod params ---
    g_sp, ra, rt, re, gt, rp, g_sel = caps
    pp_off, pp_pad = _pp_layout(caps)
    pp = jnp.zeros((pp_pad, b), dtype=jnp.float32)

    def put(name, mat, cap):
        if not cap:
            return None
        off = pp_off[name]
        nonlocal pp
        pp = pp.at[off:off + cap, :].set(
            mat[:, :cap].T.astype(jnp.float32)
            if mat.ndim == 2 and mat.shape[1] >= cap
            else mat.T.astype(jnp.float32)
        )

    put("sp_limit", _dense_limit(
        sp_pod_groups, sp_pod_max_skew, sp_pod_self, g_sp or 1
    ), g_sp)
    put("sp_match", sp_pod_match, g_sp)
    put("aff_act", _dense_act(af_pod_aff_rows, ra or 1), ra)
    put("aff_bump", af_pod_bump_aff, ra)
    put("anti_act", _dense_act(af_pod_anti_rows, rt or 1), rt)
    put("anti_bump", af_pod_bump_anti, rt)
    put("exist_match", af_pod_exist_match, re)
    put("exist_bump", af_pod_bump_exist, re)
    put("soft_w", _dense_weight(sc_pod_soft_groups, gt or 1), gt)
    put("soft_match", sc_pod_soft_match, gt)
    put("ipa_w", sc_pod_ipa_weight, rp)
    put("ipa_match", sc_pod_ipa_match, rp)
    put("ipa_bump", sc_pod_ipa_bump, rp)
    put("sel_match", sc_pod_sel_match, g_sel)

    ipa_live = (sc_ipa_node_value[:rp or 1] >= 0).any() if rp else False
    n_live = live_steps(active)
    flags = jnp.concatenate(
        [
            sc_weights[:5].astype(jnp.int32),
            jnp.asarray(ipa_live, dtype=jnp.int32)[None],
            n_live,
            jnp.zeros((1,), dtype=jnp.int32),
        ]
    )

    # 1-D SMEM blocks must align with the T(512)/T(1024) scalar-memory
    # tiling: sub-array chunks smaller than the tile fail layout
    # verification, so the chunk is the whole batch up to 1024 (same
    # rule as pallas_solver.py)
    chunk = min(b, 1024)
    assert b % chunk == 0, "batch must be a multiple of the pod chunk"
    grid = (b // chunk,)
    kernel_caps = caps

    v_sp = sp_counts0.shape[1]
    shapes = {
        "r": r, "n": n, "u": mask_rows.shape[0], "s": sc_direct.shape[0],
        "z": sc_zone_onehot.shape[1], "v_sp": v_sp,
        "grid": grid[0],  # asg SMEM out_shape spans the full batch
    }
    in_specs, out_shapes, out_specs, iidx, oidx, aliases = _spec_plan(
        kernel_caps, shapes, chunk
    )

    kernel = functools.partial(
        _constrained_kernel,
        chunk=chunk,
        r=r,
        caps=kernel_caps,
        iidx=tuple(sorted(iidx.items())),
        oidx=tuple(sorted(oidx.items())),
        nin=len(in_specs),
        w_least=config.least_allocated_weight,
        w_balanced=config.balanced_allocation_weight,
        w_most=config.most_allocated_weight,
    )

    # -- assemble operands in iidx order --------------------------------
    operands = {}
    operands["midx"] = mask_index.astype(jnp.int32)
    operands["podreq"] = pod_requests.astype(jnp.int32).reshape(-1)
    operands["podnzr"] = pod_nzr.astype(jnp.int32).reshape(-1)
    operands["active"] = active.astype(jnp.int32)
    operands["sig"] = sc_pod_sig.astype(jnp.int32)
    if g_sel:
        operands["selg"] = sc_pod_sel_group.astype(jnp.int32)
    if ra:
        operands["selfm"] = af_pod_self_match.astype(jnp.int32)
    operands["flags"] = flags
    operands["alloc"] = allocatable.T
    operands["valid"] = valid.astype(jnp.int32)[None, :]
    operands["rows"] = mask_rows.astype(jnp.int32)
    operands["pp"] = pp
    if g_sp:
        operands["sp_nv"] = sp_node_value[:g_sp]
        operands["sp_vvalid"] = sp_value_valid[:g_sp].astype(jnp.int32)
    if ra:
        operands["vals_aff"] = row_node_values(
            af_node_value, af_row_key_aff[:ra]
        )
    if rt:
        operands["vals_anti"] = row_node_values(
            af_node_value, af_row_key_anti[:rt]
        )
    if re:
        operands["vals_exist"] = row_node_values(
            af_node_value, af_row_key_exist[:re]
        )
    operands["direct"] = sc_direct.astype(jnp.float32)
    operands["nodeaff"] = sc_nodeaff.astype(jnp.float32)
    operands["taint"] = sc_taint.astype(jnp.float32)
    if g_sel:
        operands["zone_oh"] = jnp.transpose(sc_zone_onehot).astype(
            jnp.float32
        )
        operands["zone_id"] = sc_zone_id.astype(jnp.int32)[None, :]
    if gt:
        operands["soft_nv"] = sc_soft_node_value[:gt]
    if rp:
        operands["ipa_nv"] = sc_ipa_node_value[:rp]
    # aliased initial states
    operands["req0"] = requested.T
    operands["nzr0"] = nzr.T
    if g_sp:
        operands["sp_node0"] = _node_counts(
            sp_counts0[:g_sp], sp_node_value[:g_sp]
        )
        operands["sp_val0"] = sp_counts0[:g_sp]
    if ra:
        operands["aff_node0"] = _node_counts(
            af_counts_aff0[:ra], operands["vals_aff"]
        )
        operands["aff_tot0"] = jnp.broadcast_to(
            af_counts_aff0[:ra].sum(axis=1, keepdims=True).astype(
                jnp.float32
            ),
            (ra, 128),
        )
    if rt:
        operands["anti0"] = _node_counts(
            af_counts_anti0[:rt], operands["vals_anti"]
        )
    if re:
        operands["exist0"] = _node_counts(
            af_counts_exist0[:re], operands["vals_exist"]
        )
    if g_sel:
        operands["sel0"] = sc_sel_counts0[:g_sel]
    if gt:
        operands["soft0"] = _node_counts(
            sc_soft_counts0[:gt], sc_soft_node_value[:gt]
        )
    if rp:
        operands["ipa0"] = _node_counts(
            sc_ipa_counts0[:rp], sc_ipa_node_value[:rp]
        )
        operands["ipaw0"] = _node_counts(
            sc_ipa_wcounts0[:rp], sc_ipa_node_value[:rp]
        )

    args = [None] * len(iidx)
    for name, pos in iidx.items():
        args[pos] = operands[name]

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=tuple(out_shapes),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        input_output_aliases=aliases,
        compiler_params=COMPILER_PARAMS,
        # stable device-trace name (see pallas_greedy_solve)
        name="pallas_constrained_solve",
        interpret=interpret,
    )(*args)
    asg = no_node_behind(outs[oidx["asg"]], n_live)
    req_out_t = outs[oidx["req"]]
    nzr_out_t = outs[oidx["nzr"]]
    return asg, req_out_t.T, nzr_out_t.T
