"""BatchScheduler: the TPU fast path -- drain the activeQ as a batch and
solve placement on device.

This is the north-star replacement for the reference's serialized
scheduleOne loop (/root/reference/pkg/scheduler/scheduler.go:548): the
activeQ drain becomes the batch (SURVEY.md section 2.1 "TPU equivalent"),
the NodeInfo snapshot becomes an incrementally-updated NodeTensor, the
Filter/Score plugins become the device mask/score matrices + host static
mask, and selectHost becomes the argmax inside the assignment scan.

The score weights are the PROFILE's, on the device as on the host: a
batch is one profile's pods, its resource score rule (``GreedyConfig``)
is that profile's enabled NodeResourcesLeastAllocated / BalancedAllocation
/ MostAllocated and their weights in every tier
(``BatchScheduler.solver_config``), and its label scorers take the same
profile's weights (``prof0.score_plugin_weights()``). A profile that
scores with a resource scorer no tier models (RequestedToCapacityRatio,
NodeResourceLimits) sends its pods down the host path, counted in
``pods_fallback``. Only a driver that passes ``solver_config`` overrides
the profiles.

The scheduling-framework contract stays intact: Reserve, Permit
(gang-scheduling hook), PreBind, Bind and the failure/Unreserve paths run
through the same Framework pipeline per pod (finish_schedule). Required
(anti-)affinity, topology spread, the full default score family
(including preferred inter-pod affinity), host ports (static mask for
existing pods + synthetic anti rows for within-batch conflicts), gang
quorum masks, and batched preemption all solve on device; the few
remaining shapes the solver doesn't model (volume-bound pods,
spread+nodeSelector eligibility coupling -- see solver_supported) fall
back to the sequential oracle path (attempt_schedule), exactly like the
reference runs unsupported pods through extenders.
"""

from __future__ import annotations

import collections
import logging
import math
import os
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

import jax

from kubernetes_tpu.api.types import Binding, POD_GROUP_LABEL, Pod
from kubernetes_tpu.apiserver.server import Conflict as ApiConflict
from kubernetes_tpu.cache.node_info import pod_host_ports
from kubernetes_tpu.scheduler.admission import (
    Admission,
    classify_pod as _classify_pod,
    solver_unsupported_reason,
)
from kubernetes_tpu.scheduler.device_state import (
    DeviceNodeState,
    delta_slot_pieces,
)
from kubernetes_tpu.framework.interface import (
    CycleState,
    FitError,
    PodInfo,
    Status,
    StatusCode,
)
from kubernetes_tpu.ops.assignment import (
    GreedyConfig,
    NO_NODE,
    UNMODELLED_RESOURCE_SCORE_PLUGINS,
    greedy_assign_compact,
    mesh_shard_uses_kernel,
    sinkhorn_assign,
    solve_packed,
)
from kubernetes_tpu.ops.affinity import (
    add_host_port_rows,
    cluster_has_required_anti_affinity,
    noop_affinity_tensors,
    pack_affinity_batch,
    pad_affinity_tensors,
)
from kubernetes_tpu.ops import family_facts
from kubernetes_tpu.ops.host_masks import (
    MaskRowCache,
    mask_rows_upload,
    static_mask_compact,
)
from kubernetes_tpu.ops.scoring import (
    ScoreEnvelopeCut,
    ScoreEnvelopeExceeded,
    batch_selector_spread_live,
    cluster_has_affinity_scoring,
    noop_score_tensors,
    pack_score_batch,
    pad_score_tensors,
)
from kubernetes_tpu.ops.topology import (
    noop_spread_tensors,
    pack_spread_batch,
    pad_spread_tensors,
)
from kubernetes_tpu.robustness.circuit import SolveTimeout
from kubernetes_tpu.robustness.containment import (
    ContainmentConfig,
    QuarantineManager,
)
from kubernetes_tpu.robustness.faults import (
    FaultPoint,
    PoisonError,
    SchedulerCrashed,
    get_injector,
    pod_is_poisoned,
    poison_stamp_maybe,
)
from kubernetes_tpu.robustness.ladder import (
    LadderExhausted,
    RobustnessConfig,
    SolverLadder,
    TIER_HOST_GREEDY,
    TIER_PALLAS,
    TIER_SEQUENTIAL,
    TIER_XLA,
    host_greedy_assign,
)
from kubernetes_tpu.scheduler.generic import SNAPSHOT_STATE_KEY
from kubernetes_tpu.scheduler.scheduler import Scheduler
from kubernetes_tpu.tensors import NodeTensorCache, pack_pod_batch
from kubernetes_tpu.utils import flightrecorder
from kubernetes_tpu.utils import metrics

try:
    from kubernetes_tpu.native import assume_clones as _assume_clones
    from kubernetes_tpu.native import commit_gather as _commit_gather
except Exception:  # noqa: BLE001 - pure-Python fallback
    _assume_clones = None
    _commit_gather = None


def _commit_gather_py(solver_infos, order, assigns, names):
    """Pure-Python fallback for native commit_gather: gather the placed
    slots' PodInfos, build their assumed clones with spec.node_name set,
    and resolve host names, in one pass (identical semantics to the C
    loop; differentially tested in tests/test_native_commit.py)."""
    pis, clones, hosts = [], [], []
    for oi, ci in zip(order, assigns):
        pi = solver_infos[oi]
        host = names[ci]
        assumed = pi.pod.assumed_clone()
        assumed.spec.node_name = host
        pis.append(pi)
        clones.append(assumed)
        hosts.append(host)
    return pis, clones, hosts


def _gang_contiguous(order: np.ndarray, gangs) -> np.ndarray:
    """``order`` with every gang's members together, at the place of the
    gang's first member and in the order they had: a gang is then
    solved as one run of steps, so the first gang a pass leaves short
    saw everything the gangs before it left. A gang's members share
    their priority wherever a job controller made them; where they do
    not, the run stands at its first member's place."""
    runs: dict = {}
    out = []
    for i in order.tolist():
        key = gangs[i]
        if key is None:
            out.append((i,))
            continue
        run = runs.get(key)
        if run is None:
            run = runs[key] = []
            out.append(run)
        run.append(i)
    return np.fromiter(
        (i for run in out for i in run), dtype=order.dtype, count=len(order)
    )


class _EagerDownload:
    """Device->host result copy started at DISPATCH time on its own
    daemon thread, so the transfer (and the numpy conversion) rides
    concurrently with the next batch's pop/pack instead of serializing
    inside the committer. ``result()`` blocks until the copy lands; the
    committer calls it under the same wall-clock watchdog that guarded
    the old in-committer ``np.asarray`` (a wedged device still times
    out and trips the breaker)."""

    __slots__ = ("_done", "_value", "_error")

    def __init__(self, dev) -> None:
        self._done = threading.Event()
        self._value = None
        self._error: Optional[BaseException] = None
        threading.Thread(
            target=self._run, args=(dev,), name="solve-download",
            daemon=True,
        ).start()

    def _run(self, dev) -> None:
        try:
            self._value = np.asarray(dev)
        except BaseException as e:  # noqa: BLE001 - re-raised in result()
            self._error = e
        finally:
            self._done.set()

    def result(self):
        self._done.wait()
        if self._error is not None:
            raise self._error
        return self._value

logger = logging.getLogger(__name__)


class _JitCacheWatch:
    """Runtime jit-cache watchdog: diff the solver families' compiled-
    signature counts after each solve. Every growth books
    ``scheduler_tpu_jit_compiles_total{signature}``; growth after
    ``seal()`` (end of warmup) is a MID-RUN recompile and additionally
    fires a flight-recorder mark + warning -- the production
    generalization of the dryrun's test-only ``mesh_packed_cache_size``
    probe. O(families) dict reads per batch."""

    __slots__ = ("_mesh", "_last", "_sealed")

    def __init__(self, mesh=None) -> None:
        self._mesh = mesh
        self._last: dict = {}
        self._sealed = False

    def seal(self) -> None:
        """Warmup is done: from here, cache growth is unplanned."""
        self.refresh()
        self._sealed = True

    def refresh(self) -> None:
        from kubernetes_tpu.ops.assignment import jit_cache_sizes

        for sig, n in jit_cache_sizes(self._mesh).items():
            prev = self._last.get(sig, 0)
            if n > prev:
                metrics.jit_compiles.inc(n - prev, signature=sig)
                if self._sealed:
                    flightrecorder.mark(
                        "jit_recompile", signature=sig, cache_size=n,
                        compiles=n - prev,
                    )
                    logger.warning(
                        "mid-run jit recompile: %s cache grew %d -> %d",
                        sig, prev, n,
                    )
            self._last[sig] = n


POD_BUCKET = 64  # batch padded to a multiple of this to bound re-JITs
#: constrained batches above this node capacity take the sequential host
#: path: the XLA constrained scan's compile at >32k nodes ran for
#: minutes on an earlier machine, and the fused kernel's VMEM gate
#: already excludes these shapes. The cap was set there and is not
#: re-measured on this one (PERF.md "Decisions to re-measure"). The
#: 50k-node regime is a plain-pod churn workload
#: (BASELINE #5); constrained families at that scale are out of the
#: supported envelope, like the reference's adaptive sampling regime.
CONSTRAINED_NODE_CAP = 32768
MASK_ROW_BUCKET = 8  # dedup static-mask rows padded to a multiple of this
#: solver batches in flight between dispatcher and committer. With the
#: result download riding its own thread from dispatch time
#: (_EagerDownload) extra slots keep the committer fed instead of idling
#: on the result download -- but only when the host has cores to run
#: them: on a 2-core box a deeper pipeline steals GIL time from the
#: committer, so the depth scales with the host instead of being raised
#: unconditionally. Both this and _EAGER_DOWNLOAD_OK were sized on an
#: earlier machine and are not re-measured on this one (PERF.md
#: "Decisions to re-measure").
MAX_INFLIGHT = max(3, min(6, (os.cpu_count() or 4) // 2))
#: eager result downloads need a core to run on; see _eager_download
_EAGER_DOWNLOAD_OK = (os.cpu_count() or 4) >= 4


#: the constrained layouts' family combos: the fused kernel specializes
#: per PRESENT family combo (pallas_constrained.live_caps) -- 2^3 - 1,
#: each a distinct Caps and pallas compile; the triple comes last
_FAMILY_COMBOS = (
    ("sp",), ("af",), ("sc",), ("sp", "af"), ("sp", "sc"), ("af", "sc"),
    ("sp", "af", "sc"),
)


def _family_pieces(
    padded: int, n: int, live, const_absent: bool = True
) -> list:
    """The no-op pieces of one family combo, as warm-up sends them: the
    ``live`` families ride the buffer as real arrays in the shape of a
    live batch's, absent ones as ConstPiece device constants (the
    single-device dispatch's ``fam_pieces`` contract) or, on a mesh
    (``const_absent`` false), as real arrays as well."""
    from kubernetes_tpu.ops.assignment import ConstPiece

    groups = {
        "sp": noop_spread_tensors(padded, n),
        "af": noop_affinity_tensors(padded, n),
        "sc": noop_score_tensors(padded, n, live_shape="sc" in live),
    }
    return [
        (f"{prefix}{i}", np.asarray(a))
        if prefix in live or not const_absent
        else (f"{prefix}{i}", ConstPiece.from_uniform(a))
        for prefix, arrs in groups.items()
        for i, a in enumerate(arrs)
    ]


class ScoreSignatureCut(Exception):
    """A batch's pods asked for more rows than a live score family
    carries (``ScoreEnvelopeCut``: the static rows, or the selector
    groups and preferred-affinity rows of the wide shape): ``head`` is
    inside the envelope, ``tail`` is what came after it in the solve
    order. ``schedule_batch`` solves the one, then the other, both on
    the device."""

    def __init__(self, head: List[PodInfo], tail: List[PodInfo]) -> None:
        super().__init__("batch cut at the score signature cap")
        self.head, self.tail = head, tail


def solver_supported(pod: Pod) -> bool:
    """Constraints the device solver models today. Anything else falls
    back to the sequential path (still fully correct, just not batched).

    Hard spread solves on device via the group-count scan
    (ops/topology.py), REQUIRED pod (anti-)affinity via the count-tensor
    replay (ops/affinity.py), preferred terms ride the ipa_* score
    family, host ports ride the static mask + synthetic anti rows, and
    attachable-volume COUNT limits ride the ``[N, R]`` volume columns
    (tensors/node_tensor.py) -- so the remaining host-only shapes are
    NUMA-aligned pods, soft spread with node scoping, and direct
    conflict-bearing volume sources. The per-shape reason strings (and
    the lister-dependent volume half of the decision) live in
    scheduler/admission.py, which computes the full classification once
    at informer ingest."""
    return not solver_unsupported_reason(pod)


#: per-batch expected-delta ring bound of the device-resident state
#: (scheduler/device_state.py), which is handed it: the host can trail the
#: device by at most the in-flight batches plus the mirror/assume window
_SHADOW_RING_CAP = MAX_INFLIGHT + 2



class BatchScheduler(Scheduler):
    def __init__(
        self,
        *args,
        max_batch: int = 256,
        solver_config: Optional[GreedyConfig] = None,
        tensor_cache: Optional[NodeTensorCache] = None,
        batch_window: float = 0.01,
        solver_mode: str = "greedy",
        mesh=None,
        robustness_config: Optional[RobustnessConfig] = None,
        containment_config: Optional[ContainmentConfig] = None,
        **kwargs,
    ) -> None:
        """``solver_mode``: "greedy" replays the sequential argmax exactly
        (parity mode); "sinkhorn" adds the entropic-OT global prior for
        the churn/rebalance regime (ops/sinkhorn.py) on unconstrained
        batches -- constrained batches always use the greedy replay.

        ``mesh``: an optional ``jax.sharding.Mesh`` with a "nodes" axis;
        node-dimension tensors are device_put with node-axis shardings and
        GSPMD partitions the solver scan across the mesh, inserting the
        cross-shard argmax/psum collectives over ICI (SURVEY.md
        section 2.5)."""
        super().__init__(*args, **kwargs)
        self.max_batch = max_batch
        self.solver_config = solver_config  # the property below
        for name in self._host_scored_profiles:
            logger.warning(
                "profile %r enables a resource scorer the device does not "
                "model (%s): its pods are scheduled on the host path",
                name, ", ".join(
                    p for p in UNMODELLED_RESOURCE_SCORE_PLUGINS
                    if self.profiles[name].score_plugin_weights().get(p)
                ),
            )
        self.tensor_cache = tensor_cache or NodeTensorCache()
        # static mask rows kept from batch to batch (ops/host_masks.py)
        self.mask_row_cache = MaskRowCache()
        self.family_facts = family_facts.FamilyFacts()
        self.batch_window = batch_window
        # SLO-adaptive batching (streaming/autobatch.py): when a
        # controller is attached it rewrites batch_window AND these two
        # knobs between batches -- dispatch_batch_cap bounds how many
        # pods one pop_batch drains, solve_pad floors the padded solve
        # shape below max_batch so latency-mode batches stop paying the
        # full-pad fixed solve cost. None = static knobs (today's
        # behavior, zero overhead).
        self.autobatch = None
        self.dispatch_batch_cap: Optional[int] = None
        self.solve_pad: Optional[int] = None
        # solve-pad shapes warmup() pre-compiles beyond max_batch
        # (attach_autobatch adds every controller rung)
        self._warmup_pads: set = {max_batch}
        # measured steady-solve seconds per warmed pad (warmup fills
        # this post-compile); feeds AutoBatchController.calibrate so
        # the rung ladder is sized from what each pad actually costs
        self.pad_solve_seconds: dict = {}
        if solver_mode not in ("greedy", "sinkhorn"):
            raise ValueError(f"unknown solver_mode {solver_mode!r}")
        self.solver_mode = solver_mode
        self.mesh = mesh
        # sharded mesh delta path (PR 9): the mesh dispatch rides the
        # same single-buffer + device-resident-carry + delta-scatter
        # machinery as the single-device path, through the sharded twin
        # (ops/assignment.make_mesh_packed_solver) with shard-local row
        # scatters. Greedy mesh batches additionally solve on the
        # shard_map'd PALLAS tier (PR 10, ops/assignment._mesh_shard_solver):
        # per-shard fused step + one best-of-shards combine per pod,
        # ladder [pallas, xla] with breaker fallback to the GSPMD twin;
        # KTPU_MESH_PALLAS=0 pins the twin-only behavior (predicate:
        # ops/assignment.mesh_pallas_candidate).
        self.batches_solved = 0
        self.pods_solved_on_device = 0
        self.pods_fallback = 0
        # perf-matrix visibility (VERDICT r2: the drain cliff and the
        # envelope fallbacks were unmetered)
        self.envelope_fallbacks = 0  # whole batches sent to host by packers
        # batches past the score family's signature cap: cut in two on
        # the device / sent whole to the host path (a gang's, a bisection's)
        self.score_signature_cuts = 0
        self.score_signature_host = 0
        self.pipeline_drains = 0  # constrained dispatch drained the pipeline
        self.gang_resolves = 0  # quorum-failure re-solves (_gang_fixup)
        self.nominee_constrained_fallbacks = 0  # nominees + constraints
        #: the node-spec epoch the last batch packed against
        self._packed_node_epoch = 0
        # what the chip holds between batches, its lock and its counters
        # (state_uploads and the rest, forwarded below)
        self.device_state = DeviceNodeState(_SHADOW_RING_CAP)
        # pipelined batches flow dispatcher -> committer through this
        # bounded FIFO; the committer thread owns download + commit so the
        # dispatcher never blocks on a device round trip
        self._pending_q: "collections.deque" = collections.deque()
        self._pending_cv = threading.Condition()
        self._committer: Optional[threading.Thread] = None
        # failures parked across in-flight batches for one combined
        # preemption wave (touched only by the committing thread: the
        # committer loop, or the dispatcher on the synchronous paths,
        # which drain the pipeline first)
        self._deferred_preempt: List = []
        self._volume_listers = None
        self._deferred_since = 0.0
        self._prewarm_next_commit = False
        self._committer_stop = False
        # -- admission classifier state (scheduler/admission.py) ---------
        # volume-topology generation: bumped by every PV/PVC/StorageClass/
        # CSINode event (eventhandlers), compared against each PVC-bearing
        # pod's cached admission record at pop time
        self._volume_topo_gen = 0
        # memo ownership token: an admission record from another scheduler
        # instance (different extenders / dims registry) is re-classified
        self._admission_token = object()
        self.admissions_classified = 0
        self.reclassifications = 0
        self.volume_reject_retries = 0  # device NO_NODE -> host re-checks
        # the plain-pod fast path (native ingest_stamp / its twin): ONE
        # shared read-only Admission record serves every plain pod, and
        # the native cfg tuple is built once per scheduler
        self._plain_adm: Optional[Admission] = None
        self._ingest_cfg: Optional[tuple] = None
        # per-stage wall-clock totals, ALWAYS on (bench.py emits
        # profile_stage_seconds every round; only the per-pod classify
        # timer stays behind profile_stages): every flightrecorder.stage
        # of this scheduler's threads, and of the informers it registers
        # handlers on (eventhandlers.py), adds here
        self.profile_stages = False
        self.stage_totals = flightrecorder.StageTotals()
        # flight-recorder spine (utils/flightrecorder.py): the pop-side
        # stage timings of the CURRENT drain, consumed by the first
        # span it dispatches (pop_batch drains before the flush loop
        # splits batches, so the pop cost belongs to the drain's head)
        # (drain-work seconds, arrival-wait seconds) of the current drain
        self._pop_note: Optional[Tuple[float, float]] = None
        # runtime jit-cache watchdog: sealed at the end of warmup();
        # unsealed growth still counts compiles, it just isn't flagged
        # as a mid-run recompile (tests that skip warmup stay quiet)
        self._jit_watch = _JitCacheWatch(mesh)
        # collect-at-idle gc policy, engaged only by the production run
        # loop (tests driving schedule_batch directly keep gc untouched)
        self._gc_guard = None
        # solver degradation ladder (robustness/): per-tier circuit
        # breakers + retry + watchdog around every device interaction,
        # so a sick device path steps down Pallas -> XLA -> host greedy
        # -> sequential oracle and the batch ALWAYS completes
        self.ladder = SolverLadder(robustness_config)
        if mesh is not None and not mesh_shard_uses_kernel():
            # the shard_map tier is offered on any backend; only a TPU
            # runs its kernel, and only such a batch is counted pallas
            self.ladder.book_as[TIER_PALLAS] = TIER_XLA
        # bind retries share the ladder's policy + injectable sleep
        self.bind_retry_policy = self.ladder.config.retry
        self._retry_sleep = self.ladder.config.sleep
        # set when the committer failed to join at shutdown (satellite:
        # the silent join(timeout=10) hang) -- surfaced via the
        # scheduler_degraded_health gauge and this flag
        self.commit_degraded = False
        # -- blast-radius containment (robustness/containment.py) --------
        # poison bisection + the quarantine ledger: a ladder-exhausted
        # batch is split O(log B)-wise on the warm pad rungs instead of
        # failing whole to the sequential floor; isolated pods take
        # escalating holds and park with a typed PodQuarantined
        # condition when the strike budget runs out
        self.containment_config = containment_config or ContainmentConfig()
        self.quarantine = QuarantineManager(
            self.queue, self.client, self.containment_config
        )
        # a real spec update releasing a PARKED pod must also clear its
        # apiserver-visible PodQuarantined condition
        self.queue.on_quarantine_release = (
            self.quarantine.clear_condition_async
        )
        self.bisections = 0
        self.pods_quarantined = 0
        # ladder_exhausted crash-loop detector: the uid signature of the
        # last exhausted batch and how many consecutive times it
        # exhausted (>= 2 books exhausted_crashloop and forces the
        # containment path over another identical full-batch retry)
        self._last_exhaust_sig: Optional[frozenset] = None
        self._exhaust_repeats = 0
        # batches the mesh's shard_map tier solved, with or without its
        # kernel (mesh_solver_tier)
        self.mesh_shard_solves = 0
        # the mirror sequence number of the newest batch whose commit
        # has finished (its pods assumed into the cache); written by
        # the committer alone, read by the dispatcher before a refresh
        self._assumed_seq = 0
        # device-loss rebuild: perf_counter at loss detection; cleared
        # (and metered into device_rebuild_ms) when the next jitted
        # solve lands on fully re-uploaded state
        self._device_lost_at: Optional[float] = None
        # -- pipelined speculative dispatch (ISSUE 18) --------------------
        # in-flight depth knob: the bench's serial arm pins 1 so the
        # pipelined/serial comparison runs the same code path
        self.max_inflight = MAX_INFLIGHT
        self.speculative_launches = 0
        self.speculative_rewinds = 0

    # -- the device state's counters, read where they always were ------------

    state_uploads = property(lambda self: self.device_state.state_uploads)
    state_reuses = property(lambda self: self.device_state.state_reuses)
    delta_rows_uploaded = property(
        lambda self: self.device_state.delta_rows_uploaded
    )
    membership_row_patches = property(
        lambda self: self.device_state.membership_row_patches
    )
    carry_divergences = property(
        lambda self: self.device_state.carry_divergences
    )
    carry_audits = property(lambda self: self.device_state.audits)
    carry_audit_heals = property(lambda self: self.device_state.audit_heals)

    # -- the resource score rule ----------------------------------------------

    @property
    def solver_config(self) -> GreedyConfig:
        """The resource score rule of the batches: the driver's override
        where one was passed or set, else the first profile's own."""
        if self._solver_override is not None:
            return self._solver_override
        return self.solver_configs()[0]

    @solver_config.setter
    def solver_config(self, override: Optional[GreedyConfig]) -> None:
        """The rule of each profile's batches, the same in every tier:
        ``override`` for all of them, or with None each profile's own
        enabled resource scorers and weights. A profile that scores with
        a resource scorer no tier models (RequestedToCapacityRatio,
        NodeResourceLimits) has no rule: its pods keep the host path,
        counted in ``pods_fallback``, where the framework's own plugins
        score them."""
        self._solver_override = override
        self._profile_rules = {
            name: override
            or GreedyConfig.from_score_weights(fw.score_plugin_weights())
            for name, fw in self.profiles.items()
        }
        self._host_scored_profiles = frozenset(
            name for name, rule in self._profile_rules.items()
            if rule is None
        )

    def solver_configs(self) -> List[GreedyConfig]:
        """The distinct rules this scheduler's batches are solved
        under (warm-up compiles each)."""
        rules = [r for r in self._profile_rules.values() if r is not None]
        return list(dict.fromkeys(rules)) or [GreedyConfig()]

    # -- one batch ----------------------------------------------------------

    def schedule_batch(
        self, timeout: Optional[float] = None, pipeline: bool = False
    ) -> int:
        """Pop up to max_batch pods, solve device-supported ones in one
        jitted call, route the rest through the sequential path. Returns
        the number of pods processed.

        With ``pipeline=True`` (the production run loop) a pure-resource
        batch may be left in flight on device: the NEXT call dispatches
        its own solve against the device-resident carry BEFORE downloading
        and committing the previous result, so the device round trip
        is overlapped with host commit work instead of serializing with
        it."""
        ab = self.autobatch
        if ab is not None:
            # one controller decision per interval, taken between
            # batches on the dispatcher thread (deterministic ordering
            # with the drain; the callable window below lets a shrink
            # land mid-wait too)
            ab.maybe_step(self)
        cap = self.dispatch_batch_cap
        size = (
            self.max_batch
            if not cap
            else max(1, min(self.max_batch, cap))
        )
        # the queue times drain WORK (pop_batch) and arrival wait
        # (pop_wait) apart, into this scheduler's totals
        batch_infos = self.queue.pop_batch(
            size,
            timeout=timeout,
            window=(self._live_window if ab is not None
                    else self.batch_window),
            totals=self.stage_totals,
        )
        if batch_infos:
            batch_infos = self._with_gang_siblings(batch_infos, size)
        # the first span this drain dispatches claims the pop timings
        self._pop_note = (
            self.queue.last_pop_work_seconds,
            self.queue.last_pop_wait_seconds,
        )
        guard = self._gc_guard
        if not batch_infos:
            # idle: finish whatever is still in flight
            self._drain_pending()
            if self._deferred_preempt:
                # safety net: a mixed burst whose tail took the fallback
                # path produces no further batch commits to trigger the
                # deferred wave
                self._flush_deferred_preemptions()
            if guard is not None:
                guard.idle()
            return 0
        if guard is not None:
            guard.active()
        pod_scheduling_cycle = self.queue.scheduling_cycle

        # Process in activeQ order: a fallback pod must not jump ahead of
        # higher-priority solver pods popped before it, so solver runs are
        # flushed at each fallback boundary (each flush re-snapshots and
        # re-checks cluster compatibility, so fallback capacity claims and
        # newly-placed anti-affinity pods are visible to later solver pods).
        solver_infos: List[PodInfo] = []

        def flush() -> None:
            runs = [list(solver_infos)] if solver_infos else []
            solver_infos.clear()
            while runs:
                run = runs.pop(0)
                try:
                    if pipeline:
                        self._solve_pipelined(run, pod_scheduling_cycle)
                    else:
                        self._solve_and_commit(run, pod_scheduling_cycle)
                except ScoreSignatureCut as cut:
                    # both parts next, in their order: no pod leaves the
                    # dispatcher, none goes to the host path
                    runs[:0] = [cut.head, cut.tail]
                    continue
                self.batches_solved += 1

        # admission is a precomputed-field read here: the classifier ran
        # at informer ingest (eventhandlers), so the hot loop does one
        # memo get per pod instead of re-walking annotations, volume
        # sources, and NUMA hints per pod per cycle (the round-5
        # regression). Stale volume classifications re-check inside
        # _admission_of.
        profiling = self.profile_stages
        inj = get_injector()
        quota_gate = self.quota
        host_scored = self._host_scored_profiles  # empty but for RTCR
        for pi in batch_infos:
            if self._skip_pod_schedule(pi.pod):
                continue
            if quota_gate is not None and not self._quota_admit(
                pi, pod_scheduling_cycle
            ):
                # parked typed-QuotaExceeded (woken by quota/usage
                # events) or routed to the backoff clock; either way it
                # never enters a batch uncharged
                continue
            if inj is not None:
                # one POISON_POD draw per pod ever (uid-keyed, so the
                # verdict survives informer object replacement): a
                # firing draw stamps the pod and the fault follows it
                # through every later batch
                poison_stamp_maybe(pi.pod)
            if profiling:
                # per pod, so a total alone: no span
                t_cls = time.perf_counter()
                adm = self._admission_of(pi.pod)
                self.stage_totals.add(
                    "classify", time.perf_counter() - t_cls
                )
            else:
                adm = self._admission_of(pi.pod)
            if adm.device_ok and not (
                host_scored
                and pi.pod.spec.scheduler_name in host_scored
            ):
                # one profile per solver batch: score weights (the
                # resource scorers' too, ``_profile_rules``) and owner
                # lookups are profile-scoped (the sequential path resolves
                # them per pod, scheduler.go:741)
                if solver_infos and (
                    solver_infos[0].pod.spec.scheduler_name
                    != pi.pod.spec.scheduler_name
                ):
                    flush()
                solver_infos.append(pi)
            else:
                flush()
                # the sequential path filters against the host cache,
                # which must include every in-flight placement
                self._drain_pending()
                self.pods_fallback += 1
                self.attempt_schedule(pi)
        flush()
        if not pipeline:
            self._drain_pending()
        return len(batch_infos)

    def _solve_and_commit(
        self, solver_infos: List[PodInfo], pod_scheduling_cycle: int
    ) -> None:
        """Synchronous solve: dispatch + download + commit in one call,
        with the gang quorum fixup between solve and commit."""
        gangs = self._gang_keys(solver_infos)
        try:
            if gangs is None:
                pending = self._dispatch_solve(
                    solver_infos, pod_scheduling_cycle
                )
            else:
                pending = self._gang_solve(
                    solver_infos, pod_scheduling_cycle, gangs
                )
        except SchedulerCrashed:
            self._simulate_crash()  # no recovery: the process "died"
            return
        except ScoreSignatureCut:
            raise  # schedule_batch solves the two parts
        except Exception:
            # a pass of the gang fixup failed (a download that timed
            # out): the batch's pods go round again, none is stranded
            logger.exception("gang solve failed")
            self._recover_failed_batch({
                "solver_infos": solver_infos, "cycle": pod_scheduling_cycle,
            })
            return
        if pending is None:
            return
        # completed by this thread, at once: the hand-off waits nothing
        pending["handed_off"] = time.perf_counter()
        try:
            self._complete_solve(pending)
        except SchedulerCrashed:
            self._simulate_crash()  # no recovery: the process "died"
        except Exception:
            # a failed download/commit must not crash the dispatch loop:
            # requeue the batch's pods (they retry on whatever tier the
            # breakers now route to) and drop the stale carry
            logger.exception("synchronous batch completion failed")
            self._recover_failed_batch(pending)

    # -- gang all-or-nothing group masks (SURVEY stage 6) --------------------

    def _with_gang_siblings(
        self, batch_infos: List[PodInfo], size: int
    ) -> List[PodInfo]:
        """A gang is decided whole or not at all, so a batch that holds
        some of a gang's members takes the others that are queued (at
        the back of the active queue, backing off, parked) with it,
        while the batch has room: the gang's members that have just
        arrived find the ones an earlier batch sent away for want of
        them, and no part of a gang holds nodes at Permit for a part
        that is queued behind other gangs. A batch without a gang
        member pays one label read a pod."""
        inside: dict = {}
        for pi in batch_infos:
            group = pi.pod.metadata.labels.get(POD_GROUP_LABEL)
            if group:
                inside.setdefault(
                    (pi.pod.metadata.namespace, group), set()
                ).add(pi.pod.metadata.uid)
        if not inside:
            return batch_infos
        with flightrecorder.stage(
            "gang_siblings", totals=self.stage_totals, groups=len(inside)
        ) as siblings:
            cos = self._coscheduling(batch_infos)
            if cos is None:
                return batch_infos
            room = size - len(batch_infos)
            had = len(batch_infos)
            for key, uids in inside.items():
                if room <= 0:
                    break
                known, holding = cos.members(*key)
                missing = [
                    pod_key for uid, pod_key in known.items()
                    if uid not in uids and uid not in holding
                ]
                if missing and len(missing) <= room:
                    took = self.queue.take(missing)
                    batch_infos.extend(took)
                    room -= len(took)
            siblings.set_metadata(took=len(batch_infos) - had)
        return batch_infos

    def _gang_keys(self, solver_infos: List[PodInfo]):
        """``(namespace, group)`` of every pod of the batch, None for a
        pod outside any gang; None for a batch that holds no gang
        member, or whose profile runs no Coscheduling (the group label
        then carries no gang semantics: never mask)."""
        keys = [
            (pi.pod.metadata.namespace, g) if g else None
            for pi in solver_infos
            for g in (pi.pod.metadata.labels.get(POD_GROUP_LABEL),)
        ]
        if not any(keys) or self._coscheduling(solver_infos) is None:
            return None
        return keys

    def _coscheduling(self, solver_infos: List[PodInfo]):
        prof = self.profiles.get(solver_infos[0].pod.spec.scheduler_name)
        return (
            prof.plugin_instance("Coscheduling") if prof is not None else None
        )

    def _gang_solve(
        self, solver_infos: List[PodInfo], pod_scheduling_cycle: int, gangs
    ):
        """All-or-nothing placement for PodGroups inside the solver, and
        work-conserving: a gang is masked (parked, reserving NOTHING: no
        Permit-timeout churn) only if it cannot fit what the gangs
        before it in the solve order left. Permit remains the
        cross-batch completion gate for gangs that can still assemble
        (framework/v1alpha1/interface.go:384).

        The solve order keeps a gang's members together
        (``_gang_contiguous``), so after a pass the FIRST gang short of
        its quorum is a true failure: every gang before it was placed
        whole and it saw everything they left. Gangs after it failed,
        if they did, beside the members it placed in vain, and are not
        masked for that. Where the batch's pods carry no constraint
        between pods and the failed gang's pods are identical, the
        members it did place count what the leftover holds of such
        pods, so every later gang of the same pods that needs more than
        is then left is masked with it (``_gang_census``). The rest is
        solved again against the rewound carry, until a pass fails no
        gang.

        A pass masks the first failure with every gang of its pods that
        cannot fit, so the passes a batch needs are one a distinct
        template of its gangs (gangs without one share a pass) and one
        clean pass; they are bounded by that count plus one, for a gang
        the count took for placed and that then failed. At the bound
        what still fails is left out of the commit and sent round again
        through the active queue, not parked: the next batch masks at
        least its first failure, so the round ends.

        A gang is decided when every member that holds no node yet is
        in the batch (``_gang_quorums``)."""
        cos = self._coscheduling(solver_infos)
        totals = self.stage_totals
        groups = len({k for k in gangs if k is not None})
        stats = {
            "passes": 0, "masked_groups": 0, "masked_pods": 0,
            "requeued_pods": 0, "carry": "",
        }
        with flightrecorder.stage(
            "gang_fixup", totals=totals, pods=len(solver_infos),
            groups=groups,
        ) as fixup:
            pending = self._gang_passes(
                solver_infos, pod_scheduling_cycle, gangs, cos, stats
            )
            fixup.set_metadata(**stats)
        self.gang_resolves += max(0, stats["passes"] - 1)
        return pending

    def _gang_passes(
        self, solver_infos, pod_scheduling_cycle, gangs, cos, stats
    ):
        totals = self.stage_totals
        with flightrecorder.stage("gang_fixup.members", totals=totals):
            uid_of = [pi.pod.metadata.uid for pi in solver_infos]
            members: dict = {}  # gang -> indices into solver_infos
            for i, key in enumerate(gangs):
                if key is not None:
                    members.setdefault(key, []).append(i)
        with flightrecorder.stage("gang_fixup.census", totals=totals):
            quorum = self._gang_quorums(solver_infos, members, cos)
        # a gang that the batch's members cannot bring to its quorum
        # whatever the solve finds fails before the first pass
        masked = {
            key: "members" for key, idx in members.items()
            if quorum[key] > len(idx)
        }
        taken: set = set()  # gangs a count took for placed
        requeue: set = set()  # gangs sent round again, not parked
        templates = at = None
        bound = 2  # until the first pass has shown the batch's templates
        pending = None
        while True:
            with flightrecorder.stage("gang_fixup.members", totals=totals):
                inactive = {
                    uid_of[i] for key in masked for i in members[key]
                }
            if pending is not None:
                with flightrecorder.stage(
                    "gang_fixup.resolve", totals=totals,
                    masked_pods=len(inactive),
                ):
                    stats["carry"] = self.device_state.rewind(
                        pending.get("carry_in")
                    )
                    if len(inactive) == len(solver_infos):
                        # nothing is left to place: the rewound carry
                        # is the batch's result
                        self._void_assignments(pending)
                        break
                    pending = self._dispatch_solve(
                        solver_infos, pod_scheduling_cycle,
                        inactive_uids=inactive, gangs=gangs,
                    )
            else:
                pending = self._dispatch_solve(
                    solver_infos, pod_scheduling_cycle,
                    inactive_uids=inactive or None, gangs=gangs,
                )
            if pending is None:
                return None  # packers routed the batch to the host path
            stats["passes"] += 1
            with flightrecorder.stage(
                "gang_fixup.download", totals=totals
            ):
                assignments = self._pending_assignments(pending)
            with flightrecorder.stage("gang_fixup.census", totals=totals):
                if templates is None:
                    # the solve order is the same in every pass
                    order = np.asarray(pending["order"])
                    where = np.empty(len(order), dtype=np.int64)
                    where[order] = np.arange(len(order))
                    at = {key: where[idx] for key, idx in members.items()}
                    templates = self._gang_templates(pending, at)
                    own = set(templates.values())
                    bound = (
                        sum(1 for t in own if t >= 0)
                        + any(t < 0 for t in own) + 1
                    )
                failed, certain = self._gang_census(
                    pending, assignments, at, masked, taken, quorum,
                    templates,
                )
            if not failed:
                break
            if stats["passes"] > bound:
                # the pass bound: commit what was placed whole, leave
                # out what failed. Its capacity stays reserved in the
                # device output, so the carry drops
                for key in failed:
                    masked[key] = "bound"
                requeue.update(failed)
                self.device_state.invalidate()
                stats["carry"] = "dropped"
                flightrecorder.mark(
                    "gang_starved", groups=len(failed),
                    passes=stats["passes"],
                )
                break
            if not taken.isdisjoint(failed):
                # a gang the count took for placed has failed: what was
                # masked by that count was masked on a wrong premise
                requeue.update(
                    key for key, why in masked.items() if why == "count"
                )
            masked.update(certain)
        with flightrecorder.stage(
            "gang_fixup.verdict", totals=totals, rejected_groups=len(masked)
        ):
            for key in requeue:
                masked[key] = "requeue"
            inactive = {uid_of[i] for key in masked for i in members[key]}
            pending["gang_failed_uids"] = inactive
            pending["gang_requeue_uids"] = {
                uid_of[i] for key in requeue for i in members[key]
            }
            stats["masked_groups"] = len(masked)
            stats["masked_pods"] = len(inactive)
            stats["requeued_pods"] = len(pending["gang_requeue_uids"])
            # members of a masked gang that wait at Permit from an earlier
            # batch wait for members that are not coming: their nodes are
            # given back now, not at the timeout
            for ns, group in masked:
                cos.reject_waiting(
                    ns, group, "the rest of the pod group was not placed"
                )
        return pending

    def _gang_quorums(self, solver_infos, members, cos) -> dict:
        """gang -> how many of the batch's members have to be placed for
        the gang to reach ``min_member``. Members outside the batch
        count for it while they hold a node. Members that do not are
        queued behind this batch or still arriving (``_with_gang_siblings``
        took the ones it had room for): they count only for a gang too
        large to be whole in any batch, which can assemble no other way
        than part by part at Permit. Any other gang waits for them, so
        that no part of it holds nodes for a part that may not fit."""
        need = {}
        for key, idx in members.items():
            min_member = cos.min_member(solver_infos[idx[0]].pod, key[1])
            known, holding = cos.members(*key)
            inside = {solver_infos[i].pod.metadata.uid for i in idx}
            held = sum(1 for uid in holding if uid not in inside)
            elsewhere = sum(
                1 for uid in known
                if uid not in inside and uid not in holding
            )
            if len(idx) + elsewhere > self.max_batch:
                held += elsewhere
            need[key] = min_member - held
        return need

    @staticmethod
    def _gang_templates(pending, at) -> dict:
        """gang -> the one template its pods of the batch share (what a
        pod asks of a node: its request rows and its static mask row),
        or a value of its own where they differ or the batch holds
        constraints between pods, under which the pods a gang placed
        say nothing of what another would have. ``at`` is gang -> its
        members' places in the solve order."""
        b = pending["b"]
        rows = np.concatenate(
            [pending["req"][:b], pending["nzr"][:b],
             np.asarray(pending["mask_index_solved"][:b])[:, None]],
            axis=1,
        )
        _, ids = np.unique(rows, axis=0, return_inverse=True)
        ids = ids.reshape(-1)
        out = {}
        for n, (key, places) in enumerate(at.items()):
            own = set(ids[places].tolist())
            if pending["constrained"] or len(own) != 1:
                out[key] = -1 - n
            else:
                out[key] = own.pop()
        return out

    @staticmethod
    def _gang_census(
        pending, assignments, at, masked, taken, quorum, templates
    ):
        """One pass read: ``failed``, the gangs that were active and
        short of their quorum, and ``certain``, those of them to mask
        before the next pass with why: the first in the solve order
        (``first``) and, where it has a template, every later active
        gang of that template that needs more such pods than the
        leftover holds once the gangs between them took theirs
        (``count``); the gangs the count takes for placed join
        ``taken``. ``at`` is gang -> its members' places in the solve
        order."""
        placed_at = np.asarray(assignments[: pending["b"]]) != NO_NODE
        active = sorted(
            (int(places.min()), key) for key, places in at.items()
            if key not in masked
        )
        placed = {key: int(placed_at[at[key]].sum()) for _, key in active}
        failed = [key for _, key in active if placed[key] < quorum[key]]
        if not failed:
            return failed, {}
        first = failed[0]
        certain = {first: "first"}
        template = templates[first]
        if template >= 0:
            left = placed[first]
            after = False
            for _, key in active:
                if key == first:
                    after = True
                elif after and templates[key] == template:
                    if quorum[key] > left:
                        certain[key] = "count"
                    else:
                        left -= min(left, len(at[key]))
                        taken.add(key)
        return failed, certain

    @staticmethod
    def _void_assignments(pending) -> None:
        """Every pod of the batch is masked: the batch places nothing,
        whatever its last pass found."""
        pending["assignments_dev"] = np.full(
            len(pending["mask_index_solved"]), NO_NODE, dtype=np.int32
        )
        pending["download"] = None

    def _pending_assignments(self, p):
        """The batch's downloaded assignments for the gang fixup: await
        the eager copy when one is in flight, else convert now -- under
        the same wall-clock watchdog that guards the committer's
        download, so a wedged device raises SolveTimeout (routed
        through _solve_and_commit's recovery) instead of hanging the
        dispatcher thread forever."""
        tier = p.get("tier", TIER_XLA)
        timeout = (
            self.ladder.config.solve_timeout_seconds
            if tier in (TIER_PALLAS, TIER_XLA) and self.ladder.config.enabled
            else 0.0
        )

        def download():
            eager = p.get("download")
            if eager is not None:
                return eager.result()
            return np.asarray(p["assignments_dev"])

        try:
            return self.ladder.watchdog.call(download, timeout, tier=tier)
        except SolveTimeout:
            breaker = self.ladder.breakers.get(tier)
            if breaker is not None:
                breaker.force_open()
            raise

    def _pending_exists(self) -> bool:
        with self._pending_cv:
            return bool(self._pending_q)

    def _in_flight(self):
        """How many batches are in flight, and the first pending record
        whose commit has NOT passed the shadow-mutation point (the
        mirror in ``_complete_solve``). Mirrors land in FIFO order, so
        this record's ``carry_in`` is the one snapshot that still equals
        the host shadows -- the under-load carry audit's comparand."""
        with self._pending_cv:
            head = next(
                (p for p in self._pending_q if not p.get("mirrored")), None
            )
            return len(self._pending_q), head

    def _unmirrored_exists(self) -> bool:
        """Any dispatched batch whose shadow mirror has NOT landed yet?
        Once every pending record is mirrored the device carry equals
        the host shadow exactly (each dispatch rebinds the carry refs
        and the mirror is the only shadow writer), so the handshake can
        negotiate row-exact repairs with commits still in flight -- the
        speculative chain's cheap-rewind precondition."""
        return self._in_flight()[1] is not None

    def _await_mirrors(self, timeout: float = 30.0) -> bool:
        """Block until every in-flight batch has mirrored its deltas
        into the shadow -- far cheaper than ``_drain_pending``, which
        also waits out the bind/commit API transactions. The committer
        notifies ``_pending_cv`` right after each mirror. Returns False
        on timeout or when no committer is running (the caller falls
        back to a full drain)."""
        if self._committer is None:
            return not self._pending_exists()
        deadline = time.monotonic() + timeout
        with self._pending_cv:
            while any(not p.get("mirrored") for p in self._pending_q):
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._pending_cv.wait(min(left, 0.5))
        return True

    def _device_tiers(
        self, mode: str, b: int, n_cap: int, r_dims: int, u_rows: int
    ) -> List[str]:
        """Device tiers live for this (mode, shape), ladder order. The
        pallas tier is only offered when solve_packed would actually run
        the fused kernel (shared predicate ops.assignment
        .pallas_candidate) -- otherwise a shape-ineligible batch would
        run the identical XLA solve twice on failure and charge it to
        the pallas breaker. The XLA scan is always available.

        A MESH offers the shard_map'd Pallas tier instead (shared
        predicate ops.assignment.mesh_pallas_candidate: greedy batches,
        KTPU_MESH_PALLAS=1, node axis divisible by the mesh): each
        device runs the fused whole-array step on its own carry shard
        with one best-of-shards combine per pod. The single-core
        whole-array kernels themselves are still never attempted on a
        mesh; a faulted mesh-pallas solve steps down to the GSPMD XLA
        twin through the same breaker."""
        from kubernetes_tpu.ops.assignment import (
            mesh_pallas_candidate,
            pallas_candidate,
        )

        if self.mesh is None and pallas_candidate(
            mode, b, n_cap, r_dims, u_rows
        ):
            return [TIER_PALLAS, TIER_XLA]
        if (
            self.mesh is not None
            and mesh_pallas_candidate(mode, n_cap, self.mesh)
        ):
            return [TIER_PALLAS, TIER_XLA]
        return [TIER_XLA]

    def _pending_has_required_anti(self) -> bool:
        with self._pending_cv:
            return any(p.get("has_required_anti") for p in self._pending_q)

    # -- admission classification (scheduler/admission.py) -------------------

    def _listers(self):
        """Lazily constructed shared PVC/PV/SC/CSINode lister access."""
        listers = self._volume_listers
        if listers is None:
            from kubernetes_tpu.plugins.volumes import _Listers

            prof = next(iter(self.profiles.values()), None)
            listers = _Listers(prof)
            self._volume_listers = listers
        return listers

    def bump_volume_topology_gen(self) -> None:
        """A PV/PVC/StorageClass/CSINode mutation landed: cached
        admission records of PVC-bearing pods are stale from here."""
        self._volume_topo_gen += 1

    def classify_pod(self, pod: Pod) -> Admission:
        """Compute + memoize the pod's admission record (called at
        informer ingest by the event handlers, and lazily at pop time
        for pods that entered the queue some other way). Does NOT touch
        the tensor schema -- only the dispatcher thread registers volume
        columns (_ensure_vol_columns), so the dims registry never grows
        under a concurrently packing NodeTensorCache.update."""
        self.admissions_classified += 1
        return _classify_pod(
            pod,
            extenders=self.algorithm.extenders,
            listers=self._listers(),
            volume_gen=self._volume_topo_gen,
            token=self._admission_token,
            priority_resolver=self._effective_priority,
        )

    def _effective_priority(self, pod: Pod) -> int:
        """The pod's band priority: an explicit spec.priority wins; a
        bare priorityClassName resolves through the PriorityClass
        lister (stamped once at ingest -- the queue's band check is a
        memo read, never a lister lookup per drain)."""
        if pod.spec.priority:
            return pod.spec.priority
        name = pod.spec.priority_class_name
        if name:
            prof = next(iter(self.profiles.values()), None)
            informers = prof.informers if prof is not None else None
            if informers is not None:
                pc = informers.priority_classes().get("default", name)
                if pc is None:
                    pc = informers.priority_classes().get("", name)
                if pc is not None:
                    return int(pc.value)
        return pod.spec.priority

    def _plain_admission_record(self) -> Admission:
        adm = self._plain_adm
        if adm is None:
            from kubernetes_tpu.scheduler.admission import plain_admission

            adm = plain_admission(self._admission_token)
            self._plain_adm = adm
        return adm

    def classify_pods_bulk(self, pods: List[Pod]) -> None:
        """One ingest pass over a watch frame's new pending pods (the
        event handlers' bulk classify): plain pods get their WHOLE
        ingest record -- spec memos, pack-ready row, band priority, and
        the shared Admission -- stamped in one native C pass
        (ingest_stamp; Python twin scheduler/admission.stamp_plain_pods
        behind KTPU_NATIVE_INGEST=0), and only the non-plain remainder
        runs the full per-pod classifier. With extenders configured the
        fast path is off: is_interested must see every pod."""
        if not pods:
            return
        rest_targets: List[Pod] = pods
        if not self.algorithm.extenders:
            from kubernetes_tpu import native as _native
            from kubernetes_tpu.scheduler.admission import (
                ingest_stamp_cfg,
                stamp_plain_pods,
            )

            plain = self._plain_admission_record()
            fn, expected = _native.ingest_fn("ingest_stamp")
            rest = None
            if fn is not None:
                cfg = self._ingest_cfg
                if cfg is None:
                    cfg = ingest_stamp_cfg(plain)
                    self._ingest_cfg = cfg
                try:
                    rest = fn(pods, cfg)
                except Exception:
                    # a fast-path failure must NEVER cost the frame its
                    # enqueue (the caller adds to the queue right after
                    # this): count it and run the twin
                    logger.exception("native ingest_stamp failed")
                    metrics.ingest_native_fallbacks.inc(
                        site="classify-stamp"
                    )
            elif expected:
                metrics.ingest_native_fallbacks.inc(site="classify-stamp")
            if rest is None:
                rest = stamp_plain_pods(pods, plain)
            self.admissions_classified += len(pods) - len(rest)
            rest_targets = [pods[i] for i in rest]
        for pod in rest_targets:
            try:
                self.classify_pod(pod)
            except Exception:
                logger.exception("classifying pod %s", pod.key())

    def attach_volume_counts(self, pod: Pod) -> None:
        """Resolve + memoize a BOUND pod's attachable-volume counts
        before it enters the cache (event handlers call this on the
        cache side of the frame): NodeInfo.add_pod reads the memo into
        the node's in-use accounting. Column registration for in-use
        names happens on the dispatcher thread inside
        NodeTensorCache.update (it scans NodeInfo.volume_in_use)."""
        if not pod.spec.volumes or "_volcount_memo" in pod.__dict__:
            return
        from kubernetes_tpu.plugins.volumes import classify_pod_volumes

        try:
            _reason, counts = classify_pod_volumes(pod, self._listers())
        except Exception:  # noqa: BLE001 - never block the cache path
            logger.exception("volume counts for %s", pod.key())
            counts = ()
        pod.__dict__["_volcount_memo"] = counts

    def _ensure_vol_columns(self, adm: Admission) -> None:
        """Register the record's volume resources as tensor columns.
        Dispatcher-thread only: schema growth must never race the
        packer (registration bumps dims.version, so the next
        NodeTensorCache.update full-repacks with the new column)."""
        if adm.vol_counts:
            dims = self.tensor_cache.dims
            for name, _qty in adm.vol_counts:
                dims.volume_column(name)

    def _admission_of(self, pod: Pod) -> Admission:
        """The pop-time admission read: a memo hit is a dict get; a miss
        (new object, foreign token) or a stale volume classification
        (PVC binding landed mid-queue) re-classifies. Dispatcher-thread
        only (it registers volume columns)."""
        adm = pod.__dict__.get("_admission")
        if adm is not None and adm.token is self._admission_token:
            if adm.pinned or not adm.has_pvc:
                return adm
            if adm.volume_gen == self._volume_topo_gen:
                self._ensure_vol_columns(adm)
                return adm
            self.reclassifications += 1
        adm = self.classify_pod(pod)
        self._ensure_vol_columns(adm)
        return adm

    def _memo_admissions(self, solver_infos: List[PodInfo]) -> List[Admission]:
        """Admission records for a dispatched batch, without the
        staleness re-check: routing was decided at pop time, and the
        record's feature bits describe the same pod object either way."""
        out = []
        token = self._admission_token
        for pi in solver_infos:
            adm = pi.pod.__dict__.get("_admission")
            if adm is None or adm.token is not token:
                adm = self.classify_pod(pi.pod)
                self._ensure_vol_columns(adm)
            out.append(adm)
        return out

    def _live_window(self) -> float:
        """Window source handed to pop_batch when the adaptive
        controller is attached. The queue calls it at every window
        wakeup, so the controller is re-polled MID-WINDOW (still
        interval-gated, and re-entrant on the queue's RLock since this
        runs on the dispatcher thread): a shrink decided while a drain
        is waiting lands on that drain immediately, while the queue
        clamps the deadline so a grow never extends it."""
        ab = self.autobatch
        if ab is not None:
            ab.maybe_step(self)
        return self.batch_window

    def attach_autobatch(self, controller) -> None:
        """Wire an AutoBatchController (streaming/autobatch.py) into the
        dispatch loop: EVERY controller rung joins the warmup compile
        set so rung switches never pay JIT latency mid-run (warmup also
        measures each rung's solve cost, and the controller's
        ``calibrate`` prunes rungs that don't pay), and the controller's
        current outputs are applied immediately."""
        self.autobatch = controller
        for rung in getattr(
            controller, "rungs",
            (controller.latency_batch, controller.max_batch),
        ):
            self._warmup_pads.add(int(rung))
        self._warmup_pads.add(int(controller.max_batch))
        self.batch_window = controller.window
        self.dispatch_batch_cap = controller.batch_cap
        self.solve_pad = controller.batch_cap

    @property
    def stage_seconds(self) -> dict:
        """Per-stage wall-clock totals, merged across every thread that
        accumulates (dispatcher, committer, bind pool, informers)."""
        return self.stage_totals.seconds()

    @property
    def mesh_solver_tier(self) -> str:
        """Which of the mesh's two PROGRAMS the run actually solved
        on, for the perf matrix's ``solver_mesh_tier`` label, under the
        ladder's names for their attempts: "pallas" once any batch rode
        the shard_map'd tier, else "xla" (the GSPMD twin -- either
        KTPU_MESH_PALLAS=0, an ineligible shape, or every shard_map
        attempt faulted to the twin). Whether the shard_map tier ran
        its Pallas kernel is the ledger's to say
        (``ladder.solves_by_tier``: off a TPU it did not, and the batch
        is counted ``xla``). Empty off-mesh."""
        if self.mesh is None:
            return ""
        if self.mesh_shard_solves:
            return "pallas"
        return "xla"

    def _pending_has_ports(self) -> bool:
        with self._pending_cv:
            return any(p.get("has_ports") for p in self._pending_q)

    def _pending_has_scoring_terms(self) -> bool:
        with self._pending_cv:
            return any(p.get("has_scoring_terms") for p in self._pending_q)

    def _ensure_committer(self) -> None:
        if self._committer is None:
            self._committer_stop = False
            self._committer = threading.Thread(
                target=self._committer_loop, name="batch-committer",
                daemon=True,
            )
            self._committer.start()

    def _stop_committer(self) -> None:
        with self._pending_cv:
            self._committer_stop = True
            self._pending_cv.notify_all()
        if self._committer is not None:
            self._committer.join(timeout=10)
            if self._committer.is_alive():
                # the join timed out: the committer is wedged (most
                # likely a hung result download).
                # Silence here would strand in-flight batches invisibly
                # -- log, count, and raise the degraded-health flag so
                # operators and the health endpoint see it.
                logger.error(
                    "committer thread failed to join within 10s; "
                    "%d batch(es) may be stranded in flight",
                    len(self._pending_q),
                )
                metrics.commit_join_timeouts.inc()
                metrics.degraded_health.set(
                    1, reason="committer_join_timeout"
                )
                flightrecorder.dump_on_degraded("committer_join_timeout")
                self.commit_degraded = True
            self._committer = None

    def _committer_loop(self) -> None:
        """Completes dispatched batches in FIFO order: the ~100ms serving
        link round trip per result download happens here, off the
        dispatcher thread (which is already packing the next batch). A
        batch stays at the queue head until fully committed so
        _drain_pending and the dispatch-time pending checks see it."""
        flightrecorder.name_thread()
        while True:
            with self._pending_cv:
                while not self._pending_q and not self._committer_stop:
                    self._pending_cv.wait()
                if not self._pending_q and self._committer_stop:
                    return
                p = self._pending_q[0]
            try:
                p["committing"] = True
                self._complete_solve(p)
            except SchedulerCrashed:
                self._simulate_crash()  # no recovery: the process "died"
            except Exception:
                logger.exception("batch commit crashed")
                self._recover_failed_batch(p)
            finally:
                with self._pending_cv:
                    self._pending_q.popleft()
                    self._pending_cv.notify_all()

    def _recover_failed_batch(self, p) -> None:
        """A committer crash (device error mid-download, commit
        bug) must not strand the batch's pods as Pending-forever: every
        pod not already assumed goes back through the failure path
        (requeue with backoff + condition), and the device carry is
        dropped since the batch's true placements are unknown."""
        self.device_state.invalidate()
        try:
            if self._deferred_preempt:
                self._flush_deferred_preemptions()
        except Exception:
            logger.exception("flushing deferred preemptions on recovery")
        prof = self.profiles.get(
            p["solver_infos"][0].pod.spec.scheduler_name
        )
        for pi in p["solver_infos"]:
            try:
                if prof is None or self.cache.is_assumed_pod(pi.pod):
                    continue
                self.record_scheduling_failure(
                    prof, pi, "batch commit failed", "SchedulerError", "",
                    p["cycle"],
                )
            except Exception:
                logger.exception("recovering pod %s", pi.pod.key())

    def _solve_pipelined(
        self, solver_infos: List[PodInfo], pod_scheduling_cycle: int
    ) -> None:
        """Dispatch this batch and enqueue it for the committer thread;
        blocks only when MAX_INFLIGHT batches are already in flight.
        Gang batches take the synchronous path: the quorum fixup
        (SURVEY stage 6 all-or-nothing group masks) may re-solve, which
        must not race in-flight batches."""
        if any(
            pi.pod.metadata.labels.get(POD_GROUP_LABEL)
            for pi in solver_infos
        ):
            self._drain_pending()
            self._solve_and_commit(solver_infos, pod_scheduling_cycle)
            return
        pending = self._dispatch_solve(solver_infos, pod_scheduling_cycle)
        if pending is None:
            return
        self._ensure_committer()
        with self._pending_cv:
            if len(self._pending_q) >= self.max_inflight:
                # the pipeline is full: the dispatcher waits for the
                # committer (which holds solve_wait or commit open)
                with flightrecorder.stage(
                    "inflight_wait", totals=self.stage_totals,
                    batch=pending["span"].batch_id,
                ):
                    while len(self._pending_q) >= self.max_inflight:
                        self._pending_cv.wait()
            if self._pending_q:
                # the solve launched against the shadow-EXPECTED state
                # of still-uncommitted batches: a speculative link in
                # the chain (a commit divergence rewinds it via the
                # row-patch path instead of a drain)
                self.speculative_launches += 1
                metrics.speculative_launches.inc()
            # the hand-off to the committer: solve_wait says how long
            # the batch waited for that thread
            pending["handed_off"] = time.perf_counter()
            self._pending_q.append(pending)
            self._pending_cv.notify_all()

    def _drain_pending(self) -> None:
        """Block until every in-flight batch has committed (the host
        cache then reflects every dispatched placement)."""
        if self._committer is None:
            while self._pending_q:
                pend = self._pending_q.popleft()
                try:
                    self._complete_solve(pend)
                except Exception:
                    logger.exception("drain commit failed")
                    self._recover_failed_batch(pend)
            return
        with self._pending_cv:
            while self._pending_q:
                self._pending_cv.wait()

    def _dispatch_solve(
        self,
        solver_infos: List[PodInfo],
        pod_scheduling_cycle: int,
        inactive_uids=None,
        raise_on_exhaust: bool = False,
        gangs=None,
    ):
        """``_dispatch_batch`` as one ``sched/dispatch`` span of a
        profiler trace, which carries the batch's size and queue waits.
        ``gangs`` is ``_gang_keys`` of a gang batch: the solve order
        then keeps every gang's members together."""
        with flightrecorder.stage(
            "dispatch", pods=len(solver_infos)
        ) as dispatch:
            return self._dispatch_batch(
                dispatch, solver_infos, pod_scheduling_cycle,
                inactive_uids, raise_on_exhaust, gangs,
            )

    def _dispatch_batch(
        self,
        dispatch: flightrecorder.stage,
        solver_infos: List[PodInfo],
        pod_scheduling_cycle: int,
        inactive_uids,
        raise_on_exhaust: bool,
        gangs=None,
    ):
        """Pack + upload + dispatch one solver batch. Returns a pending
        record for _complete_solve, or None when the batch was routed to
        the sequential path. Paths that read host-side cluster state the
        in-flight batch would change (spread counts, nominee overlays,
        incompatible clusters) drain the pipeline first.

        ``raise_on_exhaust`` (the bisection sub-solve mode): a ladder
        exhaustion re-raises to the caller -- after the carry-state
        un-booking -- instead of routing the batch to containment or
        the sequential floor (the bisection loop owns that batch's
        disposition)."""
        if not raise_on_exhaust:
            inj0 = get_injector()
            if inj0 is not None and inj0.should_fire(
                FaultPoint.DEVICE_LOST
            ):
                self._on_device_lost()
        self.device_state.begin_dispatch()
        # -- flight-recorder span: one per dispatch (a gang re-solve or
        # drain-redispatch is honestly its own span), with the per-pod
        # linkage (uid -> batch id, queue-wait, attempts) that makes a
        # pod's whole pod-to-bind path one join
        # (dispatch's own children are trace-only, as it is: each holds
        # 1 ms a batch or more in a burst cell, PERF.md section 5)
        with flightrecorder.stage("dispatch.begin") as begin:
            now_m = time.monotonic()
            waits = [max(0.0, now_m - pi.timestamp) for pi in solver_infos]
            if flightrecorder.ENABLED:
                span = flightrecorder.begin_batch(
                    len(solver_infos),
                    pods=[
                        (pi.pod.metadata.uid, wait, pi.attempts)
                        for pi, wait in zip(solver_infos, waits)
                    ],
                )
                pop_note = self._pop_note
                if pop_note is not None:
                    # the stages ran in the queue, before this batch had a
                    # span: only the ring is still to be written
                    self._pop_note = None
                    work, pop_waited = pop_note
                    if pop_waited:
                        span.stage("pop_wait", pop_waited)
                    span.stage("pop_batch", work)
                if inactive_uids:
                    span.note(gang_redispatch=True)
                if raise_on_exhaust:
                    span.note(bisect=True)
            else:
                span = flightrecorder.NULL_SPAN
            dispatch.set_metadata(
                batch=span.batch_id,
                queue_wait_sum_ms=round(sum(waits) * 1e3, 3),
                queue_wait_max_ms=round(max(waits, default=0.0) * 1e3, 3),
            )
            begin.set_metadata(batch=span.batch_id)
        totals = self.stage_totals
        with flightrecorder.stage("pack", span, totals) as packing:
            # pack's parts (``batch_id`` on each): totals and trace
            # only, the ring keeps the one ``pack``
            batch_id = span.batch_id
            with flightrecorder.stage(
                "pack.aggregates", totals=totals, batch=batch_id
            ) as aggregates:
                pods = [pi.pod for pi in solver_infos]
                # poison manifestation: any stamped pod in the dispatch fails
                # every ladder tier (PoisonError), driving the exhaustion the
                # bisection containment hangs off; a sub-batch WITHOUT the
                # stamped pod solves normally -- exactly the signature the
                # O(log B) search isolates on
                poison_key = None
                if get_injector() is not None:
                    for pod_p in pods:
                        if pod_is_poisoned(pod_p):
                            poison_key = pod_p.key()
                            break
                # batch-level constraint aggregates from the cached admission
                # feature bits (scheduler/admission.py): any() over memo reads
                # instead of re-walking every spec per dispatch
                adms = self._memo_admissions(solver_infos)
                has_hard_spread = any(a.hard_spread for a in adms)
                batch_ports = any(a.ports for a in adms)
                has_affinity_terms = any(a.affinity_req for a in adms)
                has_affinity = has_affinity_terms or batch_ports
                has_required_anti = any(a.required_anti for a in adms)
                prof0 = self.profiles.get(pods[0].spec.scheduler_name)
                # the batch's resource score rule is its profile's
                config = (
                    self._profile_rules.get(pods[0].spec.scheduler_name)
                    or self.solver_config
                )
                # gated on the profile actually scoring with
                # InterPodAffinity -- otherwise the ipa family packs nothing
                # and draining for it would serialize the pipeline for free
                ipa_weight = (
                    prof0.score_plugin_weights().get("InterPodAffinity", 0)
                    if prof0 is not None
                    else 0
                )
                score_dynamic = (
                    any(a.score_soft for a in adms)
                    or (
                        bool(ipa_weight)
                        and any(a.score_pref for a in adms)
                    )
                    or batch_selector_spread_live(
                        pods, prof0.informers if prof0 is not None else None
                    )
                )
                # this batch's pods become symmetric scorers for later batches
                # once placed (preferred terms, and required affinity terms via
                # hardPodAffinityWeight)
                has_scoring_terms = bool(ipa_weight) and any(
                    a.scoring_terms for a in adms
                )
                nominated_by_node = self.queue.all_nominated_pods_by_node()
                nominee_uids = (
                    {
                        p.metadata.uid
                        for noms in nominated_by_node.values()
                        for p in noms
                    }
                    if nominated_by_node else set()
                )
                aggregates.set_metadata(nominees=len(nominee_uids))

            def drain_inflight(reason: str) -> None:
                # the dispatcher waits, inside pack, for the batches in
                # flight: a wait with a name of its own
                with flightrecorder.stage(
                    "pack.drain", totals=totals, batch=batch_id,
                    reason=reason,
                ):
                    self._drain_pending()

            def drained(reason: str) -> bool:
                """Land every in-flight batch when there is a reason to,
                then rebuild the drain-sensitive inputs (nominee overlay
                source; callers refresh the snapshot themselves when they
                hold one). Returns True when a drain happened."""
                nonlocal nominated_by_node
                if not reason or not self._pending_exists():
                    return False
                self.pipeline_drains += 1
                drain_inflight(reason)
                # the drain can assume previously nominated pods (dropping
                # their nomination) and nominate new ones via preemption --
                # rebuild the overlay source from the post-drain state
                nominated_by_node = self.queue.all_nominated_pods_by_node()
                return True

            drained(
                "spread" if has_hard_spread
                else "affinity" if has_affinity_terms
                else "dynamic_score" if score_dynamic
                # a port batch must see in-flight PORT placements committed
                # into the static mask; port-free in-flight batches cannot
                # conflict, so they don't force the drain
                else "ports" if batch_ports and self._pending_has_ports()
                # an in-flight batch carrying required anti-affinity or
                # scoring-relevant terms imposes symmetric constraints this
                # batch can only see once its placements are committed
                else "anti" if self._pending_has_required_anti()
                else "dynamic_score" if self._pending_has_scoring_terms()
                # a batch RETRYING preemption nominees must see the fully
                # committed post-eviction state, or in-flight placements
                # race it onto the freed capacity and cascade re-preemption
                # (the old answer -- drain while ANY nomination lived --
                # serialized every post-preemption dispatch; this drains
                # only the nominees' own retry batches)
                else "nominees" if any(
                    pi.pod.metadata.uid in nominee_uids
                    for pi in solver_infos
                )
                else ""
            )

            snapshot = self.algorithm.snapshot

            assumed_seq = 0
            # a batch in flight when the snapshot is refreshed may be
            # missing from this pack even if it has committed by the
            # time the handshake looks: the pack may then repair rows
            # of the resident carry, never become it (the upload)
            in_flight_at_pack = False

            def refresh_snapshot() -> None:
                nonlocal assumed_seq, in_flight_at_pack
                with flightrecorder.stage(
                    "pack.snapshot", totals=totals, batch=batch_id
                ) as refresh:
                    # read BEFORE the refresh: commits up to here are
                    # in what it reads
                    assumed_seq = self._assumed_seq
                    in_flight_at_pack = self._pending_exists()
                    self.cache.update_snapshot(snapshot)
                    refresh.set_metadata(
                        **snapshot.refresh_stats(),
                        nodes=len(snapshot.node_info_list),
                    )

            def cluster_terms() -> flightrecorder.stage:
                # what the residents and the nominees ask of every batch:
                # the three reads below, each up to the drain it may ask
                # for (the drains and the refreshes stay pack's own
                # children, beside these)
                return flightrecorder.stage(
                    "pack.cluster_terms", totals=totals, batch=batch_id
                )

            refresh_snapshot()
            # existing pods with required anti-affinity constrain EVERY
            # incoming pod symmetrically (filtering.go:404) -- such clusters
            # need the affinity tensors even for batches without affinity, and
            # their counts must include any in-flight placements
            with cluster_terms():
                cluster_anti = (
                    not has_affinity_terms
                    and cluster_has_required_anti_affinity(snapshot)
                )
            if cluster_anti:
                has_affinity = True
                has_affinity_terms = True
                if drained("anti"):
                    refresh_snapshot()
            # existing pods with symmetric scoring terms make EVERY batch's
            # preferred-affinity family live (scoring.go:111): the in-flight
            # counts must land before packing
            with cluster_terms():
                cluster_ipa = (
                    bool(ipa_weight)
                    and cluster_has_affinity_scoring(snapshot)
                )
            if not score_dynamic and cluster_ipa:
                score_dynamic = True
                if drained("dynamic_score"):
                    refresh_snapshot()
                    with cluster_terms():
                        cluster_ipa = cluster_has_affinity_scoring(snapshot)
            with cluster_terms():
                nominee_constrained = nominated_by_node and (
                    has_hard_spread or has_affinity or score_dynamic
                    # a CONSTRAINED nominee (required (anti-)affinity /
                    # spread) imposes symmetric constraints the
                    # resource-only overlay can't express even for a
                    # plain batch
                    or any(
                        p.spec.affinity is not None
                        and (
                            p.spec.affinity.pod_affinity is not None
                            or p.spec.affinity.pod_anti_affinity is not None
                        )
                        or p.spec.topology_spread_constraints
                        for noms in nominated_by_node.values()
                        for p in noms
                    )
                )
            if nominee_constrained:
                # ADVICE r2 (medium): nominees are overlaid as RESOURCES
                # only; the affinity/spread/score count tensors pack from
                # the snapshot, which excludes them, so a constrained device
                # batch could violate a nominee's symmetric constraints.
                # The host path runs _add_nominated_pods exactly
                # (generic_scheduler.go:535) -- take it for this rare
                # combination (active nominations + constraints on either
                # side).
                drain_inflight("nominees")
                self.nominee_constrained_fallbacks += 1
                span.finish(
                    tier=TIER_SEQUENTIAL, routed="nominee_constrained"
                )
                for pi in solver_infos:
                    self.pods_fallback += 1
                    self.attempt_schedule(pi)
                return None
            # whether this batch packs against another node-spec epoch
            # than the last one did: what is kept per epoch (the static
            # mask rows, the family packers' node rows) is built again
            epoch = snapshot.node_spec_epoch
            packing.set_metadata(
                node_epoch_moved=int(epoch != self._packed_node_epoch)
            )
            self._packed_node_epoch = epoch
            with flightrecorder.stage(
                "pack.state", totals=totals, batch=batch_id
            ) as state:
                # the stage's wall clock holds whatever the other
                # threads did under the GIL meanwhile: its own work is
                # the rows it repacked and the span's ``cpu_ms``
                nt = self.tensor_cache.update(snapshot)
                state.set_metadata(**nt.delta.row_stats())
            with flightrecorder.stage(
                "pack.pods", totals=totals, batch=batch_id
            ) as packed_pods:
                batch = pack_pod_batch(
                    pods, nt.dims,
                    timestamps=[pi.timestamp for pi in solver_infos],
                )
                packed_pods.set_metadata(templates=batch.templates)
            with flightrecorder.stage(
                "pack.masks", totals=totals, batch=batch_id
            ) as masks:
                kept = self.mask_row_cache
                reused0 = kept.rows_reused
                mask_rows, mask_index = static_mask_compact(
                    pods, snapshot, nt, kept
                )
                masks.set_metadata(
                    rows=mask_rows.shape[0],
                    rows_reused=kept.rows_reused - reused0,
                )
            with flightrecorder.stage(
                "pack.overlay", totals=totals, batch=batch_id
            ) as overlay:
                # pods requesting resources no node advertises are
                # unsatisfiable: point them at a dedicated all-False row
                if batch.unsatisfiable.any():
                    mask_rows = np.concatenate(
                        [mask_rows, np.zeros((1, nt.capacity), dtype=bool)]
                    )
                    mask_index = mask_index.copy()
                    mask_index[batch.unsatisfiable] = mask_rows.shape[0] - 1

                # Nominated-pod overlay: reserve capacity for preemption
                # nominees (the batch analogue of _add_nominated_pods' virtual
                # add, generic_scheduler.go:535). Conservatively reserves for
                # ALL nominees EXCEPT pods already being placed: this batch's
                # own members and pods inside in-flight batches (their
                # placement rides the device carry; overlaying them too would
                # double-count and spuriously starve nodes -- the old answer
                # was a full pipeline drain per dispatch while ANY nomination
                # lived, which serialized the dispatcher against the committer
                # for the whole post-preemption burst).
                node_requested = nt.requested
                node_nzr = nt.non_zero_requested
                # skip the overlay for pods being placed RIGHT NOW: this
                # batch's members and pods inside dispatched-but-not-committing
                # batches (their placement rides the device carry; overlaying
                # them too over-reserves their nodes and cascades spurious
                # preemption). The mid-COMMIT head batch is NOT excluded: its
                # failures are being requeued with live nominations by the
                # deferred wave at this very moment, and their reservations
                # must stand.
                batch_uids = {pi.pod.metadata.uid for pi in solver_infos}
                own_uids = len(batch_uids)
                with self._pending_cv:
                    for pend in self._pending_q:
                        if not pend.get("committing"):
                            batch_uids.update(
                                pi.pod.metadata.uid
                                for pi in pend["solver_infos"]
                            )
                overlay_pods = []
                overlay_rows = []
                for node_name, nominated in nominated_by_node.items():
                    if node_name not in nt.names:
                        continue
                    j = nt.row(node_name)
                    for npod in nominated:
                        if npod.metadata.uid in batch_uids:
                            continue
                        overlay_pods.append(npod)
                        overlay_rows.append(j)
                overlaid = bool(overlay_pods)
                if overlaid:
                    node_requested = node_requested.copy()
                    node_nzr = node_nzr.copy()
                    nbatch = pack_pod_batch(overlay_pods, nt.dims)
                    np.add.at(
                        node_requested, np.asarray(overlay_rows),
                        nbatch.requests,
                    )
                    np.add.at(
                        node_nzr, np.asarray(overlay_rows),
                        nbatch.non_zero_requests,
                    )
                overlay.set_metadata(
                    inflight_pods=len(batch_uids) - own_uids,
                    overlaid=len(overlay_pods),
                )
            with flightrecorder.stage(
                "pack.order", totals=totals, batch=batch_id
            ) as ordering:
                b = batch.size
                # fixed solve shape: every batch pads to max_batch so the
                # solver JITs exactly once per (node-bucket, variant). The
                # adaptive controller may floor the pad at its current rung
                # instead -- small batches then run a proportionally cheaper
                # solve -- so the signature set is {warmed rungs} +
                # {max_batch} plus the defensive oversize bucket. Warmup
                # compiles the BASIC layouts for every rung; constrained
                # layouts warm at max_batch only (the pre-existing
                # latency-rung tradeoff: rare enough that the one-time
                # compile lands on demand), so a batch whose aggregates say
                # constraint families may pack never ESCALATES to a mid rung
                # -- it takes the max_batch signature as before.
                pad_floor = self.solve_pad
                if not pad_floor or b > pad_floor:
                    # escalate to the smallest pre-compiled rung that fits
                    # (ladder-aware: an oversize plain batch lands on the
                    # next warmed rung up instead of jumping straight to the
                    # max_batch signature); anything past every warmed rung,
                    # or possibly-constrained, takes the max_batch signature
                    may_constrain = (
                        has_hard_spread or has_affinity or score_dynamic
                        or has_scoring_terms
                    )
                    fitting = [p for p in self._warmup_pads if p >= b]
                    pad_floor = (
                        min(fitting) if fitting and not may_constrain
                        else self.max_batch
                    )
                padded = max(
                    pad_floor, POD_BUCKET * math.ceil(b / POD_BUCKET)
                )
                order = batch.order
                # -- tenant fairness bias (scheduler/tenancy.py): within
                # each priority level, re-merge the solve order so the tenant
                # with the lowest virtual dominant share places next -- the
                # solve order IS the arbitration point of the
                # sequential-replay scan, so every tier
                # (pallas/XLA/mesh/host-greedy) honors the bias with zero
                # kernel changes. Single-tenant batches exit after one
                # namespace sweep.
                tt = self.tenant_shares
                if tt is not None and b > 1:
                    from kubernetes_tpu.scheduler.tenancy import fair_order

                    tt.refresh_capacity(nt)
                    order = fair_order(order, pods, batch.priorities, tt)
                if gangs is not None:
                    order = _gang_contiguous(order, gangs)
                req = np.zeros((padded, nt.dims.num_dims), dtype=np.int32)
                nzr = np.zeros((padded, 2), dtype=np.int32)
                midx = np.zeros(padded, dtype=np.int32)
                active = np.zeros(padded, dtype=bool)
                req[:b] = batch.requests[order]
                nzr[:b] = batch.non_zero_requests[order]
                midx[:b] = mask_index[order]
                active[:b] = True
                if inactive_uids:
                    # gang quorum fixup: masked group members solve to
                    # NO_NODE
                    for k in range(b):
                        if (
                            solver_infos[int(order[k])].pod.metadata.uid
                            in inactive_uids
                        ):
                            active[k] = False
                u = mask_rows.shape[0]
                u_padded = MASK_ROW_BUCKET * math.ceil(u / MASK_ROW_BUCKET)
                rows = np.zeros((u_padded, nt.capacity), dtype=bool)
                rows[:u] = mask_rows

                # hard topology-spread constraints solve on device via the
                # group-count scan (ops/topology.py); required (anti-)affinity
                # via the count-tensor replay (ops/affinity.py)
                # non-resource score plugins: pack when they can influence
                # ranking (dynamic families already forced a pipeline drain
                # above, so the snapshot these counts come from includes
                # in-flight placements)
                ordered_pods = [pods[int(i)] for i in order]
                hard_w = 1
                if prof0 is not None:
                    ipa_plugin = prof0.plugin_instance("InterPodAffinity")
                    hard_w = getattr(
                        ipa_plugin, "hard_pod_affinity_weight", 1
                    ) if ipa_plugin is not None else 1
                ordering.set_metadata(
                    padded=padded,
                    inactive=(
                        int(b - np.count_nonzero(active[:b]))
                        if inactive_uids else 0
                    ),
                )
            score_batch = None
            spread = None
            affinity = None
            # a family past its envelope sends the batch to the host
            # path: (reason, whether that path must first see every
            # in-flight placement committed)
            routed = None
            with flightrecorder.stage(
                "pack.families", totals=totals, batch=batch_id
            ) as families:
                facts = self.family_facts
                tally0 = facts.tally()
                cut_at = None
                try:
                    with flightrecorder.stage("pack.score"):
                        score_batch = pack_score_batch(
                            ordered_pods, snapshot, nt,
                            prof0.informers if prof0 is not None else None,
                            prof0.score_plugin_weights()
                            if prof0 is not None else {},
                            hard_pod_affinity_weight=hard_w,
                            cluster_affinity_scoring=cluster_ipa,
                            admissions=adms, facts=facts,
                        )
                except ScoreEnvelopeCut as cap:
                    # cut where the envelope is met, unless the batch has
                    # to stay whole (a gang's passes, a bisection's
                    # halves); routed either way, so no other family is
                    # packed
                    if not (gangs or inactive_uids or raise_on_exhaust):
                        cut_at = cap.fit
                        if cap.reason != "score_signatures":
                            facts.score_dynamic_cuts += 1
                    routed = (cap.reason, True)
                except ScoreEnvelopeExceeded:
                    # the sequential path filters against the host
                    # cache, which must include every in-flight placement
                    routed = ("score_envelope", True)
                if routed is None and has_hard_spread:
                    spread = pack_spread_batch(
                        ordered_pods, snapshot, nt, facts
                    )
                    if spread is None:
                        routed = ("spread_envelope", False)
                if routed is None and has_affinity:
                    affinity = pack_affinity_batch(
                        ordered_pods, snapshot, nt, facts
                    )
                    if affinity is None and has_affinity_terms:
                        # real affinity/exist rows expected but the
                        # packer bailed -- port-only batches fall through
                        # to the port-row builder instead
                        routed = ("affinity_envelope", False)
                    elif batch_ports:
                        # within-batch host-port conflicts ride synthetic
                        # anti rows (ops/affinity.add_host_port_rows);
                        # existing-pod conflicts are already in the
                        # static mask
                        affinity = add_host_port_rows(
                            ordered_pods, snapshot, nt, affinity, facts
                        )
                        if affinity is None:
                            # a port-only batch may not have drained above
                            routed = ("port_envelope", True)
                families.set_metadata(**{
                    name: now - before
                    for name, now, before in zip(
                        family_facts.TALLY, facts.tally(), tally0
                    )
                })
            if cut_at is not None:
                if routed[0] == "score_signatures":
                    self.score_signature_cuts += 1
                    metrics.score_signature_caps.inc(action="cut")
                    span.finish(routed="score_signature_cut")
                else:
                    span.finish(routed="score_dynamic_cut")
                in_order = [solver_infos[int(i)] for i in order]
                raise ScoreSignatureCut(in_order[:cut_at], in_order[cut_at:])
            if routed is not None:
                # envelope exceeded: the host path keeps full correctness
                reason, drain = routed
                if reason == "score_signatures":
                    self.score_signature_host += 1
                    metrics.score_signature_caps.inc(action="host")
                self.envelope_fallbacks += 1
                if drain:
                    drain_inflight(reason)
                span.finish(tier=TIER_SEQUENTIAL, routed=reason)
                for pi in solver_infos:
                    self.pods_fallback += 1
                    self.attempt_schedule(pi)
                return None

        # from the packed batch to the solve's call: the prewarm test, the
        # state's handshake, the upload's pieces and the tiers to try
        with flightrecorder.stage("dispatch.handshake", batch=batch_id):
            span.note(padded=padded)
            dispatch.set_metadata(padded=padded)
            solve_timer = metrics.SinceTimer(metrics.batch_solve_duration)

            # preemption prewarm: when the batch's most demanding request
            # fits on NO node right now, failures (and a preemption wave)
            # are coming -- build + upload the victim pack on a helper
            # thread WHILE the solve runs, instead of paying the ~0.25s
            # pack + ~5MB upload inside the wave
            if self.preemptor is not None and b:
                free_nodes = nt.allocatable - node_requested  # [N, R]
                req_max = req[:b].max(axis=0)
                if not (
                    (free_nodes >= req_max).all(axis=1) & nt.valid
                ).any():
                    self.preemptor.prewarm_pack_async()

            constrained = (
                spread is not None
                or affinity is not None
                or score_batch is not None
            )
            if constrained and nt.capacity > CONSTRAINED_NODE_CAP:
                self._drain_pending()
                self.envelope_fallbacks += 1
                span.finish(
                    tier=TIER_SEQUENTIAL, routed="constrained_node_cap"
                )
                for pi in solver_infos:
                    self.pods_fallback += 1
                    self.attempt_schedule(pi)
                return None

            # -- device-state generation handshake
            # (scheduler/device_state.py) --
            # Runs after every route-to-host bail-out above: it reconciles the
            # state's bookkeeping on the assumption that the decided upload /
            # scatter actually reaches the device this dispatch.
            state = self.device_state

            def negotiate(unmirrored: bool):
                return state.negotiate(
                    nt, self.tensor_cache, node_requested, node_nzr, overlaid,
                    in_flight=in_flight_at_pack or self._pending_exists(),
                    unmirrored=unmirrored, assumed_seq=assumed_seq,
                )

            unmirrored = self._unmirrored_exists()
            neg = negotiate(unmirrored)
            mirrored = False
            if neg is None and unmirrored:
                # the dispatcher waits, outside pack, until every batch in
                # flight has mirrored: a wait with a name of its own. With
                # nothing unmirrored the answer cannot change (a pack that
                # predates a commit stays one): straight to the drain
                with flightrecorder.stage(
                    "mirror_wait", totals=totals, batch=span.batch_id
                ):
                    mirrored = self._await_mirrors()
            if mirrored:
                # the blocked path (membership adopt / divergence repair)
                # only needs the carry to equal the expectation, which holds
                # the moment every in-flight batch has MIRRORED -- so wait for
                # the mirrors (the committer signals them; typically a few
                # ms) and renegotiate before paying a full pipeline drain
                neg = negotiate(False)
                if neg is not None:
                    self.speculative_rewinds += 1
                    metrics.speculative_rewinds.inc(reason="mirror_wait")
            if neg is None:
                # the handshake needs an upload but the device carry is ahead
                # of the host by the in-flight batches (node churn, bind
                # failure, dead carry): land them, then redo this dispatch
                # from the fresh host state
                if self._pending_exists():
                    self.speculative_rewinds += 1
                    metrics.speculative_rewinds.inc(reason="drain")
                self._drain_pending()
                span.finish(routed="drain_redispatch")
                return self._dispatch_solve(
                    solver_infos, pod_scheduling_cycle,
                    inactive_uids=inactive_uids,
                )
            if neg.row_patch_rewind:
                self.speculative_rewinds += 1
                metrics.speculative_rewinds.inc(reason="row_patch")
            # how the resident state is brought up to date and the rows sent
            # for it, in the ring and on the profiler's
            # ``sched/solve_dispatch`` span: a carry that is reused where it
            # should have been uploaded shows here and nowhere else
            span.note(carry=neg.carry, delta_rows=neg.delta_rows)
            carry_stats = {
                "devices": (
                    1 if self.mesh is None else int(self.mesh.devices.size)
                ),
                "carry": neg.carry,
                "carry_rows": neg.carry_rows,
                # of them, the slots a node joined or left since the last
                # batch (membership churn rides the same scatter)
                "member_rows": neg.member_rows,
                # the pods of the batch: the steps a one-chip kernel runs of
                # the ``padded`` that ``sched/dispatch`` says
                "steps": int(b),
                # the call's resource columns (four fixed, one an extended
                # resource the nodes advertise) and whether its profile
                # scores MostAllocated
                "r_dims": int(nt.dims.num_dims),
                "score_most": int(bool(config.most_allocated_weight)),
                # the static rows of the score family the call carries: 0
                # where the family is not live
                "score_sig_rows": (
                    0 if score_batch is None
                    else int(score_batch.direct_rows.shape[0])
                ),
            }
            # single-buffer upload: the whole batch -- including a
            # constrained batch's ~40 family count tensors -- rides ONE
            # int32 buffer, re-sliced (and bitcast for float tensors)
            # on device (ops/assignment.py solve_packed), so a dispatch
            # is one host->device transfer instead of one per operand.
            # Chosen on an earlier machine; one transfer versus many is
            # not re-measured on this one (PERF.md "Decisions to
            # re-measure"). On a mesh the buffer
            # uploads replicated while the resident node state stays
            # SHARDED over the node axis; the delta-scatter slots apply
            # shard-locally in the sharded twin, so steady-state churn
            # costs O(DELTA_ROW_BUCKET) on the link regardless of N
            pieces = [
                ("req", req),
                ("nzr", nzr),
                ("midx", midx),
                ("active", active.astype(np.int32)),
                # on a mesh the rows ship as a separate bool operand,
                # column-sharded host-side (ops/host_masks.py) -- each
                # shard uploads only its [U, N/P] mask columns
                ("rows", mask_rows_upload(rows, self.mesh)),
            ]
            if not neg.static_ok:
                pieces.append(("alloc", nt.allocatable))
                pieces.append(("valid", nt.valid.astype(np.int32)))
            if not neg.carry_ok:
                pieces.append(("req_state", node_requested))
                pieces.append(("nzr_state", node_nzr))
            else:
                # steady state: the resident [N, R] tensors stay on
                # device; only the changed-row scatter rides the buffer
                pieces += delta_slot_pieces(
                    nt.capacity, nt.dims.num_dims,
                    fix_rows=neg.fix_rows, alloc_rows=neg.alloc_rows,
                    node_requested=node_requested, node_nzr=node_nzr,
                    allocatable=nt.allocatable, valid=nt.valid,
                )
            if constrained:
                from kubernetes_tpu.ops.assignment import ConstPiece

                def fam_pieces(prefix, packed_arrs, noop_arrs):
                    """Present families ride the buffer; absent ones
                    become ConstPiece markers (free on-device constants
                    instead of ~1MB of uploaded zeros/sentinels). On a
                    MESH absent families ride as real zero arrays
                    instead: every ConstPiece combo is its own layout
                    (= its own multi-second GSPMD compile), and the
                    mesh contract is TWO constrained jit signatures per
                    mesh shape, the score family absent (its few
                    placeholder rows) and live -- the upload cost of the
                    noop tensors is what the pre-delta mesh path always
                    paid."""
                    if packed_arrs is not None:
                        for i, a in enumerate(packed_arrs):
                            pieces.append((f"{prefix}{i}", np.asarray(a)))
                    elif self.mesh is not None:
                        for i, a in enumerate(noop_arrs):
                            pieces.append((f"{prefix}{i}", np.asarray(a)))
                    else:
                        for i, a in enumerate(noop_arrs):
                            pieces.append(
                                (f"{prefix}{i}", ConstPiece.from_uniform(a))
                            )

                fam_pieces(
                    "sp",
                    pad_spread_tensors(spread, padded)
                    if spread is not None else None,
                    noop_spread_tensors(padded, nt.capacity),
                )
                fam_pieces(
                    "af",
                    pad_affinity_tensors(affinity, padded)
                    if affinity is not None else None,
                    noop_affinity_tensors(padded, nt.capacity),
                )
                fam_pieces(
                    "sc",
                    pad_score_tensors(score_batch, padded)
                    if score_batch is not None else None,
                    noop_score_tensors(padded, nt.capacity),
                )
            solve_mode = "constrained" if constrained else self.solver_mode

            def run_device(allow_pallas: bool):
                if poison_key is not None:
                    raise PoisonError(poison_key)
                inj = get_injector()
                if inj is not None:
                    hang = inj.hang_seconds_maybe(
                        FaultPoint.DEVICE_SOLVE_HANG
                    )
                    if hang > 0:
                        time.sleep(hang)
                    inj.raise_maybe(FaultPoint.DEVICE_SOLVE)
                return solve_packed(
                    pieces,
                    *state.operands(neg),
                    config=config,
                    mode=solve_mode,
                    allow_pallas=allow_pallas,
                    mesh=self.mesh,
                )

            def run_host_greedy():
                if poison_key is not None:
                    # the malformed row poisons the host replay too (it
                    # packs from the same arrays); only the per-pod
                    # sequential oracle fails it ALONE
                    raise PoisonError(poison_key)
                a, r_out, z_out = host_greedy_assign(
                    nt.allocatable, node_requested, node_nzr, nt.valid,
                    req, nzr, rows, midx, active,
                    config=config,
                )
                return a, r_out, z_out, None, None

            attempts = [
                (t, (lambda ap=(t == TIER_PALLAS): run_device(ap)))
                for t in self._device_tiers(
                    solve_mode, padded, nt.capacity, nt.dims.num_dims,
                    u_padded,
                )
            ]
            # the host tier needs host state that reflects EVERY
            # placement; with batches in flight the device carry is
            # ahead of node_requested, so the tier is only offered when
            # nothing is pending (exhaustion with pending batches drains
            # and redispatches from fresh host state instead)
            if not constrained and not self._pending_exists():
                attempts.append((TIER_HOST_GREEDY, run_host_greedy))
        try:
            with flightrecorder.stage(
                "device_solve", span, totals, **carry_stats
            ) as solving:
                tier, out = self.ladder.run(
                    attempts, label=f"batch b={b}"
                )
                booked = self.ladder.booked_tier(tier)
                solving.set_metadata(tier=booked)
            self._jit_watch.refresh()
            metrics.solves_by_resource_score.inc(score=config.label())
        except LadderExhausted as exhaust_err:
            state.nothing_landed(neg)
            if raise_on_exhaust:
                # bisection sub-solve: the caller owns this group's
                # disposition (split further or isolate)
                span.finish(routed="bisect_exhausted")
                raise
            if self._pending_exists():
                # in-flight batches blocked the host tier: land them
                # (the committer's own recovery handles their
                # failures), then redo this dispatch from fresh host
                # state with the breakers now routing around the
                # sick tiers
                self._drain_pending()
                span.finish(routed="exhausted_redispatch")
                return self._dispatch_solve(
                    solver_infos, pod_scheduling_cycle,
                    inactive_uids=inactive_uids, gangs=gangs,
                )
            return self._contain_exhausted_batch(
                solver_infos, pod_scheduling_cycle, span,
                inactive_uids,
                poisoned=isinstance(
                    exhaust_err.__cause__, PoisonError
                ),
            )
        # what follows the solve's dispatch: the resident state's new
        # references, the download begun, the pending record
        with flightrecorder.stage("dispatch.landed", batch=batch_id):
            assignments_dev, *resident = out
            if tier == TIER_HOST_GREEDY:
                state.host_solved(neg, assignments_dev, req, nzr, overlaid)
            else:
                if state.landed(neg, resident, overlaid):
                    self._note_device_rebuilt()
                assignments_dev.copy_to_host_async()
            if self.mesh is not None and tier == TIER_PALLAS:
                self.mesh_shard_solves += 1
            span.note(tier=booked)
            return {
                # the attempt's own name: its breaker guards the download
                "tier": tier,
                "carry_in": neg.carry_in,
                "span": span,
                "solver_infos": list(solver_infos),
                "has_required_anti": has_required_anti,
                # constraints between pods (spread, affinity, score
                # families): the gang fixup counts no slots under them
                "constrained": constrained,
                "has_ports": batch_ports,
                "has_scoring_terms": has_scoring_terms,
                "order": order,
                "assignments_dev": assignments_dev,
                "download": self._eager_download(assignments_dev),
                "req": req,
                "nzr": nzr,
                "b": b,
                "names": nt.names,
                "num_nodes": nt.num_nodes,
                "snapshot": snapshot,
                "cycle": pod_scheduling_cycle,
                "overlaid": overlaid,
                "solve_timer": solve_timer,
                "mask_rows": mask_rows,
                "mask_index_solved": midx,
            }

    # -- blast-radius containment (robustness/containment.py) ----------------

    def _note_exhaust_sig(self, solver_infos: List[PodInfo]) -> bool:
        """Track the exhausted-batch uid signature; True when the SAME
        batch has now fallen whole at least twice in a row (a retry
        storm, not a transient)."""
        sig = frozenset(
            pi.pod.metadata.uid for pi in solver_infos
        )
        if sig and sig == self._last_exhaust_sig:
            self._exhaust_repeats += 1
        else:
            self._last_exhaust_sig = sig
            self._exhaust_repeats = 1
        return self._exhaust_repeats >= 2

    def _contain_exhausted_batch(
        self, solver_infos: List[PodInfo], pod_scheduling_cycle: int,
        span, inactive_uids, poisoned: bool = False,
    ):
        """Disposition of a ladder-exhausted batch with nothing in
        flight. Tracks the crash-loop signature (an identical batch
        exhausting twice in a row is a retry storm, not a transient),
        then: multi-pod batches take the bisection search, a
        crash-looping singleton goes straight to quarantine, and
        everything else (containment off, gang batches, first-time
        singletons) keeps the sequential-floor fallback."""
        crashloop = self._note_exhaust_sig(solver_infos)
        if crashloop:
            metrics.exhausted_crashloops.inc()
            flightrecorder.mark(
                "exhausted_crashloop", pods=len(solver_infos),
                repeats=self._exhaust_repeats,
            )
            logger.warning(
                "ladder_exhausted crash loop: the same %d-pod batch "
                "exhausted %d times in a row; engaging containment",
                len(solver_infos), self._exhaust_repeats,
            )
        cc = self.containment_config
        gang = any(
            pi.pod.metadata.labels.get(POD_GROUP_LABEL)
            for pi in solver_infos
        )
        if not cc.enabled or inactive_uids or gang:
            # gang batches never bisect (a split would break the
            # all-or-nothing quorum semantics); the sequential path
            # keeps full correctness for them
            return self._exhausted_sequential(
                solver_infos, pod_scheduling_cycle, span
            )
        if len(solver_infos) == 1:
            if crashloop or poisoned:
                # the singleton itself is the poison (typed cause, or
                # the same batch exhausting repeatedly): no batch left
                # to protect, but redispatching it forever is the
                # retry storm -- strike it into quarantine
                span.finish(routed="quarantine")
                self._quarantine_isolated(
                    solver_infos[0],
                    reason="poison" if poisoned else "crashloop",
                )
                return None
            # first exhaustion of a singleton may be transient (breaker
            # cool-offs, a blocked host tier): one sequential attempt
            return self._exhausted_sequential(
                solver_infos, pod_scheduling_cycle, span
            )
        span.finish(routed="bisect")
        self._bisect_batch(
            solver_infos, pod_scheduling_cycle, force=crashloop
        )
        return None

    def _exhausted_sequential(
        self, solver_infos: List[PodInfo], pod_scheduling_cycle: int,
        span,
    ):
        """The pre-containment floor: the whole batch runs the per-pod
        sequential oracle."""
        metrics.solver_fallbacks.inc(
            tier=TIER_SEQUENTIAL, reason="ladder_exhausted"
        )
        flightrecorder.mark(
            "fallback", tier=TIER_SEQUENTIAL,
            reason="ladder_exhausted",
        )
        span.finish(
            tier=TIER_SEQUENTIAL, routed="ladder_exhausted"
        )
        self.ladder.record_sequential(len(solver_infos))
        logger.warning(
            "solver ladder exhausted; %d pods take the "
            "sequential oracle path", len(solver_infos),
        )
        for pi in solver_infos:
            self.pods_fallback += 1
            self.attempt_schedule(pi)
        return None

    def _bisect_batch(
        self, solver_infos: List[PodInfo], pod_scheduling_cycle: int,
        force: bool = False,
    ) -> None:
        """O(log B) poison isolation: split the exhausted batch and
        re-solve each half synchronously on the already-warm pad rungs
        (sub-batches pad to the smallest warmed rung that fits, so no
        sub-solve compiles). Halves that solve COMMIT at their normal
        device tier -- the healthy pods' blast radius ends here; halves
        that exhaust again split further until the offenders are
        singletons, which go to the quarantine ledger.

        Systemic-failure guard: ``bisect_abort_after`` isolated
        singletons with ZERO successful sub-solves means every subset
        fails -- a sick device, not a poison signature -- and the run
        aborts to the sequential floor. ``force`` (set by the
        crash-loop detector) disables the guard: a batch that already
        exhausted repeatedly must not keep redispatching."""
        cc = self.containment_config
        t0 = time.perf_counter()
        self.bisections += 1
        metrics.bisections.inc()
        flightrecorder.mark(
            "bisect_start", pods=len(solver_infos), force=force
        )
        mid = len(solver_infos) // 2
        work: "collections.deque" = collections.deque(
            [solver_infos[:mid], solver_infos[mid:]]
        )
        # (pod_info, typed_poison) -- a singleton isolated by a TYPED
        # PoisonError always quarantines; untyped isolations are only
        # trusted once some sibling sub-solve succeeded (else they are
        # indistinguishable from a systemic device failure)
        isolated: List[Tuple[PodInfo, bool]] = []
        done_uids: set = set()
        successes = 0
        subsolves = 0
        aborted = False

        def untyped_isolated() -> int:
            return sum(1 for _pi, typed in isolated if not typed)

        while work:
            if (
                not force
                and successes == 0
                and untyped_isolated() >= cc.bisect_abort_after
            ):
                aborted = True
                break
            group = list(work.popleft())
            subsolves += 1
            metrics.bisect_subsolves.inc()
            try:
                pending = self._dispatch_solve(
                    group, pod_scheduling_cycle, raise_on_exhaust=True
                )
            except SchedulerCrashed:
                raise
            except Exception as sub_err:  # noqa: BLE001 - split again
                if len(group) == 1:
                    # LadderExhausted-from-PoisonError (ladder paths)
                    # or a bare PoisonError (legacy mesh path)
                    typed = isinstance(sub_err, PoisonError) or (
                        isinstance(sub_err, LadderExhausted)
                        and isinstance(sub_err.__cause__, PoisonError)
                    )
                    isolated.append((group[0], typed))
                    flightrecorder.mark(
                        "bisect_isolated",
                        pod=group[0].pod.metadata.uid,
                        typed=typed,
                    )
                else:
                    m = len(group) // 2
                    # left-first DFS: committed groups land in the
                    # original pod order, so healthy placements match
                    # the no-poison batch bit-for-bit
                    work.appendleft(group[m:])
                    work.appendleft(group[:m])
                continue
            if pending is None:
                # the dispatch itself routed the group (envelope bails,
                # nested containment): those paths already disposed of
                # every pod
                successes += 1
                done_uids.update(
                    pi.pod.metadata.uid for pi in group
                )
                continue
            try:
                self._complete_solve(pending)
            except SchedulerCrashed:
                raise
            except Exception:  # noqa: BLE001 - download/commit failure
                # not an exhaustion: the standard recovery requeues the
                # group (a genuinely poisoned member re-trips
                # containment on its next pass)
                logger.exception("bisect sub-solve completion failed")
                self._recover_failed_batch(pending)
                done_uids.update(
                    pi.pod.metadata.uid for pi in group
                )
                continue
            successes += 1
            done_uids.update(pi.pod.metadata.uid for pi in group)
        dt_ms = (time.perf_counter() - t0) * 1000.0
        # post-loop systemic check too: a batch SMALLER than the abort
        # threshold can drain the work deque with zero successes and
        # only untyped isolations -- that is still "every subset
        # failed", not a poison signature
        if (
            not aborted
            and not force
            and successes == 0
            and untyped_isolated() > 0
        ):
            aborted = True
        if aborted:
            metrics.bisect_aborts.inc()
            # typed-poison singletons quarantine even on an aborted
            # run (the cause is attributable); everything else --
            # untyped isolations and unprocessed work -- takes the
            # sequential floor
            typed_pis = [pi for pi, typed in isolated if typed]
            for pi in typed_pis:
                done_uids.add(pi.pod.metadata.uid)
            remaining = [
                pi for pi in solver_infos
                if pi.pod.metadata.uid not in done_uids
            ]
            flightrecorder.mark(
                "bisect_abort", pods=len(solver_infos),
                isolated=len(isolated), subsolves=subsolves,
                remaining=len(remaining), ms=round(dt_ms, 3),
            )
            logger.warning(
                "bisection aborted after %d failed sub-solves with no "
                "success (systemic failure); %d pods take the "
                "sequential path", subsolves, len(remaining),
            )
            for pi in typed_pis:
                self._quarantine_isolated(pi, reason="poison")
            self.ladder.record_sequential(len(remaining))
            for pi in remaining:
                self.pods_fallback += 1
                self.attempt_schedule(pi)
            return
        flightrecorder.mark(
            "bisect_done", pods=len(solver_infos),
            isolated=len(isolated), subsolves=subsolves,
            ms=round(dt_ms, 3),
        )
        for pi, typed in isolated:
            self._quarantine_isolated(
                pi, reason="poison" if typed else "bisect"
            )

    def _quarantine_isolated(self, pi: PodInfo, reason: str) -> None:
        """Route one isolated pod through the quarantine ledger and
        surface the event on the pod (Warning event; the PARK
        additionally writes the typed PodQuarantined condition)."""
        # a quarantined pod holds no capacity: its quota charge (taken
        # at pop) must not pin the namespace ledger while it sits out
        self._quota_refund(pi.pod, "quarantine")
        self.pods_quarantined += 1
        disposition = self.quarantine.isolate(pi, reason=reason)
        prof = self.profiles.get(pi.pod.spec.scheduler_name)
        if prof is not None:
            try:
                prof.recorder.eventf(
                    pi.pod, "Warning", "Quarantined",
                    f"pod isolated by blast-radius containment "
                    f"({reason}); disposition: {disposition}",
                )
            except Exception:  # noqa: BLE001 - events are best-effort
                logger.exception(
                    "quarantine event for %s", pi.pod.key()
                )

    # -- carry integrity audit + device-loss rebuild -------------------------

    def audit_carry(self) -> str:
        """One carry-integrity sweep of the device-resident state
        (``DeviceNodeState.audit`` says what it compares and returns).
        Runs from the ControlPlaneReconciler sweep; safe to call from
        any thread."""
        return self.device_state.audit(self._in_flight)

    def _on_device_lost(self) -> None:
        """DEVICE_LOST fired: every device-resident buffer is gone.
        Drop all resident state + shadows, flag the in-flight batches
        (their results are garbage; the committer's recovery requeues
        their pods through the PR-1 machinery), drain, and let the
        current dispatch rebuild from the host cache through the
        existing cold-upload path. Detection -> rebuilt is metered into
        ``scheduler_tpu_device_rebuild_ms``."""
        self._device_lost_at = time.perf_counter()
        metrics.device_lost_events.inc()
        metrics.degraded_health.set(1, reason="device_lost")
        flightrecorder.mark("device_lost")
        logger.error(
            "device lost: dropping resident state, requeueing "
            "in-flight batches, rebuilding from the host cache"
        )
        with self._pending_cv:
            for p in self._pending_q:
                p["device_lost"] = True
        self.device_state.lost()
        self._drain_pending()

    def _note_device_rebuilt(self) -> None:
        """The first full upload after a device loss landed under a
        jitted solve: the resident state is rebuilt."""
        at = self._device_lost_at
        if at is None:
            return
        self._device_lost_at = None
        dt_ms = (time.perf_counter() - at) * 1000.0
        metrics.device_rebuild_ms.observe(dt_ms)
        metrics.degraded_health.set(0, reason="device_lost")
        flightrecorder.mark("device_rebuilt", ms=round(dt_ms, 3))
        logger.warning(
            "device state rebuilt from host cache %.1fms after loss",
            dt_ms,
        )

    @staticmethod
    def _eager_download(assignments_dev):
        """Start the device->host result copy at dispatch time (host
        tiers already hand back numpy -- nothing to transfer)."""
        if isinstance(assignments_dev, np.ndarray):
            return None
        if not _EAGER_DOWNLOAD_OK:
            # a starved host (<=2 cores) has no spare core to run the
            # copy thread: the overlap becomes pure GIL contention with
            # the dispatcher/committer (measured ~10% slower end-to-end)
            return None
        return _EagerDownload(assignments_dev)

    def _complete_solve(self, p) -> None:
        """Download the assignments, mirror the scan's node-state deltas
        into the host shadow (same int32 arithmetic), then run the batched
        commit pipeline.

        The download is the other blocking device interaction (a wedged
        device hangs np.asarray forever), so it runs under the same
        wall-clock watchdog as the solve, and the result is validated
        before it drives commits: garbage indices from a sick device
        (NaN-score argmax artifacts) must degrade, not bind pods to
        phantom nodes. Failures raise; the callers route the batch
        through _recover_failed_batch (requeue, never strand)."""
        if p.get("device_lost"):
            # the device died with this batch in flight: its result
            # buffers are gone/garbage. Raise so the caller's recovery
            # requeues every pod (the PR-1 machinery); the carry was
            # already dropped by _on_device_lost.
            sp = p.get("span") or flightrecorder.NULL_SPAN
            sp.finish(routed="device_lost")
            raise RuntimeError(
                "device lost with this batch in flight; requeueing"
            )
        tier = p.get("tier", TIER_XLA)
        breaker = self.ladder.breakers.get(tier)
        timeout = (
            self.ladder.config.solve_timeout_seconds
            if tier in (TIER_PALLAS, TIER_XLA)
            and self.ladder.config.enabled
            else 0.0
        )

        def download():
            eager = p.get("download")
            if eager is not None:
                # copy already in flight since dispatch; await it
                return eager.result()
            return np.asarray(p["assignments_dev"])

        fspan = p.get("span") or flightrecorder.NULL_SPAN
        totals = self.stage_totals
        # how long the dispatched batch waited for this thread
        waited = flightrecorder.handoff_wait(p.get("handed_off", 0.0))
        try:
            with flightrecorder.stage("download", fspan, totals, **waited):
                assignments = self.ladder.watchdog.call(
                    download, timeout, tier=tier
                )
        except SolveTimeout:
            if breaker is not None:
                breaker.force_open()
            metrics.solver_fallbacks.inc(
                tier=TIER_SEQUENTIAL, reason=f"{tier}_download_timeout"
            )
            flightrecorder.mark(
                "fallback", tier=TIER_SEQUENTIAL,
                reason=f"{tier}_download_timeout",
            )
            fspan.finish(routed="download_timeout")
            raise
        except Exception:
            if breaker is not None:
                breaker.record_failure()
            raise
        inj = get_injector()
        if inj is not None:
            assignments = inj.corrupt_assignments_maybe(
                FaultPoint.SOLVE_GARBAGE, assignments
            )
        head = assignments[: p["b"]]
        if head.size and (
            (head < NO_NODE).any() or (head >= len(p["names"])).any()
        ):
            # out-of-range node indices: the solve result is garbage
            if breaker is not None:
                breaker.record_failure()
            metrics.solver_fallbacks.inc(
                tier=TIER_SEQUENTIAL, reason=f"{tier}_garbage_result"
            )
            flightrecorder.mark(
                "fallback", tier=TIER_SEQUENTIAL,
                reason=f"{tier}_garbage_result",
            )
            fspan.finish(routed="garbage_result")
            raise RuntimeError(
                f"solve on tier {tier!r} returned out-of-range "
                f"assignments; discarding the batch result"
            )
        p["solve_timer"].observe()
        b = p["b"]
        metrics.batch_size.observe(b)
        mirror_seq = self.device_state.mirror(
            p, assignments, b, p["req"], p["nzr"], p["overlaid"]
        )
        # wake dispatchers parked in _await_mirrors at MIRROR time: the
        # commit/bind API transactions below can be hundreds of ms away,
        # and the speculative renegotiation only needs the mirror
        with self._pending_cv:
            self._pending_cv.notify_all()
        if inj is not None and inj.should_fire(FaultPoint.CARRY_CORRUPT):
            self.device_state.corrupt_row()
        with flightrecorder.stage("commit", fspan, totals):
            self._commit_batch(
                p["solver_infos"], p["order"], assignments, p["names"],
                p["num_nodes"], p["snapshot"], p["cycle"],
                mask_info=(p.get("mask_rows"), p.get("mask_index_solved")),
                gang_failed_uids=p.get("gang_failed_uids"),
                gang_requeue_uids=p.get("gang_requeue_uids"),
                span=fspan,
            )
        # every pod this batch placed is in the cache now: a snapshot
        # refreshed from here on holds them (_explain_rows)
        self._assumed_seq = mirror_seq
        fspan.finish()
        if (
            self._prewarm_next_commit
            and not self._deferred_preempt
            and self.preemptor is not None
        ):
            # the wave's preemptors just bound: refresh the victim pack
            # in the background so the next contention burst finds it
            # (and its device upload) warm
            self._prewarm_next_commit = False
            self.preemptor.prewarm_pack_async()

    # -- batched commit ------------------------------------------------------

    def _commit_batch(
        self,
        solver_infos: List[PodInfo],
        order: np.ndarray,
        assignments: np.ndarray,
        names: List[str],
        num_nodes: int,
        snapshot,
        pod_scheduling_cycle: int,
        mask_info=None,
        gang_failed_uids=None,
        gang_requeue_uids=None,
        span=None,
    ) -> None:
        """Post-solve pipeline for the whole batch: Reserve -> assume ->
        Permit (scheduler.go:615-660 semantics preserved), then ONE async
        binding task that commits every default-binder pod in a single
        bulk transaction; non-default binds (extenders, custom bind
        plugins, Permit waiters) take the per-pod binding cycle.

        Pods for which every Reserve/Permit plugin is a declared no-op
        (Framework.plugins_relevant) skip the per-pod plugin pipeline and
        are assumed in one bulk cache transaction -- the batch commit is
        otherwise the profile-run hot loop of the 10k burst."""
        b = len(solver_infos)
        if span is None:
            span = flightrecorder.NULL_SPAN
        # schedule_batch flushes at profile boundaries, so the whole batch
        # shares one profile (batch.py:242)
        prof = self.profiles.get(solver_infos[0].pod.spec.scheduler_name)
        if prof is None:
            logger.error(
                "no profile for %s", solver_infos[0].pod.key()
            )
            return
        extenders = self.algorithm.extenders
        bulk_ok = (
            prof.uses_default_binder_only() and self._bind_pool is not None
        )
        # hoisted out of the per-pod loop: binder extenders (normally
        # none) and the relevance tables (empty table =>
        # plugins_relevant is False for every pod, no call needed)
        binder_extenders = [e for e in extenders if e.is_binder()]
        reserve_maybe = prof.relevance_entries("reserve")
        permit_maybe = prof.relevance_entries("permit")

        plain_pis: List[PodInfo] = []  # placed pods on the bulk path ...
        clones: List = []  # ... their assumed clones ...
        hosts: List[str] = []  # ... and target nodes (parallel lists)
        permit_at: List[int] = []  # ... those of them that Permit reads
        slow: List[Tuple[PodInfo, int, int]] = []  # (pod_info, choice, k)

        # -- fused fast path: when no per-pod gate can fire (default
        # binder only, no gang masking, no binder extenders, and no
        # reserve/permit plugin relevant to ANY pod in the batch -- one
        # any() probe instead of three checks per pod), the whole
        # classification collapses to numpy: one stable argsort over the
        # assignment row splits NO_NODE from placed AND groups the
        # placed slots by target node (the grouped order feeds the
        # cache's per-node bulk assume), and one native pass
        # (commit_gather) gathers PodInfos + assumed clones + hosts.
        fast = bulk_ok and not gang_failed_uids and not binder_extenders
        if fast and (reserve_maybe or permit_maybe):
            fast = not any(
                (
                    reserve_maybe
                    and prof.plugins_relevant("reserve", pi.pod)
                )
                or (
                    permit_maybe
                    and prof.plugins_relevant("permit", pi.pod)
                )
                for pi in solver_infos
            )
        if fast:
            with flightrecorder.stage(
                "commit.gather", batch=span.batch_id
            ):
                head = np.asarray(assignments[:b])
                grp = np.argsort(head, kind="stable")
                n_unplaced = int((head == NO_NODE).sum())
                placed = grp[n_unplaced:]
                order_np = np.asarray(order)
                order2 = order_np[placed].tolist()
                assign2 = head[placed].tolist()
                gather = (
                    _commit_gather
                    if _commit_gather is not None
                    else _commit_gather_py
                )
                plain_pis, clones, hosts = gather(
                    solver_infos, order2, assign2,
                    names if isinstance(names, list) else list(names),
                )
            if n_unplaced:
                slow = [
                    (solver_infos[int(order_np[k])], NO_NODE, k)
                    for k in grp[:n_unplaced].tolist()
                ]
        else:
            # numpy scalar -> int conversion in one C pass each (only
            # the per-pod loop reads them)
            order_l = order.tolist()
            assign_l = assignments.tolist()
            plain: List[Tuple[PodInfo, str]] = []  # (pod_info, host)
            for k in range(b):
                pi = solver_infos[order_l[k]]
                choice = assign_l[k]
                if (
                    gang_failed_uids
                    and pi.pod.metadata.uid in gang_failed_uids
                ):
                    # quorum-masked gang member: no placement, no
                    # preemption (the group chose not to place; a
                    # PodGroupMemberAdd wakeup, or the wakeup of
                    # capacity given back, retries once the group can
                    # assemble). A member of a gang the fixup could not
                    # decide goes round again at once
                    metrics.schedule_attempts.inc(result="unschedulable")
                    span.bump("gang_masked")
                    self.record_scheduling_failure(
                        prof, pi,
                        "pod group cannot reach minMember this cycle",
                        "Unschedulable", "", pod_scheduling_cycle,
                        skip_backoff=bool(
                            gang_requeue_uids
                            and pi.pod.metadata.uid in gang_requeue_uids
                        ),
                    )
                    self.pods_solved_on_device += 1
                    continue
                if choice == NO_NODE:
                    slow.append((pi, choice, k))
                    continue
                pod = pi.pod
                if (
                    not bulk_ok
                    or (
                        reserve_maybe
                        and prof.plugins_relevant("reserve", pod)
                    )
                    or (
                        binder_extenders
                        and any(
                            e.is_interested(pod) for e in binder_extenders
                        )
                    )
                ):
                    slow.append((pi, choice, k))
                elif not (
                    permit_maybe and prof.plugins_relevant("permit", pod)
                ):
                    plain.append((pi, names[choice]))
                elif prof.permit_batchable(pod):
                    # assumed in bulk with the plain pods, then Permit
                    # once for all of them (a gang at a time)
                    plain.append((pi, names[choice]))
                    permit_at.append(len(plain) - 1)
                else:
                    slow.append((pi, choice, k))
            if plain:
                with flightrecorder.stage(
                    "commit.clone", batch=span.batch_id
                ):
                    if _assume_clones is not None:
                        clones = _assume_clones(
                            [pi.pod for pi, _ in plain],
                            [host for _, host in plain],
                        )
                    else:
                        clones = []
                        for pi, host in plain:
                            assumed = pi.pod.assumed_clone()
                            assumed.spec.node_name = host
                            clones.append(assumed)
                plain_pis = [pi for pi, _ in plain]
                hosts = [host for _, host in plain]

        bulk: List[Tuple] = []
        deferred: List[Tuple] = []  # sync-mode Permit waiters
        if plain_pis:
            with flightrecorder.stage(
                "commit.assume", batch=span.batch_id
            ):
                # on the fast path the argsort grouped the clones by
                # target node, so the cache lands them as per-node runs
                # (one node lookup + one generation bump per run)
                errs = self.cache.assume_pods(clones)
            self.queue.delete_nominated_pods_if_exist(clones)
            # CycleState is built lazily in the binding cycle (only
            # pre_bind/unreserve/post_bind plugins and failure paths read
            # it; the plain burst has none)
            if any(errs):
                for pi, assumed, host, err in zip(
                    plain_pis, clones, hosts, errs
                ):
                    if err is not None:
                        self.record_scheduling_failure(
                            prof, pi, str(err), "SchedulerError", "",
                            pod_scheduling_cycle,
                        )
                        continue
                    bulk.append((prof, None, pi, assumed, host))
            else:
                bulk = [
                    (prof, None, pi, assumed, host)
                    for pi, assumed, host in zip(plain_pis, clones, hosts)
                ]
            self.pods_solved_on_device += len(plain_pis)
            span.bump("placed", len(plain_pis))
            if permit_at:
                bulk = self._permit_assumed(
                    prof, bulk, permit_at, errs, snapshot,
                    pod_scheduling_cycle, span,
                )

        failed_group: List[Tuple[PodInfo, FitError]] = []
        cluster_anti = None
        # live nodes only: with the slot layout, num_nodes counts free
        # (retired) slots too, and the "0/N nodes are available" message
        # must not claim more nodes than the cluster has
        live_nodes = sum(1 for n in names if n)
        # statuses are a pure function of the (deduplicated) mask row:
        # identical unschedulable pods share one dict
        statuses_by_row: dict = {}
        for pi, choice, k in slow:
            if choice == NO_NODE:
                adm = pi.pod.__dict__.get("_admission")
                if adm is not None and adm.vol_counts:
                    # the additive volume-count columns are CONSERVATIVE
                    # (a handle shared across resident pods counts once
                    # per pod), so a device reject of a countable-volume
                    # pod may be a false negative. Pin the pod host-only
                    # and requeue straight to the activeQ: the next
                    # cycle runs the exact per-node oracle (CSILimits /
                    # in-tree unique-handle sets), which either places
                    # it or produces the true unschedulable verdict.
                    pi.pod.__dict__["_admission"] = adm.as_host_only(
                        "volume-count-reject"
                    )
                    self.volume_reject_retries += 1
                    span.bump("volume_retries")
                    self.record_scheduling_failure(
                        prof, pi,
                        "countable-volume pod rejected by the device "
                        "solve; re-checking on the host path",
                        "Unschedulable", "", pod_scheduling_cycle,
                        skip_backoff=True,
                    )
                    continue
                coord = self.partition_coordinator
                if coord is not None and coord.try_spill(pi.pod):
                    # cross-partition spill: this stack's node slice has
                    # no room (or no feasible node) -- the pod is
                    # re-stamped to a sibling partition and forwarded
                    # through the apiserver, so preemption and backoff
                    # wait until every partition has had a look (its
                    # new home stack's quota gate re-charges it there)
                    self._quota_refund(pi.pod, "spill")
                    self.pods_solved_on_device += 1
                    span.bump("spilled")
                    continue
            state = CycleState()
            state.write(SNAPSHOT_STATE_KEY, snapshot)
            if choice == NO_NODE:
                metrics.schedule_attempts.inc(result="unschedulable")
                span.bump("no_node")
                # per-node reason codes (SURVEY section 7 hardest-part d,
                # generic_scheduler.go:1033): nodes rejected by the
                # STATIC mask (label/taint/name/unschedulable mismatch)
                # can never be helped by preemption -- mark them
                # UnschedulableAndUnresolvable so
                # nodes_where_preemption_might_help prunes like the
                # reference instead of scanning every node
                statuses = {}
                # host-port pods: the static row folds NodePorts in, and
                # a port conflict IS resolvable by evicting the holder
                # (generic_scheduler.go:940 re-runs filters with victims
                # removed) -- leave statuses empty so preemption scans
                # every node instead of wrongly pruning them
                if (
                    mask_info is not None
                    and mask_info[0] is not None
                    and not pod_host_ports(pi.pod)
                ):
                    m_rows, m_idx = mask_info
                    ridx = int(m_idx[k])
                    statuses = statuses_by_row.get(ridx)
                    if statuses is None:
                        statuses = {
                            names[int(j)]:
                            Status.unschedulable_and_unresolvable(
                                "node(s) didn't match the static "
                                "feasibility mask"
                            )
                            for j in np.flatnonzero(
                                ~m_rows[ridx][:num_nodes]
                            )
                            # free (retired) slots are masked off too
                            # but are not nodes
                            if names[int(j)]
                        }
                        statuses_by_row[ridx] = statuses
                fit_err = FitError(pi.pod, live_nodes, statuses)
                self.pods_solved_on_device += 1
                # device-eligible failures preempt as ONE group (one
                # device round trip via Preemptor.preempt_batch); the
                # rest take the per-pod host path
                if self.preemptor is not None:
                    if cluster_anti is None:
                        from kubernetes_tpu.ops.affinity import (
                            cluster_has_required_anti_affinity,
                        )

                        cluster_anti = cluster_has_required_anti_affinity(
                            snapshot
                        )
                    if self.preemptor.device_eligible(
                        prof, pi.pod, cluster_anti=cluster_anti
                    ):
                        failed_group.append((pi, fit_err))
                        continue
                # populate PreFilter state so host preemption's victim
                # simulation can run the full filter pipeline (the
                # sequential path gets this from algorithm.schedule)
                prof.run_pre_filter_plugins(state, pi.pod)
                self.handle_fit_error(
                    prof, state, pi, fit_err, pod_scheduling_cycle
                )
                continue
            host = names[choice]
            assumed = self.reserve_assume_permit(
                prof, state, pi, host, pod_scheduling_cycle
            )
            self.pods_solved_on_device += 1
            if assumed is None:
                continue
            span.bump("placed")
            waiting = prof.get_waiting_pod(assumed.metadata.uid) is not None
            binder_extender = any(
                e.is_binder() and e.is_interested(assumed)
                for e in extenders
            )
            if (
                waiting
                or binder_extender
                or not prof.uses_default_binder_only()
                or self._bind_pool is None
            ):
                # per-pod binding cycle (wait-on-permit / custom binds)
                if self._bind_pool is not None:
                    with self._inflight_lock:
                        self._inflight_binds += 1
                    self._bind_pool.submit(
                        self._binding_cycle_safe, prof, state, pi, assumed,
                        host, pod_scheduling_cycle,
                    )
                elif waiting:
                    # synchronous binding + a Permit waiter: running the
                    # cycle inline would block THIS loop on
                    # wait_on_permit while the quorum it waits for is
                    # later in the same batch (deadlock until the permit
                    # timeout); defer until every pod is assumed
                    deferred.append(
                        (prof, state, pi, assumed, host)
                    )
                else:
                    self._binding_cycle(
                        prof, state, pi, assumed, host, pod_scheduling_cycle
                    )
            else:
                bulk.append((prof, state, pi, assumed, host))
        if failed_group:
            # a burst that overflows the cluster fails across SEVERAL
            # in-flight batches; preempting per batch pays the wave's
            # fixed costs (state pack, result round trip) repeatedly and
            # fragments the nomination replay. While more solver batches
            # are queued behind this one (FIFO committer), park the
            # failures; the LAST in-flight batch preempts the whole
            # accumulated group in one device wave.
            if not self._deferred_preempt:
                self._deferred_since = time.monotonic()
            self._deferred_preempt.extend(
                (prof, pi, fe, pod_scheduling_cycle)
                for pi, fe in failed_group
            )
        if self._deferred_preempt:
            with self._pending_cv:
                more_inflight = len(self._pending_q) > 1
            # the burst is still streaming when the activeQ holds more
            # pods or batches are in flight; hold the wave for them --
            # bounded by age and size so a trickle of unschedulable
            # pods cannot starve preemption
            burst_live = (
                more_inflight or self.queue.active_count() > 0
            )
            flush_anyway = (
                len(self._deferred_preempt) >= self.max_batch
                or time.monotonic() - self._deferred_since > 0.3
            )
            if not burst_live or flush_anyway:
                self._flush_deferred_preemptions()
        if bulk:
            with self._inflight_lock:
                self._inflight_binds += 1
            self._bind_pool.submit(
                self._bulk_binding_cycle_safe, bulk, pod_scheduling_cycle,
                snapshot, span, time.perf_counter(),
            )
        for prof_d, state_d, pi_d, assumed_d, host_d in deferred:
            self._binding_cycle(
                prof_d, state_d, pi_d, assumed_d, host_d,
                pod_scheduling_cycle,
            )

    def _permit_assumed(
        self, prof, bulk, permit_at, errs, snapshot, pod_scheduling_cycle,
        span,
    ) -> List[Tuple]:
        """Permit for the pods of a commit that were assumed in bulk and
        that a Permit plugin reads (gang members), in one call a plugin.
        ``permit_at`` indexes the commit's bulk-assumed pods, ``errs``
        their assume errors. Returns ``bulk`` less the pods that do not
        go on to the bulk bind: one that has to wait takes a binding
        cycle of its own, which waits for its gang at Permit; one that
        was refused gives its node back."""
        # ``bulk`` lost the pods whose assume failed: index it as the
        # commit's bulk-assumed pods were
        at = {}
        n = 0
        for i, err in enumerate(errs):
            if err is None:
                at[i] = n
                n += 1
        rows = [at[i] for i in permit_at if i in at]
        with flightrecorder.stage(
            "commit.permit", totals=self.stage_totals, batch=span.batch_id,
            pods=len(rows),
        ) as permit:
            waiting_before = len(prof.waiting_pods)
            statuses = prof.run_permit_plugins_batch(
                [bulk[r][3] for r in rows], [bulk[r][4] for r in rows]
            )
            drop = set()
            parked: dict = {}  # gang -> its members that have to wait
            waiting = rejected = 0
            for r, status in zip(rows, statuses):
                if status is None or status.is_success():
                    continue
                drop.add(r)
                _prof, _state, pi, assumed, host = bulk[r]
                state = CycleState()
                state.write(SNAPSHOT_STATE_KEY, snapshot)
                if status.code == StatusCode.WAIT:
                    waiting += 1
                    parked.setdefault(
                        assumed.metadata.labels.get(POD_GROUP_LABEL), []
                    ).append((prof, state, pi, assumed, host))
                    continue
                rejected += 1
                self._permit_refused(
                    prof, state, pi, assumed, host, status,
                    pod_scheduling_cycle,
                )
            for items in parked.values():
                # a thread a gang, not one of the bind pool's a pod: a
                # pool whose threads all wait at Permit binds nothing,
                # the gangs that were let through included
                with self._inflight_lock:
                    self._inflight_binds += 1
                threading.Thread(
                    target=self._permit_wait_cycle, name="permit-wait",
                    args=(prof, items, pod_scheduling_cycle, snapshot),
                    daemon=True,
                ).start()
            permit.set_metadata(
                groups=len({
                    bulk[r][3].metadata.labels.get(POD_GROUP_LABEL)
                    for r in rows
                }),
                waiting=waiting, rejected=rejected,
                # members of earlier batches that this batch's let go
                released=max(
                    0, waiting_before + waiting - len(prof.waiting_pods)
                ),
            )
        if not drop:
            return bulk
        return [item for r, item in enumerate(bulk) if r not in drop]

    def _permit_refused(
        self, prof, state, pi, assumed, host, status, pod_scheduling_cycle
    ) -> None:
        """Permit refused the assumed pod, at once or after a wait: it
        gives its node back and takes a failure record."""
        self._forget(assumed)
        prof.run_unreserve_plugins(state, assumed, host)
        self.record_scheduling_failure(
            prof, pi, status.message(),
            "Unschedulable" if status.is_unschedulable()
            else "SchedulerError", "", pod_scheduling_cycle,
        )

    def _permit_wait_cycle(
        self, prof, items, pod_scheduling_cycle, snapshot
    ) -> None:
        """The binding cycle of one gang's members that a commit parked
        at Permit together: wait for each (they are let go, rejected or
        timed out together), give back the nodes of those that were not
        let through, and bind the rest in one transaction."""
        try:
            ready = []
            for item in items:
                _prof, state, pi, assumed, host = item
                status = prof.wait_on_permit(assumed)
                if status is None or status.is_success():
                    ready.append(item)
                    continue
                self._permit_refused(
                    prof, state, pi, assumed, host, status,
                    pod_scheduling_cycle,
                )
            if ready:
                self._bulk_binding_cycle(
                    ready, pod_scheduling_cycle, snapshot
                )
        except SchedulerCrashed:
            self._simulate_crash()
        except Exception:
            logger.exception("permit wait cycle crashed")
        finally:
            with self._inflight_lock:
                self._inflight_binds -= 1
                self._inflight_lock.notify_all()

    def _flush_deferred_preemptions(self) -> None:
        """Run one preemption wave for every parked failure, grouped by
        profile (preempt_batch is profile-scoped), then requeue the pods
        with their nominations."""
        parked = self._deferred_preempt
        self._deferred_preempt = []
        # preempt_batch (and the host-side nomination fold inside the
        # device wave) require priority-DESC order; parked failures from
        # several batches can interleave priorities
        parked.sort(key=lambda t: (-t[1].pod.spec.priority, t[1].timestamp))
        by_prof: dict = {}
        for prof, pi, fe, cycle in parked:
            by_prof.setdefault(id(prof), (prof, []))[1].append(
                (pi, fe, cycle)
            )
        for prof, items in by_prof.values():
            victim_uids: Optional[List[str]] = []
            self.preemptor.last_wave = {}

            def wave_stats() -> dict:
                # the wave's numbers: on its span for the trace's
                # readers, and on the ring's mark beside it
                return dict(
                    self.preemptor.last_wave,  # searched, nodes, v_max, pack
                    nominated=sum(1 for n in nominated if n),
                    victims=len(victim_uids or ()),
                    tier=getattr(self.preemptor, "wave_solver_tier", ""),
                )

            try:
                with flightrecorder.stage(
                    "preempt_wave", totals=self.stage_totals,
                    pods=len(items),
                ) as wave:
                    nominated, victim_uids = self.preemptor.preempt_batch(
                        prof, [(pi.pod, fe) for pi, fe, _ in items]
                    )
                    wave.set_metadata(**wave_stats())
            except Exception:
                logger.exception("batched device preemption failed")
                nominated = [""] * len(items)
            evict_ok = victim_uids is not None
            flightrecorder.mark(
                "preemption_wave", pods=len(items), **wave_stats()
            )
            # wait (bounded) for the evictions to propagate from the
            # watch into the cache: the nominated pods retry WITHOUT
            # backoff below -- their failure was just resolved by this
            # wave's evictions, so backing off would only add the full
            # 1s initial-backoff round trip to every preemption -- and
            # an instant retry against a cache that still holds the
            # victims would waste a scheduling cycle
            if victim_uids:
                with flightrecorder.stage(
                    "victim_wait", totals=self.stage_totals,
                    victims=len(victim_uids),
                ) as waiting:
                    deadline = time.monotonic() + 0.5
                    pending = list(victim_uids)
                    while pending and time.monotonic() < deadline:
                        pending = [
                            u for u in pending
                            if self.cache.has_pod_uid(u)
                        ]
                        if pending:
                            time.sleep(0.002)
                    # victims the cache still held when the wait gave up
                    waiting.set_metadata(timed_out=len(pending))
            with flightrecorder.stage(
                "preempt_requeue", totals=self.stage_totals,
                pods=len(items),
            ) as requeue:
                # the wave's failure records as ONE hand-back: the
                # dispatcher wakes to all of them and retries them as
                # one batch, the informer takes their status echoes as
                # one frame
                records = [
                    (
                        pi, str(fe), node, cycle,
                        # no-backoff retry only when the wave actually
                        # evicted: otherwise the failure is persistent
                        # and the 1s backoff must damp it
                        bool(node) and evict_ok,
                    )
                    for (pi, fe, cycle), node in zip(items, nominated)
                    # stale parked record: the pod is in the cache,
                    # assumed or bound, so another record of it was
                    # placed during the deferral window (a real update
                    # re-added it: the queue ignores a status echo for
                    # a pod it does not hold); requeueing it would
                    # double-place a running pod
                    if not self.cache.has_pod_uid(pi.pod.metadata.uid)
                ]
                stats = self.record_scheduling_failures(
                    prof, records, "Unschedulable"
                )
                stats["stale"] += len(items) - len(records)
                requeue.set_metadata(**stats)
            if any(nominated):
                # once these preemptors bind, the cluster is full again:
                # refresh the victim pack so the NEXT contention wave
                # finds it (and its device upload) already warm
                self._prewarm_next_commit = True

    def _bind_bulk_with_retry(self, assumed_list, binding):
        """bind_assumed_bulk with retry-with-backoff around TRANSACTION
        failures (apiserver unavailable, injected conflict burst);
        ``binding`` is the bind's stage, which learns of the retries.
        Per-slot errors are the API's answer, not a transport failure --
        they return to the caller, whose per-slot handling already does
        forget + Unreserve + requeue. On terminal transaction failure
        every slot becomes an error so no pod is silently stranded
        assumed."""
        policy = self.ladder.config.retry
        coord = self.partition_coordinator
        binder = coord.identity if coord is not None else None
        attempt = 0
        while True:
            attempt += 1
            try:
                inj = get_injector()
                if inj is not None:
                    inj.raise_maybe(FaultPoint.BIND_CONFLICT)
                if binder is not None:
                    return self.client.bind_assumed_bulk(
                        assumed_list, binder=binder
                    )
                # keyword omitted off the partitioned path: test/bench
                # doubles that stub the client keep their old signature
                return self.client.bind_assumed_bulk(assumed_list)
            except Exception as e:  # noqa: BLE001 - transaction failure
                # max_attempts counts TOTAL attempts (ladder semantics)
                if attempt >= max(1, policy.max_attempts):
                    logger.exception(
                        "bulk bind failed terminally after %d attempts",
                        attempt,
                    )
                    return [(i, e) for i in range(len(assumed_list))]
                metrics.bind_retries.inc()
                binding.set_metadata(retries=attempt)
                self.ladder.config.sleep(
                    policy.backoff_for_attempt(attempt)
                )

    def _absorb_bind_conflict(
        self, prof, state, pi, assumed, host, err, pod_scheduling_cycle,
        span=None,
    ) -> None:
        """Absorb one typed bind conflict into the ledger: forget the
        optimistic reservation, release plugin state, then route by
        apiserver truth -- a pod that turned out ALREADY bound (a
        sibling stack won the race, or our own retried commit landed)
        is satisfied and records nothing; anything else requeues for
        another attempt. Exactly one disposition bucket per conflict:
        ``bind_conflicts_absorbed == conflict_requeues +
        conflict_stale_binds`` is a tier-1 invariant."""
        kind = getattr(err, "kind", "already-bound")
        self.bind_conflicts_absorbed += 1
        metrics.bind_conflicts_absorbed.inc(kind=kind)
        if span is not None:
            span.bump("conflicts")
        flightrecorder.mark(
            "bind_conflict", conflict=kind, pod=assumed.metadata.uid,
        )
        self._forget(assumed)
        prof.run_unreserve_plugins(state, assumed, host)
        live = None
        try:
            live = self.client.get_pod(
                assumed.metadata.namespace, assumed.metadata.name
            )
        except KeyError:
            pass  # deleted: nothing left to place
        except Exception:
            logger.exception(
                "conflict disposition read for %s", assumed.key()
            )
        if (
            live is not None
            and live.spec.node_name
            and live.metadata.uid == assumed.metadata.uid
        ):
            # satisfied elsewhere: the informer delivers the bound pod
            # into the cache; requeueing would double-schedule it
            self.conflict_stale_binds += 1
            return
        self.conflict_requeues += 1
        if live is None:
            return  # deleted while conflicting: requeue bucket, no add
        try:
            self.record_scheduling_failure(
                prof, pi, str(err), "BindConflict", "",
                pod_scheduling_cycle,
            )
            # a typed conflict is a TRANSIENT coordination race (fence
            # window, sibling overlap), not a cluster-state failure: no
            # future cluster event is guaranteed to wake the pod, so
            # parking it unschedulable could strand it for the 60s
            # flush. Route it to the backoff queue instead -- it retries
            # on the exponential backoff clock.
            self.queue.move_pods_to_active_or_backoff_queue(
                [pi], "BindConflictRetry"
            )
        except Exception:
            logger.exception("requeueing conflicted pod %s", pi.pod.key())

    def _bulk_binding_cycle_safe(
        self, items, pod_scheduling_cycle, snapshot=None,
        span=flightrecorder.NULL_SPAN, submitted: float = 0.0,
    ) -> None:
        """On a bind-pool thread; ``submitted`` is when the committer
        handed the bulk to the pool."""
        try:
            # how long the bulk waited for a thread of the pool
            waited = flightrecorder.handoff_wait(submitted)
            self._bulk_binding_cycle(
                items, pod_scheduling_cycle, snapshot, span, waited
            )
        except SchedulerCrashed:
            # simulated process death: halt with NO cleanup (the items
            # stay assumed-but-unbound; the next incarnation recovers)
            self._simulate_crash()
        except Exception:
            logger.exception("bulk binding cycle crashed")
        finally:
            with self._inflight_lock:
                self._inflight_binds -= 1
                self._inflight_lock.notify_all()

    def _bulk_binding_cycle(
        self, items, pod_scheduling_cycle, snapshot=None,
        span=flightrecorder.NULL_SPAN, waited=None,
    ) -> None:
        """One API transaction commits the batch (the pipelined bulk
        analogue of BindingREST.Create, storage.go:142). PreBind still
        runs per pod (skipped when every PreBind plugin declares itself
        a no-op for the pod); per-binding conflicts fail only their own
        pod.

        Plain pods arrive with ``state is None``: a CycleState is built
        only on the paths that read one (relevant pre_bind/post_bind
        plugins, unreserve on failure) -- the framework contract is
        per-pod state, and a fresh snapshot-seeded state is exactly what
        the eager path carried for these pods."""
        # the pre_bind gate must consider every profile in the bulk:
        # schedule_batch flushes on scheduler_name change today, but a
        # mixed bulk silently skipping another profile's PreBind plugins
        # would be a correctness bug, not a perf loss
        profs = {id(t[0]): t[0] for t in items}
        any_pre_bind = any(
            prof.relevance_entries("pre_bind") for prof in profs.values()
        )

        def mk_state():
            state = CycleState()
            state.write(SNAPSHOT_STATE_KEY, snapshot)
            return state

        if any_pre_bind:
            ready = []
            for prof, state, pi, assumed, host in items:
                if prof.plugins_relevant("pre_bind", assumed):
                    if state is None:
                        state = mk_state()
                    status = prof.run_pre_bind_plugins(state, assumed, host)
                else:
                    status = None
                if status is not None and not status.is_success():
                    self._forget(assumed)
                    prof.run_unreserve_plugins(state, assumed, host)
                    self.record_scheduling_failure(
                        prof, pi, status.message(), "SchedulerError", "",
                        pod_scheduling_cycle,
                    )
                    continue
                ready.append((prof, state, pi, assumed, host))
            if not ready:
                return
        else:
            ready = items
        inj = get_injector()
        if inj is not None:
            # the whole bulk is assumed but not yet bound -- the window
            # a process death strands (restart e2e drives this point)
            inj.crash_maybe(FaultPoint.CRASH_BETWEEN_ASSUME_AND_BIND)
        # commit-time lease fencing: verify ownership IMMEDIATELY before
        # the bulk transaction. A deposed leader (failed renews, standby
        # already holds the lease) must not commit placements computed
        # under its stale view -- abort and requeue; the pods are already
        # in the new leader's queue via its informers.
        if not self._fence_ok():
            metrics.fencing_aborts.inc()
            flightrecorder.mark("fencing_abort", pods=len(ready))
            logger.warning(
                "lease lost before bulk bind; fencing %d pod(s)",
                len(ready),
            )
            for prof, state, pi, assumed, host in ready:
                self._forget(assumed)
                prof.run_unreserve_plugins(
                    state if state is not None else mk_state(),
                    assumed, host,
                )
                self.record_scheduling_failure(
                    prof, pi, "lease lost before commit; fenced",
                    "SchedulerError", "", pod_scheduling_cycle,
                )
            return
        # partitioned commit fencing: the multi-lease holds_lease()
        # probe, run IMMEDIATELY before the bulk transaction. Pods on
        # partitions this stack no longer holds (handoff, lapsed lease
        # mid-dispatch) are absorbed as typed conflicts -- requeued,
        # never committed under a stale ownership view.
        coord = self.partition_coordinator
        if coord is not None and ready:
            fenced = coord.fence_hosts([t[4] for t in ready])
            if fenced:
                metrics.fencing_aborts.inc(len(fenced))
                flightrecorder.mark(
                    "fencing_abort", pods=len(fenced),
                    fence="partition",
                )
                kept = []
                fenced_pis = []
                for i, item in enumerate(ready):
                    if i not in fenced:
                        kept.append(item)
                        continue
                    prof_f, state_f, pi_f, assumed_f, host_f = item
                    self.bind_conflicts_absorbed += 1
                    self.conflict_requeues += 1
                    metrics.bind_conflicts_absorbed.inc(
                        kind="partition-fence"
                    )
                    if span is not None:
                        span.bump("conflicts")
                    flightrecorder.mark(
                        "bind_conflict", conflict="partition-fence",
                        pod=assumed_f.metadata.uid,
                    )
                    self._forget(assumed_f)
                    prof_f.run_unreserve_plugins(
                        state_f if state_f is not None else mk_state(),
                        assumed_f, host_f,
                    )
                    self.record_scheduling_failure(
                        prof_f, pi_f,
                        f"partition of node {host_f} not held at "
                        f"commit; fenced", "BindConflict", "",
                        pod_scheduling_cycle,
                    )
                    fenced_pis.append(pi_f)
                # fence conflicts are transient (a lease mid-handoff):
                # retry on the backoff clock instead of parking
                # unschedulable with no wake event in sight
                self.queue.move_pods_to_active_or_backoff_queue(
                    fenced_pis, "BindConflictRetry"
                )
                ready = kept
                if not ready:
                    return
        # one span per bulk bind, on the bind pool's thread: the API
        # transaction (``bind.api``), then the cache's finish_binding and
        # the events
        totals = self.stage_totals
        with flightrecorder.stage(
            "bind", span, totals, pods=len(ready), **(waited or {})
        ) as binding:
            assumed_list = [t[3] for t in ready]
            bind_timer = metrics.SinceTimer(metrics.binding_duration)
            with flightrecorder.stage(
                "bind.api", totals=totals, batch=span.batch_id
            ):
                errors = self._bind_bulk_with_retry(assumed_list, binding)
            bind_timer.observe()
            if errors:
                failed = dict(errors)
                bound = []
                for i, item in enumerate(ready):
                    err = failed.get(i)
                    if err is None:
                        bound.append(item)
                        continue
                    prof, state, pi, assumed, host = item
                    if isinstance(err, ApiConflict):
                        # typed conflict (already-bound / uid-mismatch /
                        # foreign-partition): the optimistic-concurrency
                        # answer of a multi-active control plane, absorbed
                        # through the requeue path -- never a scheduler
                        # error, never silently dropped
                        self._absorb_bind_conflict(
                            prof,
                            state if state is not None else mk_state(),
                            pi, assumed, host, err, pod_scheduling_cycle,
                            span=span,
                        )
                        continue
                    metrics.schedule_attempts.inc(result="error")
                    self._forget(assumed)
                    prof.run_unreserve_plugins(
                        state if state is not None else mk_state(),
                        assumed, host,
                    )
                    self.record_scheduling_failure(
                        prof, pi, str(err), "SchedulerError", "",
                        pod_scheduling_cycle,
                    )
                bound_assumed = [t[3] for t in bound]
            else:
                bound = ready
                bound_assumed = assumed_list
            if not bound:
                return
            self.cache.finish_binding_bulk(bound_assumed)
            if any(p.has_plugins("post_bind") for p in profs.values()):
                for prof, state, pi, assumed, host in bound:
                    if prof.has_plugins("post_bind"):
                        prof.run_post_bind_plugins(
                            state if state is not None else mk_state(),
                            assumed, host,
                        )
            # single-profile bulks take the batched-recorder fast path; a
            # mixed bulk passes recorder=None so _emit_bound's fallback
            # routes each event through the pod's own profile recorder
            recorder = bound[0][0].recorder if len(profs) == 1 else None
            self._emit_bound(recorder, bound)
        # arm the bind-ack ledger: each committed bind is pending until
        # its Running ack arrives over the watch (zombie-kubelet
        # detection -- scheduler/bindack.py)
        tracker = getattr(self, "bind_ack_tracker", None)
        if tracker is not None:
            tracker.track_bound([
                (
                    assumed.metadata.namespace, assumed.metadata.name,
                    assumed.metadata.uid, host,
                )
                for _, _, _, assumed, host in bound
            ])

    def _emit_bound(self, recorder, bound) -> None:
        if hasattr(recorder, "scheduled_many"):
            recorder.scheduled_many([a for _, _, _, a, _ in bound])
        elif hasattr(recorder, "eventf_many"):
            recorder.eventf_many(
                [
                    (
                        assumed, "Normal", "Scheduled",
                        f"Successfully assigned "
                        f"{assumed.metadata.namespace}/"
                        f"{assumed.metadata.name} to {host}",
                    )
                    for _, _, _, assumed, host in bound
                ]
            )
        else:
            for prof, state, pi, assumed, host in bound:
                prof.recorder.eventf(
                    assumed, "Normal", "Scheduled",
                    f"Successfully assigned "
                    f"{assumed.metadata.namespace}/"
                    f"{assumed.metadata.name} to {host}",
                )
        # batched success metrics (one lock hold per histogram)
        metrics.schedule_attempts.inc(len(bound), result="scheduled")
        metrics.pod_scheduling_attempts.observe_many(
            [pi.attempts for _, _, pi, _, _ in bound]
        )
        now = time.monotonic()
        durations = [
            max(0.0, now - pi.initial_attempt_timestamp)
            for _, _, pi, _, _ in bound
            if pi.initial_attempt_timestamp
        ]
        metrics.pod_scheduling_duration.observe_many(durations)
        # live pod-to-bind quantile sketch (P-squared): the same stream
        # the histogram sees, but queryable as p50/p99 gauges
        metrics.observe_pod_to_bind(durations)

    # -- warmup --------------------------------------------------------------

    def warmup(self) -> None:
        """Compile every solver variant for the current cluster shape so
        no measured batch pays JIT latency (the reference harness similarly
        schedules warm-up pods before b.ResetTimer,
        scheduler_perf_test.go:130).

        With the adaptive controller attached, its latency-rung solve
        pad is compiled too (basic path only -- constrained families on
        the latency rung are rare enough that the one-time compile can
        land on demand), so a controller rung switch never pays JIT
        latency inside a measured window."""
        snapshot = self.algorithm.snapshot
        self.cache.update_snapshot(snapshot)
        nt = self.tensor_cache.update(snapshot)
        if nt.capacity == 0:
            return
        extra = sorted(
            int(p) for p in self._warmup_pads
            if p and int(p) != self.max_batch
        )
        # every profile's resource score rule is a program of its own
        for config in self.solver_configs():
            for padded in [self.max_batch] + extra:
                self._warmup_at(
                    nt, padded, full=padded == self.max_batch,
                    config=config,
                )
        # seal the jit-cache watchdog: every signature compiled from
        # here on is a mid-run recompile (counted AND flight-recorded)
        self._jit_watch.seal()
        if self.autobatch is not None and hasattr(
            self.autobatch, "calibrate"
        ):
            # rung-ladder calibration (ROADMAP item-2a residual): the
            # controller drops candidate rungs whose measured solve
            # cost is not meaningfully cheaper than the rung above --
            # every surviving rung is already compiled by the loop
            # above, so a rung switch never pays JIT mid-run
            self.autobatch.calibrate(dict(self.pad_solve_seconds))

    def _warmup_at(
        self, nt, padded: int, full: bool, config: GreedyConfig
    ) -> None:
        n = nt.capacity
        r = nt.dims.num_dims
        if self.mesh is not None:
            self._warmup_mesh_packed(nt, padded, full, config)
            return
        common = jax.device_put((
            nt.allocatable, nt.requested, nt.non_zero_requested, nt.valid,
            np.zeros((padded, r), dtype=np.int32),
            np.zeros((padded, 2), dtype=np.int32),
            np.zeros((MASK_ROW_BUCKET, n), dtype=bool),
            np.zeros(padded, dtype=np.int32),
            np.zeros(padded, dtype=bool),
        ))
        if self.solver_mode == "sinkhorn":
            out = sinkhorn_assign(*common, config=config)
            jax.block_until_ready(out)
        out = greedy_assign_compact(*common, config=config)
        jax.block_until_ready(out)
        # compile every packed-upload layout the run loop can hit:
        # cold (static+carry ride the buffer), carry-refresh, and
        # steady-state carry-reuse
        base = [
            ("req", np.zeros((padded, r), dtype=np.int32)),
            ("nzr", np.zeros((padded, 2), dtype=np.int32)),
            ("midx", np.zeros(padded, dtype=np.int32)),
            ("active", np.zeros(padded, dtype=np.int32)),
            ("rows", np.zeros((MASK_ROW_BUCKET, n), dtype=np.int32)),
        ]
        static_pieces = [
            ("alloc", np.zeros((n, r), dtype=np.int32)),
            ("valid", np.zeros(n, dtype=np.int32)),
        ]
        carry_pieces = [
            ("req_state", np.zeros((n, r), dtype=np.int32)),
            ("nzr_state", np.zeros((n, 2), dtype=np.int32)),
        ]
        # steady-state dispatches always carry the (indices, rows)
        # delta-scatter slots (empty slots drop on device), so the
        # run loop hits exactly ONE steady signature per mode
        delta_slots = delta_slot_pieces(n, r)
        cold = solve_packed(
            base + static_pieces + carry_pieces, None, None, None, None,
            config=config, mode=self.solver_mode,
        )
        jax.block_until_ready(cold)
        _, _, _, alloc_d, valid_d = cold
        refresh = solve_packed(
            base + carry_pieces, alloc_d, valid_d, None, None,
            config=config, mode=self.solver_mode,
        )
        jax.block_until_ready(refresh)
        _, req_d, nzr_d, _, _ = refresh
        steady = solve_packed(
            base + delta_slots, alloc_d, valid_d, req_d, nzr_d,
            config=config, mode=self.solver_mode,
        )
        jax.block_until_ready(steady)
        # measured per-pad solve cost (post-compile): feeds the
        # AutoBatchController rung-ladder calibration, so the rungs
        # reflect what THIS cluster shape actually pays per pad.
        # Median of 3 -- a single sample absorbing a GC pause would
        # prune a rung on one run and keep it on the next, making
        # the ladder (and the controller trajectory) nondeterministic
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(solve_packed(
                base + delta_slots, alloc_d, valid_d, req_d, nzr_d,
                config=config, mode=self.solver_mode,
            ))
            samples.append(time.perf_counter() - t0)
        self.pad_solve_seconds[padded] = sorted(samples)[1]
        if not full:
            # extra (latency-rung) pads warm the basic path only
            return
        if n > CONSTRAINED_NODE_CAP:
            return  # constrained batches route to the host path
        # compile the packed constrained layouts the run loop can hit
        # (cold / carry-refresh / steady), mirroring the basic-path
        # variants above -- a first constrained batch must not pay a
        # multi-second XLA compile inside the measured window. A live
        # score family has one shape (ops/scoring.MAX_SCORE_SIGS static
        # rows), so these layouts are every one a live batch can take
        fam = _family_pieces(padded, n, _FAMILY_COMBOS[-1])
        c_cold = solve_packed(
            base + static_pieces + carry_pieces + fam,
            None, None, None, None,
            config=config, mode="constrained",
        )
        jax.block_until_ready(c_cold)
        c_refresh = solve_packed(
            base + carry_pieces + fam, alloc_d, valid_d, None, None,
            config=config, mode="constrained",
        )
        jax.block_until_ready(c_refresh)
        c_steady = solve_packed(
            base + delta_slots + fam, alloc_d, valid_d, req_d, nzr_d,
            config=config, mode="constrained",
        )
        jax.block_until_ready(c_steady)
        # family-combo layouts: warm the steady-carry variant of
        # every combo a measured phase can hit (the triple is
        # already warmed by c_cold/refresh/steady)
        for live in _FAMILY_COMBOS[:-1]:
            out_one = solve_packed(
                base + delta_slots + _family_pieces(padded, n, live),
                alloc_d, valid_d, req_d, nzr_d,
                config=config, mode="constrained",
            )
            jax.block_until_ready(out_one)
        self._pallas_canary(nt, padded, config)

    def _pallas_canary(
        self, nt, padded: int, config: GreedyConfig
    ) -> None:
        """Hold every Pallas specialization warm-up just compiled to the
        XLA scan, on a seeded non-trivial problem, before the run loop
        trusts it. That a kernel compiles does not make it right: on the
        v5e the spread+affinity specialization returned wrong placements,
        silently, at some node counts past 16k, until PR 21 repaired its
        state initialization (PERF.md); this guards the next such. With
        every family a no-op the constrained kernels must reproduce the
        basic scan exactly, so ONE reference solve -- on the
        ``greedy_assign_compact`` signature warm-up already compiled --
        judges them all, and each probe rides the steady signature its
        combo just warmed: no compile is added.

        A specialization that disagrees takes the Pallas tier out of the
        ladder for this (mode, shape) (``ops.assignment.distrust_pallas``)
        -- loudly -- and the XLA signatures warm in its place."""
        from kubernetes_tpu.ops.assignment import (
            distrust_pallas,
            pallas_candidate,
        )
        from kubernetes_tpu.tensors.node_tensor import PODS

        n, r = nt.capacity, nt.dims.num_dims
        modes = [
            m for m in (self.solver_mode, "constrained")
            if m != "sinkhorn"
            and pallas_candidate(m, padded, n, r, MASK_ROW_BUCKET)
        ]
        if not modes:
            return
        rng = np.random.default_rng(0)
        # a half-loaded copy of THIS cluster and a batch of mixed pods
        load = rng.random((n, 1)) * 0.5
        requested = (nt.allocatable * load).astype(np.int32)
        nzr_state = np.ascontiguousarray(requested[:, :2])
        req = np.zeros((padded, r), dtype=np.int32)
        req[:, 0] = rng.choice([100, 250, 500, 1000, 2000], padded)
        req[:, 1] = rng.choice([128, 256, 512, 1024], padded) * 1024
        req[:, PODS] = 1
        pod_nzr = np.ascontiguousarray(req[:, :2])
        rows = np.ones((MASK_ROW_BUCKET, n), dtype=bool)
        midx = np.zeros(padded, dtype=np.int32)
        active = np.ones(padded, dtype=bool)
        reference = np.asarray(greedy_assign_compact(
            nt.allocatable, requested, nzr_state, nt.valid,
            req, pod_nzr, rows, midx, active, config=config,
        )[0])
        state = jax.device_put(
            (nt.allocatable, nt.valid, requested, nzr_state)
        )
        pieces = [
            ("req", req), ("nzr", pod_nzr), ("midx", midx),
            ("active", active.astype(np.int32)),
            ("rows", rows.astype(np.int32)),
        ] + delta_slot_pieces(n, r)

        def agrees(mode: str, fam: list) -> bool:
            out = solve_packed(
                pieces + fam, *state,
                config=config, mode=mode,
            )
            return np.array_equal(np.asarray(out[0]), reference)

        for mode in modes:
            # per combo, the family pieces riding the buffer (the basic
            # modes have none)
            probes = {mode: []} if mode != "constrained" else {
                "+".join(live): _family_pieces(padded, n, live)
                for live in _FAMILY_COMBOS
            }
            bad = [
                name for name, fam in probes.items()
                if not agrees(mode, fam)
            ]
            if not bad:
                continue
            distrust_pallas(mode, padded, n)
            metrics.solver_fallbacks.inc(
                tier=TIER_XLA, reason="pallas_canary_mismatch"
            )
            flightrecorder.mark(
                "fallback", tier=TIER_XLA, reason="pallas_canary_mismatch",
            )
            logger.error(
                "Pallas %s kernel(s) %s disagree with the XLA scan at "
                "b=%d n=%d: the Pallas tier is off for this shape",
                mode, bad, padded, n,
            )
            # the XLA tier now serves these batches: warm ITS signatures
            for fam in probes.values():
                jax.block_until_ready(solve_packed(
                    pieces + fam, *state,
                    config=config, mode=mode,
                ))

    def _warmup_mesh_packed(
        self, nt, padded: int, full: bool, config: GreedyConfig
    ) -> None:
        """Sharded-twin warmup: compile every packed-upload layout the
        MESH run loop can hit -- cold (static+carry ride the replicated
        buffer, resharded once on device), carry-refresh, and
        steady-state delta-scatter -- for BOTH mesh tiers (the
        shard_map'd Pallas tier the ladder attempts first when
        mesh_pallas_candidate holds, and the GSPMD XLA twin the
        breakers fall back to), plus the constrained layouts.
        Absent families ride as real zero tensors on the mesh
        (fam_pieces), so the constrained dispatch has exactly TWO
        signatures per (state-variant, mesh shape), the score family
        absent and live: the multichip dryrun's zero-recompile probe
        (mesh_packed_cache_size) pins that the steady phase never
        compiles past this set -- the probe covers the Pallas-tier
        signatures too, since both tiers share the one jitted mesh
        solver. The steady solve is re-run timed
        post-compile (pad_solve_seconds, on the tier dispatch will
        actually use) for the AutoBatchController rung ladder."""
        from kubernetes_tpu.ops.assignment import mesh_pallas_candidate

        n = nt.capacity
        r = nt.dims.num_dims
        base = [
            ("req", np.zeros((padded, r), dtype=np.int32)),
            ("nzr", np.zeros((padded, 2), dtype=np.int32)),
            ("midx", np.zeros(padded, dtype=np.int32)),
            ("active", np.zeros(padded, dtype=np.int32)),
            ("rows", mask_rows_upload(
                np.zeros((MASK_ROW_BUCKET, n), dtype=bool), self.mesh
            )),
        ]
        static_pieces = [
            ("alloc", np.zeros((n, r), dtype=np.int32)),
            ("valid", np.zeros(n, dtype=np.int32)),
        ]
        carry_pieces = [
            ("req_state", np.zeros((n, r), dtype=np.int32)),
            ("nzr_state", np.zeros((n, 2), dtype=np.int32)),
        ]
        delta_slots = delta_slot_pieces(n, r)
        tiers = [False]  # the GSPMD twin always warms (breaker target)
        if mesh_pallas_candidate(self.solver_mode, n, self.mesh):
            tiers.insert(0, True)
        alloc_d = valid_d = req_d = nzr_d = None
        for allow_pallas in tiers:
            kw = dict(
                config=config, mode=self.solver_mode,
                mesh=self.mesh, allow_pallas=allow_pallas,
            )
            cold = solve_packed(
                base + static_pieces + carry_pieces,
                None, None, None, None, **kw,
            )
            jax.block_until_ready(cold)
            _, _, _, alloc_d, valid_d = cold
            refresh = solve_packed(
                base + carry_pieces, alloc_d, valid_d, None, None, **kw
            )
            jax.block_until_ready(refresh)
            _, req_d, nzr_d, _, _ = refresh
            steady = solve_packed(
                base + delta_slots, alloc_d, valid_d, req_d, nzr_d, **kw
            )
            jax.block_until_ready(steady)
            if allow_pallas is not tiers[0]:
                continue
            # median of 3 (see _warmup_at) on the FIRST-attempt tier:
            # one noisy sample must not make the calibrated ladder
            # nondeterministic run-to-run
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(solve_packed(
                    base + delta_slots, alloc_d, valid_d, req_d, nzr_d,
                    **kw,
                ))
                samples.append(time.perf_counter() - t0)
            self.pad_solve_seconds[padded] = sorted(samples)[1]
        if not full or n > CONSTRAINED_NODE_CAP:
            # latency rungs warm the basic path only; over the
            # constrained node cap every constrained batch routes host
            return
        ckw = dict(
            config=config, mode="constrained", mesh=self.mesh,
        )
        # one signature a state variant where the score family is absent
        # (its placeholders' few static rows, as real arrays) and one
        # where it is live (a live batch's rows, ops/scoring.py)
        for live in ((), ("sc",)):
            fam = _family_pieces(padded, n, live, const_absent=False)
            jax.block_until_ready(solve_packed(
                base + static_pieces + carry_pieces + fam,
                None, None, None, None, **ckw,
            ))
            jax.block_until_ready(solve_packed(
                base + carry_pieces + fam, alloc_d, valid_d, None, None,
                **ckw,
            ))
            jax.block_until_ready(solve_packed(
                base + delta_slots + fam, alloc_d, valid_d, req_d, nzr_d,
                **ckw,
            ))

    # -- loop ---------------------------------------------------------------

    def run(self) -> None:
        from kubernetes_tpu.utils.gc_tuning import GCBatchGuard

        self.queue.run()
        self._gc_guard = GCBatchGuard(self.stage_totals)
        try:
            while not self._stop.is_set():
                # in-flight batches land on the committer thread, so the
                # dispatcher can always block for the next arrivals
                self.schedule_batch(timeout=0.5, pipeline=True)
            self._drain_pending()
            self._stop_committer()
        finally:
            guard, self._gc_guard = self._gc_guard, None
            guard.close()
