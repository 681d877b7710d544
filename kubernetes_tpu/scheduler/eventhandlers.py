"""Informer event handlers bridging cluster mutations into cache + queue.

Reference: /root/reference/pkg/scheduler/eventhandlers.go:350
(addAllEventHandlers): assigned pods feed the cache, unassigned pods feed
the queue, node/PV/PVC/Service events wake unschedulable pods with typed
event strings.
"""

from __future__ import annotations

import logging
from typing import TYPE_CHECKING, Optional

from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.client.informer import InformerFactory, ResourceEventHandler
from kubernetes_tpu.queue import events
from kubernetes_tpu.utils import flightrecorder

if TYPE_CHECKING:
    from kubernetes_tpu.scheduler.scheduler import Scheduler

logger = logging.getLogger(__name__)


def _assigned(pod: Pod) -> bool:
    return bool(pod.spec.node_name)


def _responsible_for_pod(sched: "Scheduler", pod: Pod) -> bool:
    """Queue-side responsibility: the pod names one of our profiles
    AND, in a partitioned stack, its home partition (uid hash, or the
    spill re-stamp) is held here -- each pending pod has exactly ONE
    home stack, so N active stacks never race over fresh work. Read
    dynamically: ownership changes at takeover/handoff."""
    if pod.spec.scheduler_name not in sched.profiles:
        return False
    coord = sched.partition_coordinator
    return coord is None or coord.wants_pod(pod)


def _cache_side(sched: "Scheduler", pod: Pod) -> bool:
    """Cache-side responsibility: bound, and bound to a node whose
    partition this stack holds (a partitioned cache carries ONLY its
    slice of the node space -- that division is the scale-out: each
    stack's tensors are N/P rows)."""
    if not pod.spec.node_name:
        return False
    coord = sched.partition_coordinator
    return coord is None or coord.owns_node(pod.spec.node_name)


def add_all_event_handlers(
    sched: "Scheduler", informer_factory: InformerFactory
) -> None:
    pods = informer_factory.pods()
    nodes = informer_factory.nodes()

    # admission-classifier hooks (BatchScheduler only; the sequential
    # scheduler has no device path to classify for): pending pods are
    # classified ON INGEST so pop -> dispatch reads a precomputed field,
    # bound pods get their attachable-volume counts resolved before the
    # cache accounts them, and storage-object events bump the
    # volume-topology generation that invalidates cached classifications
    classify = getattr(sched, "classify_pod", None)
    classify_bulk = getattr(sched, "classify_pods_bulk", None)
    attach_counts = getattr(sched, "attach_volume_counts", None)
    bump_volume_gen = getattr(sched, "bump_volume_topology_gen", None)
    # tenant dominant-share tracker hooks (scheduler/tenancy.py): the
    # cache-side frames deliver every bound pod exactly once -- our
    # commits, sibling-stack commits, and the startup relist alike --
    # so the DRF shares stay honest without a second watch
    note_bound = getattr(sched, "note_pods_bound", None)
    note_unbound = getattr(sched, "note_pods_unbound", None)
    # multi-active residual 7(a): bound pods on FOREIGN-partition nodes
    # never enter this stack's cache, but their bind echoes must still
    # fold into the DRF shares so dominant shares are cluster-wide (the
    # tracker dedups per uid, so re-echoes are free)
    note_node_cap = getattr(sched, "note_node_capacity", None)
    note_node_gone = getattr(sched, "note_node_gone", None)
    # the scheduler's stage totals ride on the handlers of the two hot
    # kinds: their informers time each frame (store update, classify,
    # cache add, queue add) as the scheduler's ``ingest``
    stage_totals = getattr(sched, "stage_totals", None)

    def _note_foreign_bound(pod: Pod) -> None:
        if note_bound is not None:
            note_bound([pod])

    def _note_foreign_unbound(pod: Pod) -> None:
        if note_unbound is not None:
            note_unbound([pod])
    # bind-ack tracker hooks (scheduler/bindack.py): cache-side frames
    # carry the pod-Running ack transition and the gone signals the
    # ledger consumes -- same watch, no second stream
    ack_tracker = getattr(sched, "bind_ack_tracker", None)

    def _classify_safe(pod: Pod) -> None:
        try:
            classify(pod)
        except Exception:
            logger.exception("classifying pod %s", pod.key())

    def _recovered_quarantined(pod: Pod) -> bool:
        """A relisted PENDING pod still carrying the persisted
        PodQuarantined condition (ROADMAP item 6c): it must re-park at
        ingest, not re-enter batches. Freshly created pods have no
        conditions, so the fast path is one empty-list check."""
        conds = pod.status.conditions
        if not conds:
            return False
        from kubernetes_tpu.robustness.containment import (
            QUARANTINE_CONDITION,
        )

        return any(
            c.type == QUARANTINE_CONDITION and c.status == "True"
            for c in conds
        )

    # scheduled pods -> cache (eventhandlers.go:356)
    def add_pod_to_cache(pod: Pod) -> None:
        if attach_counts is not None:
            attach_counts(pod)
        try:
            sched.cache.add_pod(pod)
        except Exception:
            logger.exception("add pod %s to cache", pod.key())
        if note_bound is not None:
            note_bound([pod])
        if ack_tracker is not None:
            ack_tracker.observe_pod(None, pod)
        # Targeted wake: only parked pods whose affinity terms match the
        # added pod can benefit (eventhandlers.go:90 assignedPodAdded ->
        # scheduling_queue.go:508). During a 10k-burst the cache sees one
        # add per bound pod; a move-all here is O(pods x unschedulable).
        sched.queue.assigned_pod_added(pod)

    def update_pod_in_cache(old: Pod, new: Pod) -> None:
        if attach_counts is not None:
            attach_counts(new)
        try:
            sched.cache.update_pod(old, new)
        except KeyError:
            sched.cache.add_pod(new)
        except Exception:
            logger.exception("update pod %s in cache", new.key())
        if ack_tracker is not None:
            ack_tracker.observe_pod(old, new)
        sched.queue.assigned_pod_updated(new)

    def delete_pod_from_cache(pod: Pod) -> None:
        try:
            sched.cache.remove_pod(pod)
        except Exception:
            logger.exception("remove pod %s from cache", pod.key())
        if note_unbound is not None:
            note_unbound([pod])
        if ack_tracker is not None:
            ack_tracker.observe_gone(pod.metadata.uid)
        sched.queue.move_all_to_active_or_backoff_queue(events.AssignedPodDelete)

    # unscheduled pods owned by one of our profiles -> queue (:381)
    def add_pod_to_queue(pod: Pod) -> None:
        if _recovered_quarantined(pod):
            sched.queue.park_quarantined_recovered(pod)
            return
        if classify is not None:
            _classify_safe(pod)
        sched.queue.add(pod)
        # a new gang member can unblock siblings rejected by the
        # coscheduling fail-fast (total < minMember) -- wake exactly them
        from kubernetes_tpu.api.types import POD_GROUP_LABEL

        group = pod.metadata.labels.get(POD_GROUP_LABEL)
        if group:
            siblings = [
                pi
                for pi in sched.queue.unschedulable_pods()
                if pi.pod.metadata.labels.get(POD_GROUP_LABEL) == group
            ]
            # run even with no parked sibling: the move_request_cycle bump
            # covers siblings mid-attempt right now (lost-wakeup guard)
            sched.queue.move_pods_to_active_or_backoff_queue(
                siblings, "PodGroupMemberAdd"
            )

    def update_pod_in_queue(old: Pod, new: Pod) -> None:
        # the update arrives as a NEW object with no admission memo; an
        # eager classification here keeps the pop loop a pure memo read
        if classify is not None:
            _classify_safe(new)
        sched.queue.update(old, new)

    def delete_pod_from_queue(pod: Pod) -> None:
        sched.queue.delete(pod)
        for fw in sched.profiles.values():
            fw.reject_waiting_pod(pod.metadata.uid)

    # -- the combined whole-frame bridge -------------------------------------
    # ONE pass over each watch frame feeds BOTH sides (cache for assigned
    # pods, queue for pending pods) -- the reference registers two
    # filtered handlers (eventhandlers.go:356,:381); here the frame loop
    # itself was the hot cost during a 10k burst (every event iterated
    # twice, with the assigned-filter evaluated in both), so the two
    # bridges share one loop. Run coalescing per side is preserved:
    # consecutive cache adds confirm in one bulk add + one wakeup batch,
    # cache deletes in one bulk remove + ONE queue move, queue adds/
    # leaves in one bulk op; any opposing transition flushes that side
    # first so per-pod event order within the frame holds. Cross-side
    # order matches the old two-handler order (cache side flushed before
    # queue side at every boundary and at frame end).

    def combined_pod_update(old, new) -> None:
        """Per-event fallback (non-batch dispatch): both sides' filter-
        transition semantics (FilteringResourceEventHandler). Cache
        membership follows ``_cache_side`` (bound AND on an owned node);
        queue membership still excludes ANY bound pod -- a pod bound
        into a foreign partition is simply not ours on either side."""
        new_a = _cache_side(sched, new)
        old_a = old is not None and _cache_side(sched, old)
        if old_a and new_a:
            update_pod_in_cache(old, new)
        elif not old_a and new_a:
            add_pod_to_cache(new)
        elif old_a and not new_a:
            delete_pod_from_cache(old)
        elif _assigned(new):
            # bound into a foreign partition: fold into the DRF shares
            # (uid-deduped) even though the cache never sees it
            _note_foreign_bound(new)
        elif old is not None and _assigned(old) and not old_a:
            # a foreign-bound pod released: retire its share
            _note_foreign_unbound(old)
        new_q = not _assigned(new) and _responsible_for_pod(sched, new)
        old_q = (
            old is not None
            and not _assigned(old)
            and _responsible_for_pod(sched, old)
        )
        if old_q and new_q:
            update_pod_in_queue(old, new)
        elif not old_q and new_q:
            add_pod_to_queue(new)
        elif old_q and not new_q:
            delete_pod_from_queue(old)

    def combined_pod_add(pod) -> None:
        if _assigned(pod):
            if _cache_side(sched, pod):
                add_pod_to_cache(pod)
            else:
                _note_foreign_bound(pod)
        elif _responsible_for_pod(sched, pod):
            add_pod_to_queue(pod)

    def combined_pod_delete(pod) -> None:
        if _assigned(pod):
            if _cache_side(sched, pod):
                delete_pod_from_cache(pod)
            else:
                _note_foreign_unbound(pod)
        elif _responsible_for_pod(sched, pod):
            delete_pod_from_queue(pod)

    def pods_batch(frame) -> Optional[dict]:
        """One classification pass builds per-side ordered op-run lists;
        execution then replays the WHOLE cache side before the queue side
        -- exactly the old two-filtered-handler order (assigned handler
        saw the full frame first), with consecutive same-kind ops merged
        into bulk runs. A mixed create/bind-echo frame thus still commits
        as one cache add_pods + a few queue add_many/delete_many calls,
        and per-pod event order holds within each side because run order
        follows event order."""
        from kubernetes_tpu.api.types import POD_GROUP_LABEL

        profiles = sched.profiles
        cache_runs = []  # ("adds"|"dels", [pods]) | ("update", (old,new))
        queue_runs = []  # ("adds"|"dels", [pods]) | per-event kinds
        # what the frame's work was for, on its ingest span: pending
        # pods that entered the queue, MODIFIED events that confirm a
        # bind on a node of ours, DELETED events (local increments; the
        # stats themselves are built only under a profiler session)
        adds = bind_echoes = deletes = 0

        for etype, old, new in frame:
            # cache membership = bound AND (partitioned) on an owned
            # node; queue membership still excludes ANY bound pod, and
            # queue LEAVES stay keyed on the profile alone (deleting an
            # absent key is free; skipping a stale one is not)
            new_a = _cache_side(sched, new)
            new_bound = bool(new.spec.node_name)
            if etype == "MODIFIED":
                old_a = old is not None and _cache_side(sched, old)
                if new_a:
                    if old_a:
                        cache_runs.append(("update", (old, new)))
                    else:
                        # bind echo: cache confirm + queue leave
                        bind_echoes += 1
                        if cache_runs and cache_runs[-1][0] == "adds":
                            cache_runs[-1][1].append(new)
                        else:
                            cache_runs.append(("adds", [new]))
                        if old is not None and (
                            old.spec.scheduler_name in profiles
                        ):
                            if queue_runs and queue_runs[-1][0] == "dels":
                                queue_runs[-1][1].append(old)
                            else:
                                queue_runs.append(("dels", [old]))
                elif old_a:
                    if cache_runs and cache_runs[-1][0] == "dels":
                        cache_runs[-1][1].append(old)
                    else:
                        cache_runs.append(("dels", [old]))
                    if not new_bound and _responsible_for_pod(sched, new):
                        queue_runs.append(("add_one", new))
                elif new_bound:
                    # bound into a foreign partition: not ours on either
                    # side, but a pod WE queued must still leave the
                    # queue (the sibling stack won it) -- and its bind
                    # echo still folds into the cluster-wide DRF shares
                    _note_foreign_bound(new)
                    if old is not None and (
                        old.spec.scheduler_name in profiles
                    ):
                        if queue_runs and queue_runs[-1][0] == "dels":
                            queue_runs[-1][1].append(old)
                        else:
                            queue_runs.append(("dels", [old]))
                else:
                    if old is not None and bool(old.spec.node_name):
                        # foreign-bound pod released back to pending:
                        # retire its cluster-wide share
                        _note_foreign_unbound(old)
                    old_q = old is not None and _responsible_for_pod(
                        sched, old
                    )
                    new_q = _responsible_for_pod(sched, new)
                    if old_q and new_q:
                        queue_runs.append(("update", (old, new)))
                    elif not old_q and new_q:
                        queue_runs.append(("add_one", new))
                    elif old_q or (
                        old is not None
                        and old.spec.scheduler_name in profiles
                        and not new_q
                    ):
                        # covers the partition handoff: a pod whose home
                        # partition moved (spill re-stamp) leaves this
                        # stack's queue even though neither snapshot is
                        # "responsible" under current ownership
                        if queue_runs and queue_runs[-1][0] == "dels":
                            queue_runs[-1][1].append(old)
                        else:
                            queue_runs.append(("dels", [old]))
            elif etype == "ADDED":
                if new_a:
                    if cache_runs and cache_runs[-1][0] == "adds":
                        cache_runs[-1][1].append(new)
                    else:
                        cache_runs.append(("adds", [new]))
                elif new_bound:
                    # foreign-partition bound pod (relist or sibling
                    # commit): shares only, never cache or queue
                    _note_foreign_bound(new)
                elif _responsible_for_pod(sched, new):
                    if new.metadata.labels.get(POD_GROUP_LABEL):
                        # gang sibling wakeups take the per-event path
                        queue_runs.append(("add_one", new))
                    elif queue_runs and queue_runs[-1][0] == "adds":
                        queue_runs[-1][1].append(new)
                    else:
                        queue_runs.append(("adds", [new]))
            elif etype == "DELETED":
                deletes += 1
                if new_a:
                    if cache_runs and cache_runs[-1][0] == "dels":
                        cache_runs[-1][1].append(new)
                    else:
                        cache_runs.append(("dels", [new]))
                elif new_bound:
                    _note_foreign_unbound(new)
                elif new.spec.scheduler_name in profiles:
                    queue_runs.append(("del_one", new))

        echoes_before = sched.queue.echoes_ignored
        # cache phase (whole frame), then queue phase
        for kind, payload in cache_runs:
            if kind == "adds":
                if attach_counts is not None:
                    for pod in payload:
                        attach_counts(pod)
                try:
                    sched.cache.add_pods(payload)
                except Exception:
                    logger.exception("bulk add pods to cache")
                if note_bound is not None:
                    note_bound(payload)
                if ack_tracker is not None:
                    for pod in payload:
                        ack_tracker.observe_pod(None, pod)
                sched.queue.assigned_pods_added_many(payload)
            elif kind == "dels":
                # one bulk cache remove + ONE queue move per run (a
                # preemption wave deletes hundreds of victims per frame)
                try:
                    sched.cache.remove_pods(payload)
                except Exception:
                    logger.exception("bulk remove pods from cache")
                if note_unbound is not None:
                    note_unbound(payload)
                if ack_tracker is not None:
                    for pod in payload:
                        ack_tracker.observe_gone(pod.metadata.uid)
                sched.queue.move_all_to_active_or_backoff_queue(
                    events.AssignedPodDelete
                )
            else:
                update_pod_in_cache(*payload)
        for kind, payload in queue_runs:
            if kind == "adds":
                # relisted pods still carrying the persisted
                # PodQuarantined condition re-park instead of re-entering
                # batches (conditions are empty on fresh creates, so the
                # burst path pays one list-truthiness check per pod)
                if any(p.status.conditions for p in payload):
                    rest: list = []
                    for p in payload:
                        if _recovered_quarantined(p):
                            sched.queue.park_quarantined_recovered(p)
                        else:
                            rest.append(p)
                    payload = rest
                    if not payload:
                        continue
                # one ingest pass: plain pods stamp their full record in
                # C (native ingest_stamp), the rest classify per pod
                if classify_bulk is not None:
                    classify_bulk(payload)
                elif classify is not None:
                    for pod in payload:
                        _classify_safe(pod)
                sched.queue.add_many(payload)
                adds += len(payload)
            elif kind == "dels":
                sched.queue.delete_many(payload)
                # bound-pod echoes almost never have Permit waiters --
                # skip the per-pod reject loop when no profile holds any
                if any(fw.waiting_pods for fw in profiles.values()):
                    for pod in payload:
                        for fw in profiles.values():
                            fw.reject_waiting_pod(pod.metadata.uid)
            elif kind == "add_one":
                add_pod_to_queue(payload)
                adds += 1
            elif kind == "update":
                update_pod_in_queue(*payload)
            else:
                delete_pod_from_queue(payload)
        if not flightrecorder.tracing():
            return None  # stats of a span that only a session builds
        # with the status echoes of pods the scheduler holds, which the
        # queue ignored: stats of the frame's ingest span, each when
        # there were any
        stats = {
            "adds": adds, "bind_echoes": bind_echoes, "deletes": deletes,
            "echoes_ignored": sched.queue.echoes_ignored - echoes_before,
        }
        return {k: v for k, v in stats.items() if v} or None

    pods.add_event_handler(
        ResourceEventHandler(
            on_add=combined_pod_add,
            on_update=combined_pod_update,
            on_delete=combined_pod_delete,
            on_batch=pods_batch,
            stage_totals=stage_totals,
        )
    )

    # nodes -> cache + queue wakeups (:406). A partitioned stack's cache
    # carries ONLY its slice of the node space (owns_node_obj also
    # teaches the coordinator zone->partition mappings in zone-aligned
    # mode); partition acquire/release syncs membership out of band.
    def _node_ours(node: Node) -> bool:
        coord = sched.partition_coordinator
        return coord is None or coord.owns_node_obj(node)

    # Each handler is one ``sched/node_event`` span inside its frame's
    # ``sched/ingest``: ``kind``, whether the node-spec epoch moved
    # (``spec_changed``: a kubelet's status report must not) and the pods
    # the event moved out of the unschedulable map (``woke``). A node
    # that is not this stack's carries the kind alone.
    def node_event(kind: str):
        return flightrecorder.stage(
            "node_event", totals=stage_totals, kind=kind
        )

    def handled(timed, moved, woke: int) -> None:
        timed.set_metadata(spec_changed=int(bool(moved)), woke=woke)

    def add_node(node: Node) -> None:
        with node_event("add") as timed:
            # capacity feed runs BEFORE the ownership gate: the DRF
            # denominator is the whole cluster, not this stack's slice
            if note_node_cap is not None:
                note_node_cap(node)
            if not _node_ours(node):
                return
            moved = sched.cache.add_node(node)
            woke = sched.queue.move_all_to_active_or_backoff_queue(
                events.NodeAdd
            )
            handled(timed, moved, woke)

    def update_node(old: Node, new: Node) -> None:
        with node_event("update") as timed:
            if note_node_cap is not None:
                note_node_cap(new)
            if not _node_ours(new):
                return
            moved = sched.cache.update_node(old, new)
            event = _node_scheduling_properties_changed(old, new)
            woke = 0
            if event:
                woke = sched.queue.move_all_to_active_or_backoff_queue(event)
            handled(timed, moved, woke)

    def delete_node(node: Node) -> None:
        with node_event("delete") as timed:
            moved, woke = _delete_node(node)
            if moved is not None:
                handled(timed, moved, woke)

    def _delete_node(node: Node):
        """(whether the epoch moved, pods woken); (None, 0) for a node
        that is not this stack's."""
        if note_node_gone is not None:
            note_node_gone(node.metadata.name)
        coord = sched.partition_coordinator
        if coord is not None and not coord.owns_node(node.metadata.name):
            return None, 0
        moved = bool(sched.cache.remove_node(node))
        woke = 0
        # a nomination pointing at the dead node is a reservation on
        # capacity that no longer exists: clear it (or the next batch's
        # nominee overlay and the host oracle keep honoring a phantom
        # claim) and RE-ARM the nominees -- move them to active so they
        # re-plan now instead of waiting out a backoff for a node that
        # will never come back under that incarnation
        clear = getattr(sched.queue, "clear_nominations_for_node", None)
        if clear is not None:
            orphaned = clear(node.metadata.name)
            if orphaned:
                # also clear the API-side status: the queue map
                # re-installs a nomination from
                # status.nominated_node_name on every re-add/update
                # echo, which would resurrect the phantom reservation
                # the moment any update of the pod lands (and suppress
                # scheduling onto a same-name cold replacement node).
                # The write's own echo may re-add a pod parked for a
                # deferred wave to the activeQ early -- that is the
                # standard status-write wake, absorbed by the existing
                # requeue paths (add_unschedulable_if_not_present's
                # KeyError and the flush's bound-pod skip), and waking
                # the nominee to re-plan is exactly the point here
                client = getattr(sched, "client", None)
                dead = node.metadata.name

                def _clear_nom(q: Pod) -> None:
                    # conditional on the AUTHORITATIVE object (the map's
                    # pod copy can lag its own status-write echo across
                    # informer kinds), and only for the dead node -- a
                    # newer nomination elsewhere must stand
                    if q.status.nominated_node_name == dead:
                        q.status.nominated_node_name = ""

                for p in orphaned:
                    if client is None:
                        continue
                    try:
                        client.update_pod_status(
                            p.metadata.namespace, p.metadata.name,
                            _clear_nom,
                        )
                    except KeyError:
                        pass  # pod gone: nothing to resurrect from
                    except Exception:
                        logger.exception(
                            "clearing nominatedNodeName for %s", p.key()
                        )
                woke = sched.queue.move_all_to_active_or_backoff_queue(
                    events.NodeDelete
                )
        return moved, woke

    nodes.add_event_handler(
        ResourceEventHandler(
            on_add=add_node, on_update=update_node, on_delete=delete_node,
            stage_totals=stage_totals,
        )
    )

    # storage + service wakeups (eventhandlers.go:415-460): each mutation
    # can unblock pods parked on the corresponding filter family, so move
    # the unschedulable queue with the matching typed event
    def _wake(event):
        def on_one(*_args) -> None:
            sched.queue.move_all_to_active_or_backoff_queue(event)

        return on_one

    def _wake_volume(event):
        """Storage-object mutations additionally invalidate cached
        admission classifications: a PVC binding landing mid-queue must
        re-classify the pod at pop time, not dispatch it under the
        stale class."""
        def on_one(*_args) -> None:
            if bump_volume_gen is not None:
                bump_volume_gen()
            sched.queue.move_all_to_active_or_backoff_queue(event)

        return on_one

    informer_factory.persistent_volumes().add_event_handler(
        ResourceEventHandler(
            on_add=_wake_volume(events.PvAdd),
            on_update=_wake_volume(events.PvUpdate),
            # deletes can't make parked pods schedulable, but they MUST
            # invalidate cached device-ok classifications: a pod whose
            # PV vanished mid-queue has to re-classify to the host
            # oracle instead of solving against the stale resolution
            on_delete=_wake_volume(events.PvUpdate),
        )
    )
    informer_factory.persistent_volume_claims().add_event_handler(
        ResourceEventHandler(
            on_add=_wake_volume(events.PvcAdd),
            on_update=_wake_volume(events.PvcUpdate),
            on_delete=_wake_volume(events.PvcUpdate),
        )
    )
    informer_factory.services().add_event_handler(
        ResourceEventHandler(
            on_add=_wake(events.ServiceAdd),
            on_update=_wake(events.ServiceUpdate),
            on_delete=_wake(events.ServiceDelete),
        )
    )
    informer_factory.storage_classes().add_event_handler(
        ResourceEventHandler(on_add=_wake_volume(events.StorageClassAdd))
    )

    # CSINode -> cache attach limits (nodevolumelimits/csi.go reads
    # CSINode allocatable; the cache mirrors it onto NodeInfo so the
    # tensor packer fills the volume-limit columns) + wakeups
    def csi_node_upsert(event):
        def on_one(*args) -> None:
            obj = args[-1]
            try:
                sched.cache.add_csi_node(obj)
            except Exception:
                logger.exception("applying CSINode %s", obj.key())
            if bump_volume_gen is not None:
                bump_volume_gen()
            sched.queue.move_all_to_active_or_backoff_queue(event)

        return on_one

    def csi_node_delete(obj) -> None:
        try:
            sched.cache.remove_csi_node(obj)
        except Exception:
            logger.exception("removing CSINode %s", obj.key())
        if bump_volume_gen is not None:
            bump_volume_gen()
        sched.queue.move_all_to_active_or_backoff_queue(
            events.CSINodeUpdate
        )

    informer_factory.csi_nodes().add_event_handler(
        ResourceEventHandler(
            on_add=csi_node_upsert(events.CSINodeAdd),
            on_update=csi_node_upsert(events.CSINodeUpdate),
            on_delete=csi_node_delete,
        )
    )


def _node_scheduling_properties_changed(old: Node, new: Node) -> str:
    """eventhandlers.go:445 nodeSchedulingPropertiesChange: only wake
    pods when a property that can affect scheduling changed."""
    if old.spec.unschedulable != new.spec.unschedulable:
        return events.NodeSpecUnschedulableChange
    if old.status.allocatable != new.status.allocatable:
        return events.NodeAllocatableChange
    if old.metadata.labels != new.metadata.labels:
        return events.NodeLabelChange
    if old.spec.taints != new.spec.taints:
        return events.NodeTaintChange
    if old.status.conditions != new.status.conditions:
        return events.NodeConditionChange
    return ""
