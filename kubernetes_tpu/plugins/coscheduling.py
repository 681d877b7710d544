"""Coscheduling: all-or-nothing gang scheduling via the Permit extension
point.

Reference: the Permit/WaitingPod machinery this rides on is
/root/reference/pkg/scheduler/framework/v1alpha1/interface.go:384 (Permit,
can return Wait) + waiting_pods_map.go; the gang semantics follow the
out-of-tree scheduler-plugins Coscheduling plugin that SURVEY.md section
2.2 identifies as the reference's gang mechanism ("not in-tree -- enabled
by the Permit extension point").

Flow: each member of a PodGroup is filtered/scored/assumed normally; at
Permit, if fewer than ``min_member`` members hold assignments the pod
parks in WAIT (holding its resources via the assume). When the threshold
member arrives, it allows every waiting member. A timeout rejects the
waiters, which unreserves + requeues them -- all-or-nothing with bounded
capacity hold.

The TPU batch solver composes naturally: a whole gang usually lands in
one batch, each member is assumed during commit, and ``permit_batch``
decides a batch's members a gang at a time.

The member index. What the plugin asks of a gang (how many members the
cluster knows, how many hold a node) it answers from ``_gangs``, kept
from the pod events of the handle's informer and from its own Permit and
Unreserve calls, so that a question costs the gang's size and not the
cluster's pods. A pod without a pod-group label costs one label read an
event.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from kubernetes_tpu.api.types import POD_GROUP_LABEL, Pod, PodGroup
from kubernetes_tpu.framework.interface import CycleState, Plugin, Status

DEFAULT_SCHEDULE_TIMEOUT_SECONDS = 60


class _Gang:
    """One pod group's members, by uid."""

    __slots__ = ("known", "holding")

    def __init__(self) -> None:
        #: every member the informer knows, bound or pending: uid -> key
        self.known: Dict[str, str] = {}
        #: members that hold a node: bound, or assumed and past this
        #: plugin's Permit (allowed or waiting) and not unreserved since
        self.holding: set = set()


class Coscheduling(Plugin):
    NAME = "Coscheduling"

    def __init__(self, args: Optional[dict] = None, handle=None) -> None:
        args = args or {}
        self.handle = handle
        self.default_timeout = float(
            args.get("schedule_timeout_seconds", DEFAULT_SCHEDULE_TIMEOUT_SECONDS)
        )
        self._lock = threading.Lock()
        self._gangs: Dict[Tuple[str, str], _Gang] = {}
        informers = getattr(handle, "informers", None)
        if informers is not None:
            from kubernetes_tpu.client.informer import ResourceEventHandler

            pods = informers.pods()
            pods.add_event_handler(ResourceEventHandler(on_batch=self._on_pods))
            if pods.has_synced():
                # built beside a running informer: its adds are past
                self._on_pods([("ADDED", None, p) for p in pods.list()])

    # -- the member index ---------------------------------------------------

    def _on_pods(self, frame) -> None:
        """One watch frame of the pod informer, ``[(type, old, new)]``."""
        for etype, _old, pod in frame:
            group = pod.metadata.labels.get(POD_GROUP_LABEL)
            if not group:
                continue
            key = (pod.metadata.namespace, group)
            uid = pod.metadata.uid
            with self._lock:
                gang = self._gangs.get(key)
                if etype == "DELETED":
                    if gang is not None:
                        gang.known.pop(uid, None)
                        gang.holding.discard(uid)
                        if not gang.known and not gang.holding:
                            del self._gangs[key]
                    continue
                if gang is None:
                    gang = self._gangs[key] = _Gang()
                gang.known[uid] = pod.key()
                if pod.spec.node_name:
                    gang.holding.add(uid)

    def _hold(self, key: Tuple[str, str], uids) -> int:
        """``uids`` hold a node from here on; returns how many of the
        gang's members do."""
        with self._lock:
            gang = self._gangs.get(key)
            if gang is None:
                gang = self._gangs[key] = _Gang()
            gang.holding.update(uids)
            return len(gang.holding)

    def members(self, namespace: str, group: str):
        """(uid -> ``Pod.key()`` of every known member, uids that hold a node):
        copies, for the batch solver's quorum census."""
        with self._lock:
            gang = self._gangs.get((namespace, group))
            if gang is None:
                return {}, set()
            return dict(gang.known), set(gang.holding)

    # -- helpers ------------------------------------------------------------

    def _group_of(self, pod: Pod) -> Optional[str]:
        return pod.metadata.labels.get(POD_GROUP_LABEL)

    def _pod_group(self, pod: Pod, name: str) -> Optional[PodGroup]:
        informers = getattr(self.handle, "informers", None)
        if informers is None:
            return None
        return informers.pod_groups().get(pod.metadata.namespace, name)

    def _count_total_members(self, pod: Pod, group: str) -> int:
        """Every group member known to the cluster (informer view)."""
        with self._lock:
            gang = self._gangs.get((pod.metadata.namespace, group))
            return len(gang.known) if gang is not None else 0

    def min_member(self, pod: Pod, group: str) -> int:
        """The gang's quorum, for the batch solver's all-or-nothing
        group masks (1 where no PodGroup of that name is served)."""
        pg = self._pod_group(pod, group)
        return pg.min_member if pg is not None else 1

    def _waiting(self, key: Tuple[str, str]) -> list:
        """The gang's members that wait at Permit."""
        with self._lock:
            gang = self._gangs.get(key)
            uids = list(gang.holding) if gang is not None else ()
        waiting = (self.handle.get_waiting_pod(uid) for uid in uids)
        return [wp for wp in waiting if wp is not None]

    def _release(self, key: Tuple[str, str]) -> None:
        """Quorum reached: allow every member of the gang that waits at
        Permit."""
        for wp in self._waiting(key):
            wp.allow(self.NAME)

    def reject_waiting(self, namespace: str, group: str, why: str) -> int:
        """Reject the gang's members that wait at Permit (the rest of the
        gang was just masked: they would hold their nodes until the
        timeout for members that are not coming). Returns how many."""
        waiting = self._waiting((namespace, group))
        for wp in waiting:
            wp.reject(self.NAME, why)
        return len(waiting)

    # -- PreFilter: fail fast when the gang can never assemble --------------

    def pre_filter(self, state: CycleState, pod: Pod) -> Optional[Status]:
        group = self._group_of(pod)
        if not group:
            return None
        pg = self._pod_group(pod, group)
        if pg is None:
            return None
        total = self._count_total_members(pod, group)
        if total < pg.min_member:
            return Status.unschedulable_and_unresolvable(
                f"pod group {group!r} has {total} members, "
                f"less than minMember {pg.min_member}"
            )
        return None

    # -- Permit: the gang barrier -------------------------------------------

    def permit_relevant(self, pod: Pod) -> bool:
        """Bulk-commit fast-path predicate: permit() is a no-op for pods
        without a pod-group label."""
        return bool(pod.metadata.labels.get(POD_GROUP_LABEL))

    def permit(
        self, state: CycleState, pod: Pod, node_name: str
    ) -> Tuple[Optional[Status], float]:
        return self.permit_batch([pod], [node_name])[0]

    def permit_batch(
        self, pods: List[Pod], node_names: List[str]
    ) -> List[Tuple[Optional[Status], float]]:
        """Permit for pods that were assumed together, a gang at a time:
        the members among ``pods`` join the gang's holders at once, and
        either the gang has its quorum, so they pass and the members
        that wait are released, or all of them wait."""
        out: List[Tuple[Optional[Status], float]] = [(None, 0.0)] * len(pods)
        gangs: Dict[Tuple[str, str], List[int]] = {}
        for i, pod in enumerate(pods):
            group = pod.metadata.labels.get(POD_GROUP_LABEL)
            if group:
                gangs.setdefault(
                    (pod.metadata.namespace, group), []
                ).append(i)
        for key, idx in gangs.items():
            pg = self._pod_group(pods[idx[0]], key[1])
            min_member = pg.min_member if pg is not None else 1
            assigned = self._hold(
                key, [pods[i].metadata.uid for i in idx]
            )
            if assigned >= min_member:
                self._release(key)
                continue
            wait = (Status.wait(), float(
                pg.schedule_timeout_seconds if pg is not None
                else self.default_timeout
            ))
            for i in idx:
                out[i] = wait
        return out

    def unreserve(self, state: CycleState, pod: Pod, node_name: str) -> None:
        """The pod gave its node back (Permit timeout or rejection, a
        failed bind): it no longer counts towards its gang's quorum."""
        group = self._group_of(pod)
        if not group:
            return
        with self._lock:
            gang = self._gangs.get((pod.metadata.namespace, group))
            if gang is not None:
                gang.holding.discard(pod.metadata.uid)
