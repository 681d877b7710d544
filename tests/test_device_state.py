"""Device-resident node-state handshake units (PR 5).

The dispatcher no longer validates device-state reuse with full [N, R]
``np.array_equal`` sweeps: ``NodeTensorCache.update`` returns a
``TensorDelta`` (changed rows + monotonic epochs) and
``DeviceNodeState.negotiate`` (scheduler/device_state.py) reconciles
O(changed rows) against the committer-mirrored expectation. These tests drive the
handshake directly: the ahead-by-K committer-lag case, divergence
scatter-fix, ring-overflow degradation, and the order-insensitive row
remap.
"""

import time

import numpy as np
import pytest

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.scheduler.batch import _SHADOW_RING_CAP
from kubernetes_tpu.scheduler.device_state import (
    DELTA_ROW_BUCKET,
    DeviceNodeState,
)
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.tensors import NodeTensorCache
from kubernetes_tpu.testing import make_node, make_pod


@pytest.fixture
def sched_stack():
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=16)
    yield sched
    sched.stop()
    informers.stop()


def _cluster(n):
    cache = SchedulerCache()
    for i in range(n):
        cache.add_node(
            make_node(f"hs-{i}").capacity(cpu="8", memory="16Gi").obj()
        )
    snap = Snapshot()
    cache.update_snapshot(snap)
    return cache, snap


def _negotiate(sched, nt, in_flight=False, assumed_seq=0):
    """One dispatch's handshake with a jitted solve that lands: fake the
    device refs the solve would have produced (content is irrelevant to
    the handshake). ``in_flight`` batches are unmirrored ones."""
    ds = sched.device_state
    neg = ds.negotiate(
        nt, sched.tensor_cache, nt.requested, nt.non_zero_requested,
        False, in_flight=in_flight, unmirrored=in_flight,
        assumed_seq=assumed_seq,
    )
    if neg is not None:
        ds.landed(neg, (object(), object(), object(), object()), False)
    return neg


def _prime(sched, nt):
    """First dispatch: full upload route."""
    neg = _negotiate(sched, nt)
    assert not neg.static_ok and not neg.carry_ok
    assert neg.carry == "upload" and neg.carry_rows == nt.capacity
    assert not neg.fix_rows.size and not neg.alloc_rows.size
    assert neg.member_rows == 0 and neg.carry_in is None
    return neg


def _mirror(sched, rows, req_rows, nzr_rows):
    """What ``DeviceNodeState.mirror`` does when a batch commits:
    scatter-add the placements into the running shadow and remember the
    per-row delta."""
    ds = sched.device_state
    with ds._lock:
        ds.seq += 1
        np.add.at(ds.req_shadow, rows, req_rows)
        np.add.at(ds.nzr_shadow, rows, nzr_rows)
        ds.pending_deltas.append((rows, req_rows, nzr_rows, ds.seq))


def _pod_rows(nt, k):
    r = nt.dims.num_dims
    req_rows = np.zeros((1, r), dtype=np.int32)
    req_rows[0, 0] = 500  # 500m cpu
    req_rows[0, 3] = 1  # pod count
    nzr_rows = np.asarray([[500, 128]], dtype=np.int32)
    return (
        np.asarray([k], dtype=np.int64),
        req_rows,
        nzr_rows,
    )


class TestHandshake:
    def test_steady_state_pure_reuse(self, sched_stack):
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        assert sched.state_uploads == 1
        nt = sched.tensor_cache.update(snap)
        neg = _negotiate(sched, nt)
        assert neg.carry_ok and neg.static_ok
        assert neg.fix_rows.size == 0 and neg.alloc_rows.size == 0
        assert sched.state_reuses == 1
        assert sched.delta_rows_uploaded == 0

    def test_own_commit_explained_by_mirror(self, sched_stack):
        """A batch commits (mirror + cache assume): the repacked row is
        explained by the expectation -- reuse, nothing uploaded."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        rows, req_rows, nzr_rows = _pod_rows(nt, 2)
        _mirror(sched, rows, req_rows, nzr_rows)
        pod = make_pod("own").node("hs-2").container(cpu="500m").obj()
        # match the mirror's arithmetic: nzr defaults differ, so pin them
        pod.__dict__["_nzr_memo"] = (500, 128 * 1024)
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert nt.delta.changed_rows.tolist() == [2]
        neg = _negotiate(sched, nt)
        assert neg.carry_ok
        assert neg.fix_rows.size == 0
        assert sched.state_uploads == 1
        assert len(sched.device_state.pending_deltas) == 0  # confirmed

    def test_ahead_by_k_committer_lag(self, sched_stack):
        """Regression for the ahead-by-K carry case: K batches mirrored
        but none visible in the host pack yet -- the carry must still
        validate (the host trails the shadow by exactly the ring)."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        k = _SHADOW_RING_CAP - 1
        for i in range(k):
            _mirror(sched, *_pod_rows(nt, i % 5))
        nt = sched.tensor_cache.update(snap)  # host saw NOTHING yet
        neg = _negotiate(sched, nt, in_flight=True)
        assert neg is not None and neg.carry_ok
        # nothing confirmed: the ring still holds all K deltas
        assert len(sched.device_state.pending_deltas) == k
        assert sched.state_uploads == 1

    @pytest.mark.parametrize("assumed", [False, True])
    def test_a_bound_and_deleted_batch_is_not_a_lagging_host(
        self, sched_stack, assumed
    ):
        """A batch's pod is assumed, bound and then DELETED before the
        next dispatch packs: the row is back at what it held before the
        batch, which is also what a host that had not yet seen the
        batch's commit would show. Only what the dispatcher knows of
        the committer tells the two apart: a batch whose commit had
        finished before the snapshot was refreshed cannot be trailed,
        so the row has diverged and is set to host truth on the device.
        Without that knowledge the same row reads as lag."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        _mirror(sched, *_pod_rows(nt, 2))
        seq = sched.device_state.pending_deltas[-1][3]
        pod = make_pod("gone").node("hs-2").container(cpu="500m").obj()
        cache.add_pod(pod)
        cache.remove_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert nt.delta.changed_rows.tolist() == [2]
        assert not nt.requested[2].any()
        neg = _negotiate(sched, nt, assumed_seq=seq if assumed else 0)
        assert neg.carry_ok and sched.state_uploads == 1
        if assumed:
            assert neg.fix_rows.tolist() == [2]
            assert not sched.device_state.req_shadow[2].any()
            assert sched.carry_divergences == 1
        else:
            assert neg.fix_rows.size == 0
            assert len(sched.device_state.pending_deltas) == 1  # still trailing

    def test_ring_overflow_degrades_to_counted_upload(self, sched_stack):
        """More unobserved mirrors than the ring holds: the oldest delta
        is dropped, so the handshake can no longer explain the lag and
        must resolve with a counted full upload -- never silently."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        for i in range(_SHADOW_RING_CAP + 2):
            _mirror(sched, *_pod_rows(nt, i % 5))
        assert len(sched.device_state.pending_deltas) == _SHADOW_RING_CAP
        # host now shows NONE of them; commits land in the cache so the
        # rows repack with host-side content the shadow can't explain
        for i in range(5):
            pod = (
                make_pod(f"lag-{i}").node(f"hs-{i}")
                .container(cpu="250m").obj()
            )
            cache.add_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        neg = _negotiate(sched, nt)
        assert not neg.carry_ok
        assert sched.state_uploads == 2
        assert sched.carry_divergences >= 1

    def test_external_divergence_scatter_fixed(self, sched_stack):
        """An external change (pod removed behind the scheduler's back)
        with nothing in flight: the changed rows ride a scatter patch,
        not a full upload."""
        sched = sched_stack
        cache, snap = _cluster(5)
        pod = make_pod("ext").node("hs-3").container(cpu="1").obj()
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        cache.remove_pod(pod)  # external: never mirrored
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        neg = _negotiate(sched, nt)
        assert neg.carry_ok
        assert neg.fix_rows.tolist() == [3]
        assert sched.carry_divergences == 1
        assert sched.delta_rows_uploaded == 1
        assert sched.state_uploads == 1  # no second full upload
        # shadow reconciled to host truth
        assert np.array_equal(
            sched.device_state.req_shadow[3], nt.requested[3]
        )

    def test_divergence_with_inflight_batches_drains(self, sched_stack):
        """Divergence while batches are in flight cannot be patched in
        place (the carry is ahead of the host): the caller must drain."""
        sched = sched_stack
        cache, snap = _cluster(5)
        pod = make_pod("ext2").node("hs-1").container(cpu="1").obj()
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        cache.remove_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert _negotiate(sched, nt, in_flight=True) is None

    def test_allocatable_change_rides_scatter(self, sched_stack):
        """A node's capacity update (same membership) patches the
        resident allocatable by row instead of re-uploading it."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        cache.add_node(
            make_node("hs-4").capacity(cpu="32", memory="64Gi").obj()
        )
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        neg = _negotiate(sched, nt)
        assert neg.carry_ok and neg.static_ok
        assert neg.alloc_rows.tolist() == [4]
        assert sched.delta_rows_uploaded == 1
        assert np.array_equal(
            sched.device_state.alloc_shadow[4], nt.allocatable[4]
        )

    def test_node_add_rides_membership_scatter(self, sched_stack):
        """Tentpole (PR 6): a node joining claims a headroom slot in
        place -- the carry stays warm, the new row rides the alloc+valid
        scatter, and NOTHING [N, R]-sized re-uploads."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        cache.add_node(
            make_node("hs-new").capacity(cpu="8", memory="16Gi").obj()
        )
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert not nt.delta.full
        new_row = nt.row("hs-new")
        assert nt.delta.membership_rows.tolist() == [new_row]
        neg = _negotiate(sched, nt)
        assert neg.static_ok and neg.carry_ok
        assert neg.alloc_rows.tolist() == [new_row]
        assert neg.member_rows == 1
        assert sched.state_uploads == 1  # still only the cold upload
        assert sched.state_reuses == 1
        assert sched.membership_row_patches == 1
        assert sched.carry_divergences == 0
        # the shadow adopted the new slot's host truth
        assert np.array_equal(
            sched.device_state.req_shadow[new_row], nt.requested[new_row]
        )

    def test_node_remove_rides_membership_scatter(self, sched_stack):
        """A node retiring frees its slot in place: its row rides the
        scatter (alloc zeroed, valid dropped, requested reset) with the
        carry warm -- an expected reset, never a divergence."""
        sched = sched_stack
        cache, snap = _cluster(5)
        pod = make_pod("on3").node("hs-3").container(cpu="1").obj()
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        row3 = nt.row("hs-3")
        _prime(sched, nt)
        from kubernetes_tpu.api.types import Node, ObjectMeta

        cache.remove_pod(pod)
        cache.remove_node(Node(metadata=ObjectMeta(name="hs-3")))
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert not nt.delta.full
        assert nt.delta.membership_rows.tolist() == [row3]
        assert nt.names[row3] == ""
        assert not nt.valid[row3]
        neg = _negotiate(sched, nt)
        assert neg.static_ok and neg.carry_ok
        assert neg.alloc_rows.tolist() == [row3]
        # the slot carried requested content on device: the didx scatter
        # must reset it (free slots are infeasible like padding)
        assert neg.fix_rows.tolist() == [row3]
        assert sched.state_uploads == 1
        assert sched.carry_divergences == 0
        assert sched.membership_row_patches == 1
        assert (sched.device_state.req_shadow[row3] == 0).all()

    def test_membership_with_inflight_batches_drains(self, sched_stack):
        """Membership churn while batches are in flight cannot be
        adopted under them: the dispatcher must drain first."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        _prime(sched, nt)
        cache.add_node(
            make_node("hs-new").capacity(cpu="8", memory="16Gi").obj()
        )
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert _negotiate(sched, nt, in_flight=True) is None

    def test_headroom_exhaustion_full_repacks_once(self, sched_stack):
        """Adds past the pre-allocated slot headroom force ONE counted
        full repack (fresh headroom), after which churn scatters
        again."""
        sched = sched_stack
        cache, snap = _cluster(5)
        nt = sched.tensor_cache.update(snap)
        cap = nt.capacity
        _prime(sched, nt)
        tc = sched.tensor_cache
        for i in range(cap - 5 + 1):  # one past the allocated capacity
            cache.add_node(
                make_node(f"hs-x{i}")
                .capacity(cpu="8", memory="16Gi")
                .obj()
            )
        cache.update_snapshot(snap)
        nt = sched.tensor_cache.update(snap)
        assert nt.delta.full
        assert tc.full_repacks == 2
        assert nt.capacity > cap
        neg = _negotiate(sched, nt)
        assert not neg.static_ok and not neg.carry_ok
        assert sched.state_uploads == 2


# -- the module's own table: no scheduler, bare arrays and a tensor cache ----


class _Table:
    """A cluster, its ``NodeTensorCache`` and a bare ``DeviceNodeState``
    whose first dispatch (the cold upload) has landed."""

    def __init__(self, n=5, pods_on=()):
        self.cache, self.snap = _cluster(n)
        self.pods = {}
        for k in pods_on:
            self.add_pod(k)
        self.tc = NodeTensorCache()
        self.ds = DeviceNodeState(_SHADOW_RING_CAP)
        self.repack()
        self.land(self.negotiate())

    def add_pod(self, k, cpu="500m"):
        pod = make_pod(f"t-{k}").node(f"hs-{k}").container(cpu=cpu).obj()
        # match the mirror's arithmetic: nzr defaults differ, so pin them
        pod.__dict__["_nzr_memo"] = (500, 128 * 1024)
        self.cache.add_pod(pod)
        self.pods[k] = pod

    def remove_pod(self, k):
        self.cache.remove_pod(self.pods.pop(k))

    def repack(self):
        self.cache.update_snapshot(self.snap)
        self.nt = self.tc.update(self.snap)
        return self.nt

    def negotiate(self, in_flight=False, unmirrored=False, assumed_seq=0,
                  overlaid=False):
        nt = self.nt
        return self.ds.negotiate(
            nt, self.tc, nt.requested, nt.non_zero_requested, overlaid,
            in_flight=in_flight, unmirrored=unmirrored,
            assumed_seq=assumed_seq,
        )

    def land(self, hs, overlaid=False):
        """A jitted solve that placed nothing: the carry it returns is
        the state it was handed."""
        import jax.numpy as jnp

        nt = self.nt
        return self.ds.landed(hs, (
            jnp.asarray(nt.requested), jnp.asarray(nt.non_zero_requested),
            jnp.asarray(nt.allocatable), jnp.asarray(nt.valid),
        ), overlaid)

    def mirror(self, k):
        """A batch that placed one 500m pod on row ``k`` commits."""
        _rows, req_rows, nzr_rows = _pod_rows(self.nt, k)
        record = {}
        seq = self.ds.mirror(
            record, np.asarray([k], dtype=np.int32), 1, req_rows, nzr_rows,
            False,
        )
        assert record["mirrored"]
        return seq

    def counters(self):
        ds = self.ds
        return (
            ds.state_uploads, ds.state_reuses, ds.delta_rows_uploaded,
            ds.membership_row_patches,
        )


def _remove_node(t, k):
    from kubernetes_tpu.api.types import Node, ObjectMeta

    t.cache.remove_node(Node(metadata=ObjectMeta(name=f"hs-{k}")))


def _nothing_moved(t):
    return {}, dict(carry="reuse", carry_in=True)


def _a_node_resized(t):
    t.cache.add_node(
        make_node("hs-4").capacity(cpu="32", memory="64Gi").obj()
    )
    return {}, dict(carry="scatter", alloc=[4], carry_in=True)


def _an_external_delete(t):
    t.remove_pod(3)  # never mirrored
    return {}, dict(carry="scatter", fix=[3], divergences=1)


def _a_divergence_under_a_pending_delta(t):
    t.mirror(2)
    t.add_pod(2, cpu="250m")  # not what the mirrored batch placed
    return {}, dict(carry="upload", divergences=1, uploads=2, ring=0)


def _a_node_joins_under_an_unmirrored_batch(t):
    t.cache.add_node(
        make_node("hs-new").capacity(cpu="8", memory="16Gi").obj()
    )
    return dict(in_flight=True, unmirrored=True), None


def _a_node_leaves_with_its_delta_in_the_ring(t):
    t.mirror(3)
    _remove_node(t, 3)
    return dict(in_flight=True), dict(
        carry="scatter", alloc=[3], fix=[3], member=1, ring=0,
    )


def _more_divergent_rows_than_the_bucket(t):
    for k in range(DELTA_ROW_BUCKET + 2):
        t.remove_pod(k)
    return {}, dict(carry="upload", divergences=1, uploads=2)


def _the_layout_moved(t):
    for i in range(t.nt.capacity - 5 + 1):  # one past the slot headroom
        t.cache.add_node(
            make_node(f"hs-x{i}").capacity(cpu="8", memory="16Gi").obj()
        )
    return {}, dict(carry="upload", static_ok=False, uploads=2)


def _pr27_a_bound_and_deleted_batch_is_a_divergence(t):
    seq = t.mirror(2)
    t.add_pod(2)
    t.remove_pod(2)  # bound, then deleted: the row is back where it was
    return dict(assumed_seq=seq), dict(
        carry="scatter", fix=[2], divergences=1, ring=0,
    )


def _a_batch_past_assumed_seq_is_a_lag(t):
    t.mirror(2)
    t.add_pod(2)
    t.remove_pod(2)  # the same row, read by a pack that may trail it
    return dict(assumed_seq=0), dict(carry="reuse", carry_in=True, ring=1)


def _pr41_an_in_flight_pack_may_repair_rows(t):
    t.remove_pod(3)
    return dict(in_flight=True), dict(
        carry="scatter", fix=[3], divergences=1, rewind=True,
    )


def _pr41_an_in_flight_pack_never_becomes_the_carry(t):
    # what would be uploaded with nothing in flight (the case above)
    t.mirror(2)
    t.add_pod(2, cpu="250m")
    return dict(in_flight=True), None


_NEGOTIATIONS = [
    _nothing_moved,
    _a_node_resized,
    _an_external_delete,
    _a_divergence_under_a_pending_delta,
    _a_node_joins_under_an_unmirrored_batch,
    _a_node_leaves_with_its_delta_in_the_ring,
    _more_divergent_rows_than_the_bucket,
    _the_layout_moved,
    _pr27_a_bound_and_deleted_batch_is_a_divergence,
    _a_batch_past_assumed_seq_is_a_lag,
    _pr41_an_in_flight_pack_may_repair_rows,
    _pr41_an_in_flight_pack_never_becomes_the_carry,
]


@pytest.mark.parametrize(
    "arrange", _NEGOTIATIONS, ids=lambda f: f.__name__.strip("_")
)
def test_negotiation_table(arrange):
    """Each way a dispatch's node state can reach the device, from bare
    arrays: what ``negotiate`` decides, what it leaves in the ring and
    the shadows, and what the landed outcome books."""
    big = arrange is _more_divergent_rows_than_the_bucket
    n = DELTA_ROW_BUCKET + 6 if big else 5
    t = _Table(n, pods_on=range(DELTA_ROW_BUCKET + 2) if big else (3,))
    ds = t.ds
    assert t.counters() == (1, 0, 0, 0)
    kwargs, want = arrange(t)
    nt = t.repack()
    before = (t.counters(), len(ds.pending_deltas), ds.req_shadow.copy())
    resident = (ds.req_dev, ds.nzr_dev)
    hs = t.negotiate(**kwargs)
    if want is None:
        # blocked: the caller waits or drains, and nothing was touched
        assert hs is None
        assert t.counters() == before[0]
        assert len(ds.pending_deltas) == before[1]
        assert np.array_equal(ds.req_shadow, before[2])
        return
    assert hs.carry == want["carry"]
    assert hs.static_ok == want.get("static_ok", True)
    assert hs.carry_ok == (want["carry"] != "upload")
    assert hs.fix_rows.tolist() == want.get("fix", [])
    assert hs.alloc_rows.tolist() == want.get("alloc", [])
    assert hs.member_rows == want.get("member", 0)
    assert hs.row_patch_rewind == want.get("rewind", False)
    rows = len(want.get("fix", [])) + len(want.get("alloc", []))
    assert hs.carry_rows == (nt.capacity if not hs.carry_ok else rows)
    # the pre-solve refs serve a rewind only where no row fix rode
    if want.get("carry_in"):
        assert hs.carry_in[0] is resident[0]
        assert hs.carry_in[1] is resident[1]
    else:
        assert hs.carry_in is None
    assert ds.carry_divergences == want.get("divergences", 0)
    if "ring" in want:
        assert len(ds.pending_deltas) == want["ring"]
    # the expectation now holds host truth, for the rows it was shown
    s = len(nt.names)
    if "ring" not in want or want["ring"] == 0:
        assert np.array_equal(ds.req_shadow[:s], nt.requested[:s])
        assert np.array_equal(ds.alloc_shadow[:s], nt.allocatable[:s])
    # nothing is booked until the outcome is known
    assert t.counters() == before[0]
    assert t.land(hs) == (not hs.carry_ok)
    uploads = want.get("uploads", 1)
    assert t.counters() == (
        uploads, 2 - uploads, rows if hs.carry_ok else 0,
        want.get("member", 0),
    )


def _host_placed(t, k):
    """The host tier's answer: one 500m pod on row ``k``."""
    _rows, req_rows, nzr_rows = _pod_rows(t.nt, k)
    return np.asarray([k], dtype=np.int32), req_rows, nzr_rows


@pytest.mark.parametrize("booked", ["reuse", "alloc-patch", "static-upload"])
def test_nothing_landed_books_nothing(booked):
    """The ladder was exhausted: the counters read what they read before
    ``negotiate``, the carry drops, and the resident alloc is distrusted
    exactly where the shadow claims a patch or an upload that never
    reached the device."""
    t = _Table()
    ds = t.ds
    if booked == "alloc-patch":
        _a_node_resized(t)
    elif booked == "static-upload":
        _the_layout_moved(t)
    t.repack()
    before = t.counters()
    hs = t.negotiate()
    assert hs.static_ok == (booked != "static-upload")
    ds.nothing_landed(hs)
    assert t.counters() == before
    assert ds.req_dev is None and ds.req_shadow is None
    assert (ds.alloc_dev is None) == (booked != "reuse")
    assert (ds.valid_dev is None) == (booked != "reuse")
    # the next dispatch uploads what is missing, and is counted
    t.repack()
    hs = t.negotiate()
    assert not hs.carry_ok and hs.static_ok == (booked == "reuse")
    t.land(hs)
    assert t.counters() == (before[0] + 1, *before[1:])


@pytest.mark.parametrize("broken", [
    None, "row-fix", "overlaid", "carry-lost", "upload",
])
def test_the_host_tier_keeps_the_carry_warm_under_four_conditions(broken):
    """The host tier solved from host state: its own placements are
    added to the resident carry only where that carry equals what it
    solved from -- a reused carry, no row fix riding the dispatch, no
    overlay, and the carry still resident. No link traffic is booked."""
    t = _Table(pods_on=(3,))
    ds = t.ds
    overlaid = broken == "overlaid"
    if broken == "row-fix":
        t.remove_pod(3)
    elif broken == "upload":
        ds.invalidate()
    t.repack()
    before = t.counters()
    hs = t.negotiate(overlaid=overlaid)
    assert hs.carry_ok == (broken not in ("upload", "overlaid"))
    if broken == "carry-lost":
        ds.invalidate()  # a committer's recovery, mid-dispatch
    carry = ds.req_dev
    assignments, req_rows, nzr_rows = _host_placed(t, 1)
    ds.host_solved(hs, assignments, req_rows, nzr_rows, overlaid)
    assert t.counters() == (
        before[0], before[1] + int(hs.carry_ok), before[2], before[3]
    )
    if broken is None:
        grown = np.asarray(ds.req_dev) - np.asarray(carry)
        assert grown[1].tolist() == req_rows[0].tolist()
        assert not np.delete(grown, 1, axis=0).any()
        assert ds.req_shadow is not None
    else:
        assert ds.req_dev is None and ds.req_shadow is None
    # no alloc patch or static upload was booked: the alloc is trusted
    assert ds.alloc_dev is not None and ds.valid_dev is not None


class TestTensorDeltaMembership:
    def test_pure_reorder_is_a_noop(self):
        """A pure node-ordering change moves NOTHING: slots stay in
        place, zero rows repack, the layout epoch stands (device buffers
        remain valid row-for-row)."""
        cache, snap = _cluster(6)
        tc = NodeTensorCache()
        nt1 = tc.update(snap)
        assert tc.full_repacks == 1
        repacked = tc.rows_repacked
        content = {
            name: nt1.allocatable[nt1.row(name)].copy()
            for name in nt1.names
        }
        # rebuild the snapshot map in a rotated order (same node set)
        names = list(snap.node_info_map)
        rotated = names[2:] + names[:2]
        snap.node_info_map = {n: snap.node_info_map[n] for n in rotated}
        snap.refresh_lists()
        nt2 = tc.update(snap)
        assert tc.full_repacks == 1  # NOT a membership change
        assert tc.reorders == 1
        assert tc.rows_repacked == repacked  # zero rows repacked
        assert nt2.names == nt1.names  # slots do not move
        assert nt2.delta.layout_epoch == nt1.delta.layout_epoch
        assert nt2.delta.changed_rows.size == 0
        for name in rotated:
            assert np.array_equal(
                nt2.allocatable[nt2.row(name)], content[name]
            ), name
        # the packers' position->row map follows the new snapshot order
        infos = snap.list_node_infos()
        rows = nt2.rows_for(infos)
        for j, ni in enumerate(infos):
            assert int(rows[j]) == nt2.row(ni.node_name)

    def test_reorder_plus_changed_row_repacks_only_that_row(self):
        cache, snap = _cluster(6)
        tc = NodeTensorCache()
        tc.update(snap)
        repacked = tc.rows_repacked
        pod = make_pod("rr").node("hs-5").container(cpu="2").obj()
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        names = list(snap.node_info_map)
        snap.node_info_map = {
            n: snap.node_info_map[n] for n in reversed(names)
        }
        snap.refresh_lists()
        nt = tc.update(snap)
        assert tc.full_repacks == 1
        assert tc.reorders == 1
        assert tc.rows_repacked == repacked + 1
        assert nt.requested[nt.row("hs-5"), 0] == 2000

    def test_add_claims_slot_remove_frees_it(self):
        """Incremental membership: an add claims a headroom slot, a
        remove retires it onto the free list, and the NEXT add reclaims
        the lowest free slot -- zero full repacks, zero layout bumps."""
        cache, snap = _cluster(3)
        tc = NodeTensorCache()
        nt0 = tc.update(snap)
        layout0 = nt0.delta.layout_epoch
        from kubernetes_tpu.api.types import Node, ObjectMeta

        cache.add_node(make_node("hs-x").capacity(cpu="1").obj())
        cache.update_snapshot(snap)
        nt = tc.update(snap)
        assert tc.full_repacks == 1
        assert not nt.delta.full
        assert nt.row("hs-x") == 3  # first headroom slot
        cache.remove_node(Node(metadata=ObjectMeta(name="hs-1")))
        cache.update_snapshot(snap)
        nt = tc.update(snap)
        assert tc.full_repacks == 1
        assert tc.rows_retired == 1
        assert nt.names[1] == ""
        assert not nt.valid[1]
        assert (nt.allocatable[1] == 0).all()
        cache.add_node(make_node("hs-y").capacity(cpu="2").obj())
        cache.update_snapshot(snap)
        nt = tc.update(snap)
        assert nt.row("hs-y") == 1  # reclaimed the freed slot
        assert nt.valid[1]
        assert nt.delta.layout_epoch == layout0
        assert tc.full_repacks == 1


class TestTensorDeltaEpochs:
    def test_changed_rows_and_epoch_monotonic(self):
        cache, snap = _cluster(4)
        tc = NodeTensorCache()
        nt1 = tc.update(snap)
        assert nt1.delta.full
        assert nt1.delta.changed_rows.tolist() == [0, 1, 2, 3]
        pod = make_pod("e").node("hs-1").container(cpu="1").obj()
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        nt2 = tc.update(snap)
        assert nt2.delta.epoch > nt1.delta.epoch
        assert nt2.delta.layout_epoch == nt1.delta.layout_epoch
        assert nt2.delta.changed_rows.tolist() == [1]
        assert tc.rows_changed_since(nt1.delta.epoch).tolist() == [1]
        assert tc.rows_changed_since(nt2.delta.epoch).size == 0

    def test_sibling_consumers_do_not_steal_change_notes(self):
        """Regression: the preemptor's sibling cache and the prewarm
        thread's fresh cache update() against the SAME shared snapshot
        as the scheduler's tensor cache -- a one-shot note consume
        would let one consumer steal another's changed rows (silently
        stale packs). Reads are cursor-based now: every consumer sees
        every change."""
        cache, snap = _cluster(4)
        tc1, tc2 = NodeTensorCache(), NodeTensorCache()
        tc1.update(snap)
        tc2.update(snap)
        pod = make_pod("sib").node("hs-2").container(cpu="1").obj()
        cache.add_pod(pod)
        cache.update_snapshot(snap)
        # the OTHER consumer reads first...
        nt2 = tc2.update(snap)
        assert nt2.delta.changed_rows.tolist() == [2]
        # ...and tc1 still sees the change (and packs the row)
        nt1 = tc1.update(snap)
        assert nt1.delta.changed_rows.tolist() == [2]
        assert nt1.requested[2, 0] == 1000
        assert nt2.requested[2, 0] == 1000

    def test_foreign_snapshot_full_walk_same_result(self):
        """A snapshot the cache has no baseline for still packs
        correctly (tests/tools construct fresh snapshots)."""
        from kubernetes_tpu.cache.snapshot import new_snapshot

        node = make_node("f").capacity(cpu="4", memory="8Gi").obj()
        pod = make_pod("fp").node("f").container(cpu="1").obj()
        tc = NodeTensorCache()
        nt = tc.update(new_snapshot([pod], [node]))
        assert nt.requested[nt.row("f"), 0] == 1000
        nt = tc.update(new_snapshot([pod], [node]))
        assert nt.requested[nt.row("f"), 0] == 1000


class TestRandomizedMembershipChurn:
    """PR-6 satellite: interleaved node add/remove/reorder + external
    pod churn (the bind-failure shape: content changes the scheduler
    never mirrored) must keep (a) the slot-packed tensor equal to a
    fresh full pack of the same cluster, per name, (b) the handshake's
    shadow equal to host truth after every negotiation, and (c) the
    layout epoch UNCHANGED -- pure membership churn never full-repacks
    while adds stay inside the slot headroom."""

    def test_differential_vs_fresh_pack(self, sched_stack):
        import random

        rng = random.Random(20260803)
        sched = sched_stack
        cache = SchedulerCache()
        from kubernetes_tpu.api.types import Node, ObjectMeta

        nodes = {}
        pods_by_node = {}
        seq = [0]

        def new_node():
            name = f"rc-{seq[0]}"
            seq[0] += 1
            node = (
                make_node(name)
                .capacity(cpu="16", memory="32Gi", pods=64)
                .obj()
            )
            nodes[name] = node
            pods_by_node[name] = []
            cache.add_node(node)

        for _ in range(12):
            new_node()
        snap = Snapshot()
        cache.update_snapshot(snap)
        tc = sched.tensor_cache
        nt = tc.update(snap)
        capacity0 = nt.capacity
        layout0 = tc.layout_epoch
        _prime(sched, nt)

        def fresh_pack():
            from kubernetes_tpu.cache.snapshot import new_snapshot

            live_pods = [
                p for ps in pods_by_node.values() for p in ps
            ]
            return NodeTensorCache().update(
                new_snapshot(live_pods, list(nodes.values()))
            )

        uploads0 = sched.state_uploads
        for step in range(80):
            op = rng.choice(
                ["add", "remove", "reorder", "pod_add", "pod_del"]
            )
            if op == "add" and len(nodes) < capacity0 - 2:
                new_node()
            elif op == "remove" and len(nodes) > 3:
                name = rng.choice(sorted(nodes))
                for p in pods_by_node.pop(name):
                    cache.remove_pod(p)
                del nodes[name]
                cache.remove_node(
                    Node(metadata=ObjectMeta(name=name))
                )
            elif op == "reorder":
                names = list(snap.node_info_map)
                rng.shuffle(names)
                snap.node_info_map = {
                    n: snap.node_info_map[n] for n in names
                }
                snap.refresh_lists()
            elif op == "pod_add":
                name = rng.choice(sorted(nodes))
                p = (
                    make_pod(f"rp-{step}")
                    .node(name)
                    .container(cpu="250m", memory="256Mi")
                    .obj()
                )
                pods_by_node[name].append(p)
                cache.add_pod(p)
            else:  # pod_del: external removal the mirror never saw
                cands = [n for n in sorted(nodes) if pods_by_node[n]]
                if not cands:
                    continue
                name = rng.choice(cands)
                p = pods_by_node[name].pop()
                cache.remove_pod(p)
            cache.update_snapshot(snap)
            nt = tc.update(snap)

            # -- handshake: carry must stay warm (scatters only) --------
            neg = _negotiate(sched, nt)
            assert neg is not None, f"step {step}: drain demanded"
            assert neg.carry_ok, f"step {step}: carry dropped"
            s = len(nt.names)
            assert np.array_equal(
                sched.device_state.req_shadow[:s], nt.requested[:s]
            ), f"step {step}: shadow != host"

            # -- tensor content: equal to a fresh full pack per name ----
            fresh = fresh_pack()
            assert sorted(n for n in nt.names if n) == sorted(
                fresh.names
            )
            for name in nodes:
                i, k = nt.row(name), fresh.row(name)
                assert np.array_equal(
                    nt.requested[i], fresh.requested[k]
                ), f"step {step}: {name} requested"
                assert np.array_equal(
                    nt.allocatable[i], fresh.allocatable[k]
                ), f"step {step}: {name} allocatable"
                assert np.array_equal(
                    nt.non_zero_requested[i],
                    fresh.non_zero_requested[k],
                ), f"step {step}: {name} nzr"
                assert nt.valid[i]
            # free slots stay infeasible like padding
            for i, name in enumerate(nt.names):
                if not name:
                    assert not nt.valid[i]
                    assert (nt.allocatable[i] == 0).all()
                    assert (nt.requested[i] == 0).all()

        # the whole churn run rode scatters: zero layout bumps, zero
        # extra full uploads
        assert tc.layout_epoch == layout0
        assert tc.full_repacks == 1
        assert sched.state_uploads == uploads0


class TestApplyAssignmentDelta:
    def test_no_node_slots_drop_instead_of_wrapping(self):
        """Regression: JAX wraps negative indices even with
        ``mode="drop"`` -- NO_NODE (-1) slots must not scatter their
        pod rows onto the LAST node row of the resident state."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.assignment import (
            NO_NODE,
            apply_assignment_delta,
        )

        req = jnp.zeros((4, 3), dtype=jnp.int32)
        nzr = jnp.zeros((4, 2), dtype=jnp.int32)
        assigns = np.asarray([NO_NODE, 2, NO_NODE, 7], dtype=np.int32)
        pod_req = np.full((4, 3), 5, dtype=np.int32)
        pod_nzr = np.full((4, 2), 7, dtype=np.int32)
        req2, nzr2 = apply_assignment_delta(
            req, nzr, assigns, pod_req, pod_nzr
        )
        req2, nzr2 = np.asarray(req2), np.asarray(nzr2)
        assert req2[2].tolist() == [5, 5, 5]  # the one placed pod
        assert nzr2[2].tolist() == [7, 7]
        # NO_NODE and past-the-end slots leave every other row alone
        for i in (0, 1, 3):
            assert req2[i].tolist() == [0, 0, 0], f"row {i} corrupted"
            assert nzr2[i].tolist() == [0, 0], f"row {i} corrupted"


class TestHostTierAllocBookkeeping:
    def test_host_tier_after_layout_change_drops_stale_alloc(
        self, monkeypatch
    ):
        """Regression: the handshake books a full static upload
        (layout moved), but the ladder lands on the HOST tier so no
        jitted solve runs and the alloc/valid pieces never reach the
        device -- the stale device refs must drop, or the next dispatch
        would solve against the previous layout's allocatable."""
        from kubernetes_tpu.robustness.ladder import TIER_HOST_GREEDY

        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=8)
        for i in range(3):
            client.create_node(
                make_node(f"ht-{i}")
                .capacity(cpu="8", memory="16Gi")
                .obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        sched.queue.run()
        try:
            # dispatch 1 on the device tier: resident alloc established
            client.create_pod(
                make_pod("ht-p0").container(cpu="100m").obj()
            )
            deadline = time.time() + 10
            while time.time() < deadline:
                if sched.schedule_batch(timeout=0.2):
                    break
            sched.wait_for_inflight_binds(timeout=30)
            assert sched.device_state.alloc_dev is not None
            assert sched.state_uploads == 1

            # layout change: a node joins (full static upload booked)
            client.create_node(
                make_node("ht-new")
                .capacity(cpu="8", memory="16Gi")
                .obj()
            )
            deadline = time.time() + 10
            while time.time() < deadline:
                if "ht-new" in sched.cache._nodes:
                    break
                time.sleep(0.02)

            # ...but the device tiers are down: the HOST tier solves
            orig_run = sched.ladder.run

            def host_only(attempts, label="batch"):
                for tier, thunk in attempts:
                    if tier == TIER_HOST_GREEDY:
                        return tier, thunk()
                return orig_run(attempts, label=label)

            monkeypatch.setattr(sched.ladder, "run", host_only)
            client.create_pod(
                make_pod("ht-p1").container(cpu="100m").obj()
            )
            deadline = time.time() + 10
            while time.time() < deadline:
                if sched.schedule_batch(timeout=0.2):
                    break
            sched.wait_for_inflight_binds(timeout=30)
            assert sched.device_state.alloc_dev is None, (
                "stale device alloc survived a host-tier solve that "
                "never uploaded the new layout"
            )
            assert sched.device_state.valid_dev is None

            # device tier back: the next dispatch re-uploads in full
            # and places correctly against the 4-node layout
            monkeypatch.setattr(sched.ladder, "run", orig_run)
            uploads = sched.state_uploads
            client.create_pod(
                make_pod("ht-p2").container(cpu="100m").obj()
            )
            deadline = time.time() + 10
            while time.time() < deadline:
                if sched.schedule_batch(timeout=0.2):
                    break
            sched.wait_for_inflight_binds(timeout=30)
            assert sched.state_uploads == uploads + 1
            bound = [
                p for p in client.list_pods()[0] if p.spec.node_name
            ]
            assert len(bound) == 3
        finally:
            sched.stop()
            informers.stop()
