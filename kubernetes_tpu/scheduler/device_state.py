"""What the chip holds between batches, and the host's expectation of it.

One decision lives here: the node tensors stay on the device from batch
to batch, the host keeps an expectation of them (the shadows and the
ring of mirrored batches), and reuse is validated by generation. The
dispatcher (scheduler/batch.py) asks ``DeviceNodeState.negotiate`` how a
dispatch's node state reaches the device, takes the resident tensors it
may trust from ``operands``, and reports exactly one outcome
(``landed`` / ``host_solved`` / ``nothing_landed``); the committer calls
``mirror``. Every field is read and written under the state's own lock.
An in-flight record is the dispatcher's own mapping: this module reads
its ``carry_in`` and flips its ``mirrored``, both under that lock.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
from typing import Callable, List, Mapping, MutableMapping, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from kubernetes_tpu.ops.assignment import NO_NODE, apply_assignment_delta
from kubernetes_tpu.robustness.faults import FaultPoint, get_injector
from kubernetes_tpu.utils import flightrecorder
from kubernetes_tpu.utils import metrics

logger = logging.getLogger(__name__)

#: padded row count of the (indices, rows) delta-scatter slot riding the
#: steady-state upload buffer: one fixed bucket keeps the steady solve at
#: ONE jit signature regardless of churn; more than this many changed
#: rows per dispatch escalates to a (counted) full upload
DELTA_ROW_BUCKET = 64


def delta_slot_pieces(
    n_cap, r_dims, fix_rows=None, alloc_rows=None,
    node_requested=None, node_nzr=None, allocatable=None, valid=None,
):
    """The fixed `DELTA_ROW_BUCKET`-sized (indices, rows) scatter slots
    every steady-state dispatch carries in the single upload buffer.
    Shapes/dtypes/padding here ARE the jit signature the warmup
    precompiles -- the dispatch path and `_maybe_warm` must build them
    through this one helper or they fork a second signature and the
    first production batch pays the compile the warmup was built to
    prevent. Empty slots carry index ``n_cap`` (out of bounds) and drop
    on device.

    ``svalid`` rides with the alloc scatter: membership churn retires /
    claims row slots in place, so the patched rows must also flip the
    device-resident valid mask (a retired slot with alloc zeroed is
    still choosable by a zero-request pod unless valid drops)."""
    didx = np.full(DELTA_ROW_BUCKET, n_cap, dtype=np.int32)
    dreq = np.zeros((DELTA_ROW_BUCKET, r_dims), dtype=np.int32)
    dnzr = np.zeros((DELTA_ROW_BUCKET, 2), dtype=np.int32)
    sidx = np.full(DELTA_ROW_BUCKET, n_cap, dtype=np.int32)
    salloc = np.zeros((DELTA_ROW_BUCKET, r_dims), dtype=np.int32)
    svalid = np.zeros(DELTA_ROW_BUCKET, dtype=np.int32)
    if fix_rows is not None and fix_rows.size:
        didx[: fix_rows.size] = fix_rows
        dreq[: fix_rows.size] = node_requested[fix_rows]
        dnzr[: fix_rows.size] = node_nzr[fix_rows]
    if alloc_rows is not None and alloc_rows.size:
        sidx[: alloc_rows.size] = alloc_rows
        salloc[: alloc_rows.size] = allocatable[alloc_rows]
        svalid[: alloc_rows.size] = valid[alloc_rows]
    return [
        ("didx", didx), ("dreq", dreq), ("dnzr", dnzr),
        ("sidx", sidx), ("salloc", salloc), ("svalid", svalid),
    ]


def _mirror_scatter_py(assignments, b, req, nzr, req_shadow, nzr_shadow):
    """Pure-Python twin of native mirror_scatter: compact the batch's
    placed rows and scatter-add them into the shadow expectation.
    Returns (rows [K] int64, req_rows [K, R], nzr_rows [K, 2]) or None
    when nothing placed -- identical semantics to the C loop
    (differentially tested in tests/test_native_mirror.py)."""
    placed = assignments[:b] != NO_NODE
    if not placed.any():
        return None
    rows_placed = assignments[:b][placed].astype(np.int64)
    req_rows = req[:b][placed]
    nzr_rows = nzr[:b][placed]
    np.add.at(req_shadow, rows_placed, req_rows)
    np.add.at(nzr_shadow, rows_placed, nzr_rows)
    return rows_placed, req_rows, nzr_rows


def _mirror_scatter(assignments, b, req, nzr, req_shadow, nzr_shadow):
    """The bind-echo -> shadow-mirror hot loop: one C pass
    (native/_hotpath.c mirror_scatter) over the batch's assignments
    compacts the placed rows AND applies the scatter-add, on the
    committer thread. The C side validates every index BEFORE mutating,
    so a native failure can always fall back to the twin without
    double-applying."""
    from kubernetes_tpu import native as _native

    fn, expected = _native.ingest_fn("mirror_scatter")
    if fn is not None:
        try:
            a = np.ascontiguousarray(assignments[:b], dtype=np.int32)
            req_b = np.ascontiguousarray(req[:b], dtype=np.int32)
            nzr_b = np.ascontiguousarray(nzr[:b], dtype=np.int32)
            rows_out = np.empty(b, dtype=np.int64)
            req_out = np.empty((b, req_b.shape[1]), dtype=np.int32)
            nzr_out = np.empty((b, 2), dtype=np.int32)
            k = fn(
                a, req_b, nzr_b, req_shadow, nzr_shadow,
                rows_out, req_out, nzr_out,
            )
            if k == 0:
                return None
            return rows_out[:k], req_out[:k], nzr_out[:k]
        except Exception:
            logger.exception("native mirror_scatter failed")
            metrics.ingest_native_fallbacks.inc(site="mirror-scatter")
    elif expected:
        metrics.ingest_native_fallbacks.inc(site="mirror-scatter")
    return _mirror_scatter_py(
        assignments, b, req, nzr, req_shadow, nzr_shadow
    )


def _audit_checksum_host(arr: np.ndarray) -> Tuple[int, int]:
    """Order-independent wrapping checksum pair (plain sum + row-weighted
    sum, both mod 2^32) of a host array. Must match
    ``_audit_checksum_dev`` bit-for-bit: both sides compute in int32
    with C wrap semantics, and wrapped +/* form a ring, so reduction
    order never matters."""
    a = np.asarray(arr)
    if a.dtype != np.int32:
        a = a.astype(np.int32)
    if a.ndim == 1:
        a = a[:, None]
    w = (np.arange(a.shape[0], dtype=np.int32) + 1)[:, None]
    s = int(a.sum(dtype=np.int32))
    ws = int((a * w).sum(dtype=np.int32))
    return s, ws


def _audit_checksum_dev(arr):
    """Device twin of ``_audit_checksum_host``: two O(N*R) int32
    reductions ON the device -- the cheap per-sweep cost of the carry
    audit; the full [N, R] download happens only on mismatch. Returns
    device scalars (the caller converts once, batching the sync)."""
    a = arr.astype(jnp.int32)
    if a.ndim == 1:
        a = a[:, None]
    w = (jnp.arange(a.shape[0], dtype=jnp.int32) + 1)[:, None]
    return jnp.sum(a, dtype=jnp.int32), jnp.sum(a * w, dtype=jnp.int32)


_NO_ROWS = np.zeros(0, dtype=np.int64)


@dataclasses.dataclass(frozen=True, eq=False)
class Handshake:
    """What one negotiation decided, for the dispatcher to build its
    operands from and to hand back with the dispatch's outcome.

    - ``carry_ok`` with no rows: pure reuse, nothing node-sized rides
      the link.
    - ``carry_ok`` with ``fix_rows`` / ``alloc_rows``: reuse, with
      externally changed rows (divergences / allocatable updates)
      patched onto the resident state by the in-buffer scatter
      (ops/assignment.py). Membership churn (node add/remove
      claiming/retiring slots in place, see NodeTensorCache) rides the
      same scatter -- ``alloc_rows`` patch alloc AND valid, ``fix_rows``
      reset the slot's requested state -- and is an EXPECTED reset,
      never counted as a divergence.
    - not ``carry_ok``: full [N, R] requested upload; not ``static_ok``
      additionally re-uploads allocatable+valid.
    """

    static_ok: bool
    carry_ok: bool
    #: the packed node capacity: what a full upload sends
    capacity: int
    fix_rows: np.ndarray
    alloc_rows: np.ndarray
    #: of the rows, the slots a node joined or left since the last batch
    member_rows: int = 0
    #: pre-solve carry refs: the gang quorum fixup restores these to
    #: rewind a re-solved batch to its pre-batch device state without a
    #: re-upload (only exact when no row fixes rode this dispatch)
    carry_in: Optional[tuple] = None
    #: a divergence was repaired in place with batches in flight: the
    #: speculative chain's cheap rewind, which the pipeline counts
    row_patch_rewind: bool = False

    @property
    def delta_rows(self) -> int:
        return int(self.fix_rows.size + self.alloc_rows.size)

    @property
    def carry(self) -> str:
        """How the resident state is brought up to date."""
        if not self.carry_ok:
            return "upload"
        return "scatter" if self.delta_rows else "reuse"

    @property
    def carry_rows(self) -> int:
        """The node rows sent for it."""
        return self.delta_rows if self.carry_ok else self.capacity


class DeviceNodeState:
    """Device-resident node tensors + the generation-handshake
    bookkeeping that validates their reuse.

    Every host->device transfer pays a round trip
    (SURVEY.md section 7 "hardest parts (e)"), so the solver keeps node
    state ON DEVICE between batches: the scan already returns the
    post-batch (requested, nzr) on device, and the host mirrors the same
    integer updates into ``req_shadow``/``nzr_shadow`` at commit time.

    Reuse validation is a GENERATION HANDSHAKE, not an array sweep: the
    NodeTensorCache stamps every repacked row with a monotonic epoch, so
    at dispatch only ``rows_changed_since(validated_epoch)`` need a
    content compare against the expectation -- O(changed rows), not a
    sweep of the full [N, R] arrays. The committer may trail the
    dispatcher by several
    batches; ``pending_deltas`` holds each mirrored batch's per-row adds
    so a host state that trails the shadow by a suffix of them still
    validates. Changed rows the expectation does NOT explain (node churn,
    bind failures) are divergences: they are scatter-patched onto the
    resident state as (indices, rows) -- or, with work in flight or too
    many rows, resolved by a counted full upload. Never silently wrong.

    The link counters (``state_uploads``, ``state_reuses``,
    ``delta_rows_uploaded``, ``membership_row_patches`` and their
    Prometheus twins) count ESTABLISHED device state: the outcome books
    them, once a jitted solve has carried the decided upload / scatter
    to the device. A device tier that uploaded and then failed still
    paid the link traffic; that cost is attributed by
    solves_by_tier/breaker metrics, not here. ``carry_divergences``
    counts an observation and is booked when it is made.
    """

    def __init__(self, ring_cap: int) -> None:
        """``ring_cap`` bounds the per-batch expected-delta ring.
        Overflow drops the oldest delta, which at worst turns a later
        handshake into a counted divergence (full upload) -- never a
        silent mismatch."""
        self._lock = threading.Lock()
        self.alloc_dev = None
        self.valid_dev = None
        self.req_dev = None
        self.nzr_dev = None
        # -- handshake bookkeeping ---------------------------------------
        # the NodeTensorCache layout epoch the device buffers were built
        # against: row identity is only comparable while it stands
        self.layout_epoch = -1
        # the cache update epoch the shadows were last reconciled to
        self.validated_epoch = -1
        # expected host state: alloc mirrors the packed allocatable
        # (patched row-wise); req/nzr mirror the packed requested state
        # plus every mirrored (committed) batch
        self.alloc_shadow: Optional[np.ndarray] = None
        self.valid_shadow: Optional[np.ndarray] = None
        self.req_shadow: Optional[np.ndarray] = None
        self.nzr_shadow: Optional[np.ndarray] = None
        # per-batch expected row deltas the host pack may not have shown
        # yet: (node_rows [K], req_rows [K, R], nzr_rows [K, 2], seq),
        # newest last. ``seq`` is the batch's mirror sequence number: once
        # the scheduler's ``_assumed_seq`` has reached it the batch is
        # in the host cache, and a pack made after that can no longer
        # be said to trail it (_explain_rows)
        self.pending_deltas: "collections.deque" = collections.deque(
            maxlen=ring_cap
        )
        # bumped per dispatch AND per shadow mirror: stamps the ring,
        # and lets an audit detect that a dispatch/commit raced its
        # checksum window
        self.seq = 0
        self.state_reuses = 0
        self.state_uploads = 0
        # generation-handshake visibility: total changed node rows shipped
        # as (indices, rows) scatters instead of full [N, R] uploads, and
        # handshake mismatches (host state not explained by our own
        # mirrored placements -- node churn, bind failures)
        self.delta_rows_uploaded = 0
        self.carry_divergences = 0
        # membership churn absorbed as in-place slot scatters (node
        # add/remove rows patched onto the resident state without a
        # layout move, an upload, or a divergence)
        self.membership_row_patches = 0
        self.audits = 0
        self.audit_heals = 0

    # -- the carry's plain writers -------------------------------------------

    def _invalidate(self) -> None:
        self.req_dev = None
        self.nzr_dev = None
        self.req_shadow = None
        self.nzr_shadow = None
        self.pending_deltas.clear()

    def invalidate(self) -> None:
        """Drop the carry: the next dispatch re-uploads (counted)."""
        with self._lock:
            self._invalidate()

    def rewind(self, carry_in) -> str:
        """Rewind the device carry to a batch's pre-solve state: the
        gang quorum fixup re-solves the same batch, which must not see
        the first attempt's reservations. When the dispatch reused the
        carry, its pre-solve device refs are still alive (``carry_in``)
        and the rewind uploads nothing; otherwise the carry drops and
        the re-dispatch re-uploads."""
        with self._lock:
            if carry_in is not None and self.req_dev is not None:
                self.req_dev, self.nzr_dev = carry_in
                return "rewound"
            self._invalidate()
            return "dropped"

    def lost(self) -> None:
        """Every device-resident buffer is gone: drop all resident
        state + shadows, so the next dispatch rebuilds from the host
        cache through the cold-upload path."""
        with self._lock:
            self.alloc_dev = None
            self.valid_dev = None
            self.alloc_shadow = None
            self.valid_shadow = None
            self.layout_epoch = -1
            self._invalidate()

    def begin_dispatch(self) -> None:
        """A dispatch starts: any checksum window of the audit that
        spans this moment is void."""
        with self._lock:
            # under the lock: the committer bumps this too, and a lost
            # increment would blind the carry audit's race detector
            self.seq += 1

    # -- the generation handshake --------------------------------------------

    def _explain_rows(self, changed, host_req, host_nzr, assumed_seq):
        """Under the lock: is every changed row's host content
        explained by the shadow expectation at some committer-trail
        depth? The host may trail the shadow by a suffix of
        ``pending_deltas`` (batches mirrored but whose cache assume the
        host pack predates) -- peel them newest-first until the changed
        rows match. Only batches past ``assumed_seq`` may be peeled:
        the commits up to it had assumed their pods into the cache
        before this dispatch refreshed its snapshot, so the pack holds
        them, and a host row that equals the shadow less such a batch
        is not lagging -- the batch's pods were bound and have since
        been DELETED (a closed wave no larger than the ring would
        otherwise read as a lagging host for ever, and the device
        would go on placing around pods that are gone).
        Returns ``(ok, divergent_rows, keep)``: on a match
        ``keep`` is the number of newest deltas still unconfirmed; on a
        mismatch ``divergent_rows`` holds the depth-0 mismatches and
        ``keep`` is 0 when NO pending delta touches them (the mismatch
        is genuinely external, so a row scatter-fix is exact -- the
        device carry always equals the shadow once every dispatched
        batch has mirrored) or None when one does (the row may merely
        be host-lagging; only a full resync is safe)."""
        if changed.size == 0:
            # no repacked rows: nothing to confirm, keep every delta
            return True, None, len(self.pending_deltas)
        exp_req = self.req_shadow[changed]
        exp_nzr = self.nzr_shadow[changed]
        h_req = host_req[changed]
        h_nzr = host_nzr[changed]
        row_ok = np.all(exp_req == h_req, axis=1) & np.all(
            exp_nzr == h_nzr, axis=1
        )
        if row_ok.all():
            return True, None, 0
        div_rows = changed[~row_ok]
        pos = {int(r): j for j, r in enumerate(changed)}
        keep = 0
        trailing = [d for d in self.pending_deltas if d[3] > assumed_seq]
        for rows, req_rows, nzr_rows, _seq in reversed(trailing):
            keep += 1
            for j, r in enumerate(rows.tolist()):
                jj = pos.get(int(r))
                if jj is not None:
                    exp_req[jj] -= req_rows[j]
                    exp_nzr[jj] -= nzr_rows[j]
            if (
                np.all(exp_req == h_req, axis=1)
                & np.all(exp_nzr == h_nzr, axis=1)
            ).all():
                return True, None, keep
        div_set = set(div_rows.tolist())
        lagging = any(
            int(r) in div_set
            for rows, _req_rows, _nzr_rows, _seq in trailing
            for r in rows
        )
        return False, div_rows, (None if lagging else 0)

    def _adopt_membership_rows(self, member, host_req, host_nzr):
        """Under the lock, with nothing unmirrored (so the device
        carry equals the shadow): adopt host truth for churned row slots
        into the shadow expectation and scrub them from the pending
        ring (their pre-churn deltas can never be confirmed -- the slot
        belongs to a different node now). Returns the subset whose
        device content (== pre-adoption shadow) actually differs and
        therefore must ride the didx scatter."""
        diff = ~(
            np.all(self.req_shadow[member] == host_req[member], axis=1)
            & np.all(self.nzr_shadow[member] == host_nzr[member], axis=1)
        )
        fix = member[diff]
        self.req_shadow[member] = host_req[member]
        self.nzr_shadow[member] = host_nzr[member]
        scrubbed = collections.deque(maxlen=self.pending_deltas.maxlen)
        for rows, req_rows, nzr_rows, seq in self.pending_deltas:
            keepm = ~np.isin(rows, member)
            # entries fully on churned slots drop: nothing left to confirm
            if keepm.any():
                scrubbed.append(
                    (rows[keepm], req_rows[keepm], nzr_rows[keepm], seq)
                )
        self.pending_deltas = scrubbed
        return fix

    def negotiate(
        self, nt, tensor_cache, node_requested, node_nzr, overlaid,
        *, in_flight: bool, unmirrored: bool, assumed_seq: int,
    ) -> Optional[Handshake]:
        """Decide how this dispatch's node state reaches the device and
        reconcile the handshake bookkeeping, on the assumption that the
        decided upload / scatter reaches the device this dispatch (the
        outcome says whether it did). Returns None when in-flight
        batches block the decision (caller drains and redispatches),
        else the ``Handshake``.

        The mesh path rides the same scatters through the sharded twin
        (each delta row lands on exactly one node shard).

        ``unmirrored`` is the speculative-chain relaxation: the
        membership-adopt and scatter-fix paths only need the device
        carry to EQUAL the shadow, which holds as soon as every
        in-flight batch has mirrored -- commits may still be running.
        Only the full-upload path (which takes HOST truth as the new
        carry, so every placement must have landed in the cache) still
        gates on ``in_flight``.

        ``assumed_seq``: the newest batch whose commit had finished
        before this dispatch refreshed the snapshot it packed from
        (``_explain_rows`` says what that forbids).
        """
        d = nt.delta
        empty = _NO_ROWS
        with self._lock:
            layout_ok = (
                d is not None
                and self.alloc_dev is not None
                and self.alloc_shadow is not None
                and self.layout_epoch == d.layout_epoch
                and self.alloc_shadow.shape == nt.allocatable.shape
            )
            alloc_rows = empty
            member = empty
            member_fix = empty
            carry = "dead"
            div_rows = None
            keep = 0
            if layout_ok:
                changed = tensor_cache.rows_changed_since(
                    self.validated_epoch
                )
                member = tensor_cache.membership_rows_since(
                    self.validated_epoch
                )
                if member.size and unmirrored:
                    # churned slots cannot be reconciled while an
                    # UNMIRRORED batch is in flight: it may have placed
                    # onto a now-retired slot, and adopting host truth
                    # under it would desync the mirror. Once every
                    # in-flight batch has mirrored the carry equals the
                    # shadow and the adopt+scatter is exact, so the
                    # caller only needs to await mirrors (cheap), not a
                    # full drain.
                    return None
                nonmember = changed
                if member.size:
                    nonmember = np.setdiff1d(changed, member)
                if nonmember.size:
                    diff = ~np.all(
                        nt.allocatable[nonmember]
                        == self.alloc_shadow[nonmember],
                        axis=1,
                    )
                    alloc_rows = nonmember[diff]
                if member.size:
                    # membership rows always ride the static scatter:
                    # alloc content AND validity flip with slot identity
                    alloc_rows = np.union1d(alloc_rows, member)
                if (
                    not overlaid
                    and self.req_dev is not None
                    and self.req_shadow is not None
                ):
                    if member.size:
                        member_fix = self._adopt_membership_rows(
                            member, node_requested, node_nzr
                        )
                    ok, div_rows, keep = self._explain_rows(
                        nonmember, node_requested, node_nzr,
                        assumed_seq,
                    )
                    carry = "reuse" if ok else "diverged"
            static_full = (
                not layout_ok or alloc_rows.size > DELTA_ROW_BUCKET
            )
            fix_rows = empty
            diverged = carry == "diverged"
            if diverged:
                if (
                    not static_full
                    and div_rows.size <= DELTA_ROW_BUCKET
                    and keep == 0  # no pending delta touches a div row
                    and not unmirrored
                ):
                    # resolvable in place: with every in-flight batch
                    # mirrored the carry equals the shadow, so setting
                    # the divergent rows to host truth on device is
                    # exact even with commits still running -- the
                    # speculative chain's cheap rewind (a bind
                    # conflict / quota refund / conflict-requeue
                    # re-solves only against these patched rows)
                    fix_rows = div_rows
                else:
                    carry = "dead"  # resolve by full upload (or drain)
            didx_rows = member_fix
            if fix_rows.size:
                didx_rows = np.union1d(member_fix, fix_rows)
            if didx_rows.size > DELTA_ROW_BUCKET:
                # too many row patches: full upload. `diverged` keeps
                # its value -- a genuine divergence resolved by this
                # upload must still be counted, even when the overflow
                # came from the membership rows
                carry = "dead"
                fix_rows = empty
                didx_rows = empty
            reusable = not static_full and (
                carry == "reuse" or fix_rows.size > 0
            )
            if in_flight and not reusable:
                # the device carry is ahead of the host by the in-flight
                # placements; uploading host state now would re-place
                # them. Land everything first, then redo the dispatch.
                return None
            if reusable:
                # the fix path requires an empty ring, so keep is only
                # meaningful (a match depth) on the pure-reuse path
                for _ in range(len(self.pending_deltas) - (keep or 0)):
                    self.pending_deltas.popleft()
                if alloc_rows.size:
                    self.alloc_shadow[alloc_rows] = nt.allocatable[alloc_rows]
                    if self.valid_shadow is not None:
                        self.valid_shadow[alloc_rows] = nt.valid[alloc_rows]
                if fix_rows.size:
                    self.req_shadow[fix_rows] = node_requested[fix_rows]
                    self.nzr_shadow[fix_rows] = node_nzr[fix_rows]
                    self.carry_divergences += 1
                    metrics.carry_divergences.inc()
                self.validated_epoch = d.epoch
                return Handshake(
                    static_ok=True,
                    carry_ok=True,
                    fix_rows=didx_rows,
                    alloc_rows=alloc_rows,
                    member_rows=int(member.size),
                    capacity=int(nt.capacity),
                    carry_in=(
                        None if didx_rows.size
                        else (self.req_dev, self.nzr_dev)
                    ),
                    # the expected deltas diverged under an active
                    # speculative chain and the carry was repaired
                    # in place: the cheap rewind, not a drain
                    row_patch_rewind=bool(fix_rows.size) and in_flight,
                )
            # upload path
            if diverged:
                self.carry_divergences += 1
                metrics.carry_divergences.inc()
            static_ok = not static_full and alloc_rows.size == 0
            if not static_ok:
                self.layout_epoch = (
                    d.layout_epoch if d is not None else -1
                )
                self.alloc_shadow = nt.allocatable.copy()
                self.valid_shadow = np.array(nt.valid, dtype=bool)
            self.req_shadow = node_requested.copy()
            self.nzr_shadow = node_nzr.copy()
            self.pending_deltas.clear()
            self.validated_epoch = d.epoch if d is not None else -1
            return Handshake(
                static_ok=static_ok, carry_ok=False,
                capacity=int(nt.capacity), fix_rows=empty, alloc_rows=empty,
            )

    def operands(self, hs: Handshake):
        """The resident ``(alloc, valid, req, nzr)`` a solve may trust
        under ``hs``; None for what rides the upload buffer, so the jit
        sees one stable signature per layout (a stale device ref would
        fork a needless compile variant)."""
        with self._lock:
            return (
                self.alloc_dev if hs.static_ok else None,
                self.valid_dev if hs.static_ok else None,
                self.req_dev if hs.carry_ok else None,
                self.nzr_dev if hs.carry_ok else None,
            )

    # -- the three outcomes of a negotiated dispatch -------------------------

    def landed(self, hs: Handshake, resident, overlaid: bool) -> bool:
        """A jitted solve LANDED: the decided upload / scatter is
        established device state, booked here, in the internal counters
        and the (monotonic) Prometheus series. ``resident`` is the
        solve's returned ``(req, nzr, alloc, valid)`` refs. Returns
        True when a full carry upload landed (a lost device is rebuilt
        by exactly that)."""
        req_out, nzr_out, alloc_out, valid_out = resident
        with self._lock:
            if hs.carry_ok:
                self.state_reuses += 1
                self.delta_rows_uploaded += hs.delta_rows
                self.membership_row_patches += hs.member_rows
                if hs.delta_rows:
                    metrics.delta_rows_uploaded.inc(hs.delta_rows)
            else:
                self.state_uploads += 1
                metrics.state_uploads.inc()
            if not hs.static_ok or hs.alloc_rows.size:
                # a full static upload, or the in-buffer scatter patched
                # the resident alloc (and, for membership churn, the
                # valid mask): keep the returned refs
                self.alloc_dev, self.valid_dev = alloc_out, valid_out
            if overlaid:
                self._invalidate()
            else:
                self.req_dev, self.nzr_dev = req_out, nzr_out
        return not hs.carry_ok

    def _static_never_landed(self, hs: Handshake) -> None:
        """Under the lock, no jitted solve having run: an alloc row
        patch / full static upload never reached the device but the
        shadow already claims it. Drop the resident alloc so the next
        dispatch re-uploads instead of trusting it."""
        if hs.alloc_rows.size or not hs.static_ok:
            self.alloc_dev = None
            self.valid_dev = None

    def host_solved(
        self, hs: Handshake, assignments, req, nzr, overlaid: bool
    ) -> None:
        """The host tier solved from host state and no jitted solve
        ran: no upload / row scatter happened, so none is booked. A
        negotiated reuse still counts as one, as it always has."""
        with self._lock:
            if hs.carry_ok:
                self.state_reuses += 1
            self._static_never_landed(hs)
            if (
                hs.carry_ok
                and not hs.fix_rows.size
                and not overlaid
                and self.req_dev is not None
            ):
                # the host tier was only offered with nothing in
                # flight and a validated carry, so its input
                # state EQUALS the device carry: scatter-add its
                # own assignment output onto the resident state
                # (ops/assignment.apply_assignment_delta) and
                # keep the carry warm instead of dropping it
                self.req_dev, self.nzr_dev = apply_assignment_delta(
                    self.req_dev, self.nzr_dev,
                    np.asarray(assignments, dtype=np.int32),
                    req, nzr,
                )
            else:
                self._invalidate()

    def nothing_landed(self, hs: Handshake) -> None:
        """The ladder is exhausted: no solve of any tier landed, so
        nothing is booked (a drain-and-redispatch books the batch when
        it lands) and the carry, whose shadows were reconciled for a
        dispatch that never happened, drops."""
        with self._lock:
            self._invalidate()
            self._static_never_landed(hs)

    # -- commit time ---------------------------------------------------------

    def mirror(
        self, record: MutableMapping, assignments, b, req, nzr,
        overlaid: bool,
    ) -> int:
        """A batch commits: mirror its own placements into the running
        expectation (same int32 arithmetic as the scan carry) and
        remember the per-row delta: the handshake subtracts it while
        the host cache still trails this commit. O(B*R) in-place; the
        compact+scatter hot loop runs in native _hotpath.c
        (mirror_scatter; numpy twin behind KTPU_NATIVE_INGEST=0,
        differentially tested). Returns the mirror sequence number.

        The audit race-detector: a commit moving the shadow (or
        landing a batch) invalidates any checksum window spanning this
        moment. ``mirrored`` marks ``record`` as past the
        shadow-mutation point -- the under-load audit compares the
        first unmirrored record's carry_in against the shadows, so the
        flag must flip under the same lock as the mirror."""
        with self._lock:
            self.seq += 1
            record["mirrored"] = True
            if not overlaid and self.req_shadow is not None:
                delta = _mirror_scatter(
                    assignments, b, req, nzr,
                    self.req_shadow, self.nzr_shadow,
                )
                if delta is not None:
                    self.pending_deltas.append((*delta, self.seq))
            return self.seq

    # -- carry integrity audit -----------------------------------------------

    def audit(
        self, in_flight: Callable[[], Tuple[int, Optional[Mapping]]]
    ) -> str:
        """One carry-integrity sweep: checksum the device-resident
        req/nzr (and alloc/valid when resident) against the host shadow
        with two cheap on-device int32 reductions per array; the full
        [N, R] download happens only on mismatch. Corruption heals
        through the counted-upload path (carry drop -> next dispatch
        re-uploads), never silently. Safe to call from any thread.

        ``in_flight`` is the pipeline's answer, asked under the lock:
        how many batches are in flight, and the first of them whose
        mirror has not landed.

        Returns the disposition: "idle" (nothing resident), "busy"
        (in-flight state with no auditable snapshot), "raced" (a
        dispatch/commit moved the state mid-sweep), "clean", or
        "mismatch" (healed).

        A SATURATED pipeline does not defer the audit to quiescence:
        while batches are in flight, the FIRST UNMIRRORED pending
        record's ``carry_in`` refs are audited instead of the live
        carry. Those refs are immutable device arrays (dispatch
        REASSIGNS ``req_dev``, never mutates it) snapshotting the
        device state that record's solve consumed -- which must equal
        the host shadows exactly until that record's own commit passes
        the shadow-mutation point (the mirror, flagged ``mirrored``
        under this lock), because the committer lands batches in FIFO
        order and the req/nzr shadows mutate ONLY at the mirror. The
        coarse ``committing`` flag is deliberately NOT the gate: the
        committer raises it the instant it grabs the head, long before
        the mirror (the whole device download sits between), and gating
        on it would answer "busy" for nearly every sweep under
        saturation. Staleness is therefore bounded by pipeline depth,
        not by the arrival rate ever pausing: corruption stamped into
        the newest resident carry is seen when the batch that consumed
        it reaches the front of the unmirrored window, at most
        MAX_INFLIGHT commits later. Only req/nzr are audited under
        load (the alloc row patch CAN land on the resident alloc while
        batches are in flight); "busy" remains only for windows whose
        front record has no carry reuse (cold uploads, row-fix
        dispatches) or whose every record has already mirrored."""
        under_load = False
        head = None
        seq = 0
        alloc_dev = valid_dev = None
        shadow_ref = None
        with self._lock:
            if self.req_dev is None or self.req_shadow is None:
                metrics.carry_audit_sweeps.inc(disposition="idle")
                return "idle"
            pending, head = in_flight()
            if pending:
                carry = (
                    head.get("carry_in") if head is not None else None
                )
                if head is None or carry is None:
                    metrics.carry_audit_sweeps.inc(disposition="busy")
                    return "busy"
                under_load = True
                shadow_ref = self.req_shadow
                req_dev, nzr_dev = carry
            else:
                seq = self.seq
                req_dev, nzr_dev = self.req_dev, self.nzr_dev
                alloc_dev, valid_dev = self.alloc_dev, self.valid_dev
            # host checksums under the lock: the shadows mutate in
            # place at commit time
            host = {
                "req": _audit_checksum_host(self.req_shadow),
                "nzr": _audit_checksum_host(self.nzr_shadow),
            }
            if alloc_dev is not None and self.alloc_shadow is not None:
                host["alloc"] = _audit_checksum_host(self.alloc_shadow)
            if valid_dev is not None and self.valid_shadow is not None:
                host["valid"] = _audit_checksum_host(self.valid_shadow)
        self.audits += 1
        # device reductions OUTSIDE the lock (the refs are immutable
        # arrays; a racing dispatch reassigns, never mutates)
        dev_handles = {"req": _audit_checksum_dev(req_dev),
                       "nzr": _audit_checksum_dev(nzr_dev)}
        if "alloc" in host:
            dev_handles["alloc"] = _audit_checksum_dev(alloc_dev)
        if "valid" in host:
            dev_handles["valid"] = _audit_checksum_dev(valid_dev)
        dev = {
            name: (int(np.asarray(s)), int(np.asarray(ws)))
            for name, (s, ws) in dev_handles.items()
        }
        with self._lock:
            if under_load:
                # the snapshot is comparable until OUR record's mirror
                # lands (the only in-order in-place writer of the
                # req/nzr shadows) or a cold upload reassigns the
                # shadow arrays -- both happen under this lock, so
                # either landing mid-reduction is caught here. The
                # coarse ``committing`` flag is irrelevant: the whole
                # download phase is audit-safe.
                raced = (
                    head.get("mirrored")
                    or self.req_shadow is not shadow_ref
                )
            else:
                raced = (
                    self.seq != seq
                    or in_flight()[0]
                    or self.req_dev is not req_dev
                )
            if raced:
                metrics.carry_audit_sweeps.inc(disposition="raced")
                return "raced"
            mismatched = [n for n in dev if dev[n] != host[n]]
            if not mismatched:
                metrics.carry_audit_sweeps.inc(disposition="clean")
                return "clean"
            # full compare only on mismatch: name the divergent rows
            # for the flight record, then heal
            rows: List[int] = []
            try:
                for name, arr, shadow in (
                    ("req", req_dev, self.req_shadow),
                    ("nzr", nzr_dev, self.nzr_shadow),
                ):
                    if name in mismatched:
                        diff = ~np.all(np.asarray(arr) == shadow, axis=1)
                        rows = np.flatnonzero(diff)[:16].tolist()
                        break
            except Exception:  # noqa: BLE001 - row detail is best-effort
                logger.exception("carry audit row compare failed")
            for name in mismatched:
                metrics.carry_audit_mismatches.inc(array=name)
            flightrecorder.mark(
                "carry_audit", arrays=",".join(sorted(mismatched)),
                rows=rows, in_flight=in_flight()[0],
            )
            if "req" in mismatched or "nzr" in mismatched:
                self._invalidate()
            if "alloc" in mismatched or "valid" in mismatched:
                self.alloc_dev = None
                self.valid_dev = None
            metrics.carry_audit_heals.inc()
            self.audit_heals += 1
        metrics.carry_audit_sweeps.inc(disposition="mismatch")
        logger.warning(
            "carry integrity audit: device-resident %s diverged from "
            "the host shadow (rows %s); healed via the counted-upload "
            "path", ",".join(sorted(mismatched)), rows,
        )
        return "mismatch"

    def corrupt_row(self) -> None:
        """CARRY_CORRUPT fired: flip bits in one device-resident carry
        row WITHOUT touching the host shadow -- silent corruption only
        the integrity audit can see (the generation handshake compares
        host state against the shadow, never the device)."""
        inj = get_injector()
        with self._lock:
            if self.req_dev is None:
                return
            n = int(self.req_dev.shape[0])
            if n == 0:
                return
            fired = (
                inj.fired_count(FaultPoint.CARRY_CORRUPT)
                if inj is not None else 1
            )
            row = (fired * 131) % n
            self.req_dev = self.req_dev.at[row, 0].add(1 << 20)
        flightrecorder.mark("carry_corrupt", row=row)
        logger.warning(
            "injected carry corruption on resident row %d", row
        )
