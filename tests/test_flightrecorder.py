"""Flight-recorder tracing plane (ISSUE 13): the batch span spine, the
chaos-reconstruction contract (the dump alone explains what happened,
no log parsing), Chrome-trace export, the P-squared live quantile
sketch, the jit-cache watchdog, and the <1% always-on overhead guard.
"""

import json
import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.robustness.circuit import RetryPolicy
from kubernetes_tpu.robustness.faults import (
    FaultInjector,
    FaultPoint,
    install_injector,
    load_profile,
)
from kubernetes_tpu.robustness.ladder import (
    RobustnessConfig,
    TIER_HOST_GREEDY,
    TIER_PALLAS,
    TIER_XLA,
)
from kubernetes_tpu.robustness.lifecycle import ClusterLifecycleDriver
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import flightrecorder, metrics
from kubernetes_tpu.utils.quantiles import P2Quantile, QuantileSet

DEVICE_TIERS = (TIER_PALLAS, TIER_XLA, TIER_HOST_GREEDY)


@pytest.fixture(autouse=True)
def _clean():
    flightrecorder.RECORDER.reset()
    yield
    install_injector(None)
    flightrecorder.ENABLED = True


def _mk_cluster(num_nodes=48, max_batch=128, retry_attempts=1):
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=True, max_batch=max_batch,
        robustness_config=RobustnessConfig(
            solve_timeout_seconds=5.0,
            failure_threshold=2,
            cooloff_seconds=0.3,
            probe_batches=1,
            # one attempt per tier: every injected solve fault becomes a
            # breaker-routed fallback instead of being absorbed by the
            # in-place retry, so the reconstruction claim is non-vacuous
            retry=RetryPolicy(
                max_attempts=retry_attempts, backoff_seconds=0.01,
                max_backoff_seconds=0.05,
            ),
        ),
    )
    for i in range(num_nodes):
        client.create_node(
            make_node(f"node-{i}")
            .capacity(cpu="32", memory="64Gi", pods=110)
            .obj()
        )
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    return server, client, informers, sched


def _wait_all_bound(client, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if pods and all(p.spec.node_name for p in pods):
            return True
        time.sleep(0.1)
    return False


# -- P-squared sketch ----------------------------------------------------

class TestP2Quantile:
    def test_rejects_degenerate_quantiles(self):
        with pytest.raises(ValueError):
            P2Quantile(0.0)
        with pytest.raises(ValueError):
            P2Quantile(1.0)

    def test_small_stream_is_exact(self):
        est = P2Quantile(0.5)
        for x in (3.0, 1.0, 2.0):
            est.observe(x)
        assert est.value() == 2.0

    @pytest.mark.parametrize("q", [0.5, 0.99])
    @pytest.mark.parametrize("dist", ["uniform", "lognormal"])
    def test_tracks_numpy_percentile(self, q, dist):
        rng = np.random.default_rng(42)
        if dist == "uniform":
            xs = rng.uniform(0.0, 1.0, size=20_000)
        else:
            # the latency-like shape: heavy right tail
            xs = rng.lognormal(mean=-2.0, sigma=0.7, size=20_000)
        est = P2Quantile(q)
        for x in xs:
            est.observe(float(x))
        exact = float(np.quantile(xs, q))
        spread = float(np.quantile(xs, 0.999)) - float(np.min(xs))
        # within 5% of the full spread (P2's documented regime for
        # unimodal streams; typically far closer)
        assert abs(est.value() - exact) <= 0.05 * spread

    def test_quantile_set_threadsafe_and_resettable(self):
        qs = QuantileSet((0.5, 0.99))
        threads = [
            threading.Thread(
                target=lambda: qs.observe_many([0.1] * 1000)
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert qs.count == 4000
        assert qs.value(0.5) == pytest.approx(0.1)
        qs.reset()
        assert qs.count == 0
        assert qs.value(0.99) == 0.0


# -- recorder core -------------------------------------------------------

class TestFlightRecorder:
    def test_span_ring_bounded_and_ids_monotonic(self):
        rec = flightrecorder.FlightRecorder(
            span_capacity=4, mark_capacity=4
        )
        for i in range(10):
            span = rec.begin_batch(i, pods=[(f"u{i}", 0.01, 1)])
            span.stage("pack", 0.001)
            span.finish(tier="xla")
            rec.mark("fault", point=f"p{i}")
        d = rec.dump()
        assert len(d["spans"]) == 4
        assert len(d["marks"]) == 4
        assert [s["batch_id"] for s in d["spans"]] == [7, 8, 9, 10]
        # every surviving mark is the newest four
        assert [m["point"] for m in d["marks"]] == [
            "p6", "p7", "p8", "p9"
        ]

    def test_dump_is_json_serializable(self):
        rec = flightrecorder.FlightRecorder()
        span = rec.begin_batch(2, pods=[("u1", 0.5, 3), ("u2", 0.0, 1)])
        span.note(carry="reuse", delta_rows=7, custom_field="x")
        span.bump("placed", 2)
        span.finish(tier="xla")
        rec.mark("breaker", tier="xla", from_state="closed",
                 to_state="open")
        parsed = json.loads(rec.dump_json())
        s = parsed["spans"][0]
        assert s["tier"] == "xla"
        assert s["carry"] == "reuse"
        assert s["placed"] == 2
        assert s["extra"] == {"custom_field": "x"}
        assert s["pods"][0] == {
            "uid": "u1", "queue_wait_ms": 500.0, "attempts": 3
        }
        assert parsed["marks"][0]["kind"] == "breaker"

    def test_disabled_returns_null_span(self):
        flightrecorder.ENABLED = False
        span = flightrecorder.begin_batch(5, pods=[("u", 0, 1)])
        assert not span  # falsy NullSpan
        span.stage("pack", 0.1)
        span.note(tier="xla")
        span.finish()
        before = len(flightrecorder.RECORDER.dump()["marks"])
        flightrecorder.mark("fault", point="x")
        assert len(flightrecorder.RECORDER.dump()["marks"]) == before
        flightrecorder.ENABLED = True

    def test_dump_to_file(self, tmp_path, monkeypatch):
        monkeypatch.setattr(flightrecorder, "DUMP_DIR", str(tmp_path))
        rec = flightrecorder.FlightRecorder()
        rec.begin_batch(1, pods=[]).finish(tier="xla")
        path = rec.dump_to_file("unit")
        with open(path) as f:
            assert json.load(f)["spans"][0]["tier"] == "xla"


# -- the spine on a real burst -------------------------------------------

class TestBatchSpanSpine:
    def test_burst_produces_linked_spans(self):
        server, client, informers, sched = _mk_cluster(
            num_nodes=16, max_batch=64, retry_attempts=3
        )
        sched.start()
        names = [f"sp-{i}" for i in range(150)]
        for n in names:
            client.create_pod(
                make_pod(n).container(cpu="100m", memory="128Mi").obj()
            )
        assert _wait_all_bound(client, 60)
        sched.wait_for_inflight_binds()
        sched.stop()
        informers.stop()

        d = flightrecorder.RECORDER.dump()
        solved = [
            s for s in d["spans"]
            if s["tier"] in DEVICE_TIERS and s["routed"] is None
        ]
        assert solved, "no device-tier spans recorded"
        # per-batch record: size, pad shape, carry decision, stage
        # timings, commit outcome
        placed_total = 0
        for s in solved:
            assert s["size"] > 0
            assert s["padded"] >= s["size"]
            assert s["carry"] in ("reuse", "scatter", "upload")
            assert "pack" in s["stages_ms"]
            assert "device_solve" in s["stages_ms"]
            assert "commit" in s["stages_ms"]
            assert s["t_end"] is not None
            placed_total += s["placed"]
        assert placed_total == len(names)
        # per-pod linkage: every created pod's uid joins to exactly one
        # solving batch (none were requeued in this clean burst)
        pods, _ = client.list_pods()
        uid_of = {p.metadata.name: p.metadata.uid for p in pods}
        seen = {}
        for s in solved:
            for link in s["pods"]:
                seen.setdefault(link["uid"], []).append(
                    (s["batch_id"], link["attempts"])
                )
        for n in names:
            assert uid_of[n] in seen, f"pod {n} not linked to a batch"
            assert seen[uid_of[n]][0][1] >= 1  # attempt count recorded
        # the first batch uploaded state; spans carry the decision
        assert any(s["carry"] == "upload" for s in solved)

    def test_jit_watch_counts_and_marks_recompiles(self, monkeypatch):
        from kubernetes_tpu.scheduler import batch as batch_mod

        sizes = {"solve_packed": 3}
        monkeypatch.setattr(
            "kubernetes_tpu.ops.assignment.jit_cache_sizes",
            lambda mesh=None: dict(sizes),
        )
        w = batch_mod._JitCacheWatch()
        before = metrics.jit_compiles.value(signature="solve_packed")
        w.refresh()  # warmup-era growth: counted, not marked
        assert (
            metrics.jit_compiles.value(signature="solve_packed")
            == before + 3
        )
        marks0 = [
            m for m in flightrecorder.RECORDER.dump()["marks"]
            if m["kind"] == "jit_recompile"
        ]
        assert not marks0
        w.seal()
        sizes["solve_packed"] = 5  # a mid-run recompile
        w.refresh()
        assert (
            metrics.jit_compiles.value(signature="solve_packed")
            == before + 5
        )
        marks = [
            m for m in flightrecorder.RECORDER.dump()["marks"]
            if m["kind"] == "jit_recompile"
        ]
        assert len(marks) == 1
        assert marks[0]["signature"] == "solve_packed"
        assert marks[0]["compiles"] == 2

    def test_live_quantile_gauges_track_bound_pods(self):
        metrics.pod_to_bind_sketch.reset()
        server, client, informers, sched = _mk_cluster(
            num_nodes=16, max_batch=64, retry_attempts=3
        )
        sched.start()
        for i in range(200):
            client.create_pod(
                make_pod(f"q-{i}").container(cpu="50m").obj()
            )
        assert _wait_all_bound(client, 60)
        sched.wait_for_inflight_binds()
        sched.stop()
        informers.stop()
        assert metrics.pod_to_bind_sketch.count == 200
        p50 = metrics.pod_to_bind_quantile.value(q="0.5")
        p99 = metrics.pod_to_bind_quantile.value(q="0.99")
        assert 0.0 < p50 <= p99 < 60.0
        # the gauges expose the sketch through the labeled-callback path
        lines = metrics.pod_to_bind_quantile.collect()
        assert any('q="0.99"' in ln for ln in lines if "#" not in ln)


# -- the acceptance e2e: chaos reconstruction from the dump alone --------

class TestChaosReconstruction:
    def test_lifecycle_chaos_reconstructs_from_dump(self):
        """Run the builtin lifecycle-chaos profile (hotter DEVICE_SOLVE
        sprinkle so breaker-routed fallbacks actually occur) with the
        lifecycle driver flapping nodes mid-burst, then reconstruct --
        from the flight-recorder dump ALONE, after a JSON round trip --
        every batch's solver tier, each breaker-routed fallback, and
        each fault point fired, asserted against the injector's own
        ledger and the ladder's tier counts. No log parsing."""
        server, client, informers, sched = _mk_cluster(
            num_nodes=32, max_batch=128, retry_attempts=1
        )
        # seed 3: the device_solve stream fires on its first three
        # draws, so even a small burst (few dispatches) sees faults
        profile = load_profile("lifecycle-chaos", seed=3)
        # hotter solver sprinkle: with 1 attempt/tier each fire IS a
        # breaker-routed fallback (fallback marks must be non-empty for
        # the reconstruction claim to mean anything)
        profile.points[FaultPoint.DEVICE_SOLVE].rate = 0.5
        profile.points[FaultPoint.DEVICE_SOLVE].max_fires = 6
        inj = FaultInjector(profile)
        install_injector(inj)

        fallbacks_before = dict(metrics.solver_fallbacks._values)
        tiers_before = dict(sched.ladder.solves_by_tier)

        drv = ClusterLifecycleDriver(
            client, injector=inj, tick_interval=0.1,
            flap_down_seconds=0.4, storm_fraction=0.1,
            storm_down_seconds=0.8,
        )
        sched.start()
        drv.start()
        names = [f"lc-{i}" for i in range(300)]
        try:
            for n in names:
                client.create_pod(
                    make_pod(n).container(cpu="250m", memory="256Mi")
                    .obj()
                )
            assert _wait_all_bound(client, 120), "burst did not bind"
        finally:
            drv.stop()
        assert _wait_all_bound(client, 60)
        sched.wait_for_inflight_binds()
        sched.stop()
        informers.stop()

        # the dump, through a JSON round trip: everything below reads
        # ONLY this document (plus the ledgers it is checked against)
        d = json.loads(flightrecorder.RECORDER.dump_json())

        # (1) every batch's solver tier: span counts per device tier
        # equal the ladder's own tally (delta over this test). A span
        # keeps its tier even when a LATER stage failed (garbage
        # download, recovery) -- the ladder counted that solve too, so
        # the join keys on tier alone.
        span_tiers = {}
        for s in d["spans"]:
            if s["tier"] in DEVICE_TIERS:
                span_tiers[s["tier"]] = span_tiers.get(s["tier"], 0) + 1
        for tier in DEVICE_TIERS:
            expect = (
                sched.ladder.solves_by_tier.get(tier, 0)
                - tiers_before.get(tier, 0)
            )
            assert span_tiers.get(tier, 0) == expect, (
                f"tier {tier}: {span_tiers.get(tier, 0)} spans vs "
                f"{expect} ladder solves"
            )
        assert sum(span_tiers.values()) > 0

        # (2) each breaker-routed fallback: marks per (tier, reason)
        # equal the metric delta
        fb_marks = {}
        for m in d["marks"]:
            if m["kind"] == "fallback":
                key = (m["tier"], m["reason"])
                fb_marks[key] = fb_marks.get(key, 0) + 1
        assert fb_marks, "chaos produced no fallbacks; tune the profile"
        seen_keys = set(fb_marks)
        for key, count in metrics.solver_fallbacks._values.items():
            labels = dict(key)
            k = (labels["tier"], labels["reason"])
            delta = count - fallbacks_before.get(key, 0.0)
            if delta:
                seen_keys.add(k)
        for k in seen_keys:
            key = (("reason", k[1]), ("tier", k[0]))
            delta = (
                metrics.solver_fallbacks._values.get(key, 0.0)
                - fallbacks_before.get(key, 0.0)
            )
            assert fb_marks.get(k, 0) == delta, (
                f"fallback {k}: {fb_marks.get(k, 0)} marks vs "
                f"{delta} metric"
            )

        # (3) each fault point fired: marks per point equal the
        # injector's OWN ledger, for every point
        fault_marks = {}
        for m in d["marks"]:
            if m["kind"] == "fault":
                fault_marks[m["point"]] = (
                    fault_marks.get(m["point"], 0) + 1
                )
        for point in FaultPoint.ALL:
            assert fault_marks.get(point, 0) == inj.fired_count(point), (
                f"fault {point}: {fault_marks.get(point, 0)} marks vs "
                f"ledger {inj.fired_count(point)}"
            )
        assert fault_marks.get(FaultPoint.DEVICE_SOLVE, 0) > 0
        assert fault_marks.get(FaultPoint.NODE_FLAP, 0) > 0

        # and the chaos is attributable per batch: some span carries a
        # non-reuse carry decision (flaps forced membership patches or
        # uploads), and commit outcomes account every pod disposition
        assert any(s["carry"] != "reuse" for s in d["spans"] if s["carry"])


# -- the tier-1 overhead guard -------------------------------------------

class TestTraceOverheadGuard:
    def test_always_on_spine_under_one_percent(self):
        """Deterministic self-time bound: the recorder ops a real
        1k-pod burst performs, costed at the measured per-op rate, must
        stay under 1% of the burst's pop+pack+solve+download+commit
        wall clock. A batch is costed with every ``flightrecorder.stage``
        it passes through (total, ring and the profiler's annotation,
        which costs what it costs when no session runs), an informer
        frame with its one ``ingest`` stage, a mark with its zero-length
        annotation. (The microbench's wall-clock A/B rides in
        tools/bench_hotpath.py bench_trace_overhead; on a loaded 2-core
        box its noise floor is above a 1% effect, so the guard asserts
        the self-time share, which is stable.)"""
        from tools.bench_hotpath import (
            BATCH_STAGES,
            HOT_STAGES as HOT,
            _time_mark_ops,
            _time_span_ops,
        )

        server, client, informers, sched = _mk_cluster(
            num_nodes=64, max_batch=256, retry_attempts=3
        )
        spans_before = flightrecorder.RECORDER._next_id
        marks_before = len(flightrecorder.RECORDER.dump()["marks"])
        stage_before = dict(sched.stage_seconds)
        sched.start()
        for i in range(1000):
            client.create_pod(
                make_pod(f"ov-{i}").container(cpu="10m", memory="16Mi")
                .obj()
            )
        assert _wait_all_bound(client, 120)
        sched.wait_for_inflight_binds()
        sched.stop()
        informers.stop()
        after = sched.stage_seconds
        hot_s = sum(
            after.get(k, 0.0) - stage_before.get(k, 0.0) for k in HOT
        )
        n_spans = flightrecorder.RECORDER._next_id - spans_before
        n_marks = (
            len(flightrecorder.RECORDER.dump()["marks"]) - marks_before
        )
        calls = sched.stage_totals.calls()
        # one stage each: an informer frame, a collection, (set-up's
        # node adds) a call of a node handler, and a frame of the
        # broadcaster's events
        n_frames = (calls["ingest"] + calls.get("gc", 0)
                    + calls.get("node_event", 0) + calls.get("events", 0))
        assert n_spans > 0 and hot_s > 0 and n_frames > 0
        # every stage a batch passed through is among those costed
        assert set(calls) - {"ingest", "gc", "node_event", "events"} <= set(
            BATCH_STAGES
        )

        rec = flightrecorder.FlightRecorder()
        links = [(f"uid-{i}", 0.001, 1) for i in range(256)]
        span_us = min(
            _time_span_ops(rec, links, BATCH_STAGES, 1000)
            for _ in range(3)
        )
        stage_us = min(
            _time_span_ops(rec, [], ("ingest",), 5000) for _ in range(3)
        )
        mark_us = min(_time_mark_ops(rec, 5000) for _ in range(3))
        self_s = (
            n_spans * span_us + n_frames * stage_us
            + max(n_marks, 0) * mark_us
        ) / 1e6
        share = self_s / hot_s
        assert share < 0.01, (
            f"spine self-time {self_s * 1e3:.2f}ms is "
            f"{share * 100:.2f}% of {hot_s * 1e3:.0f}ms hot path"
        )
