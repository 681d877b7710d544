"""Unit tests for the robustness subsystem (kubernetes_tpu/robustness/):
fault injector determinism, circuit-breaker state machine, watchdog,
retry policy, host-greedy tier parity, informer relist, and the config
surface."""

import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.robustness.circuit import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    RetryPolicy,
    SolveTimeout,
    Watchdog,
)
from kubernetes_tpu.robustness.faults import (
    FaultInjected,
    FaultInjector,
    FaultPoint,
    FaultProfile,
    PointConfig,
    builtin_profiles,
    get_injector,
    install_injector,
    load_profile,
)
from kubernetes_tpu.robustness.ladder import (
    LadderExhausted,
    RobustnessConfig,
    SolverLadder,
    TIER_HOST_GREEDY,
    TIER_XLA,
    host_greedy_assign,
)


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    install_injector(None)


class TestFaultInjector:
    def test_deterministic_per_seed(self):
        prof = FaultProfile(
            "t", seed=7,
            points={FaultPoint.DEVICE_SOLVE: PointConfig(rate=0.5)},
        )
        a = [
            FaultInjector(prof).should_fire(FaultPoint.DEVICE_SOLVE)
            for _ in range(1)
        ]
        seq1 = [
            x for inj in [FaultInjector(prof)]
            for x in [
                inj.should_fire(FaultPoint.DEVICE_SOLVE) for _ in range(50)
            ]
        ]
        seq2 = [
            x for inj in [FaultInjector(prof)]
            for x in [
                inj.should_fire(FaultPoint.DEVICE_SOLVE) for _ in range(50)
            ]
        ]
        assert seq1 == seq2
        assert any(seq1) and not all(seq1)

    def test_max_fires_bounds_the_burst(self):
        prof = FaultProfile(
            "t", seed=0,
            points={
                FaultPoint.DEVICE_SOLVE: PointConfig(rate=1.0, max_fires=3)
            },
        )
        inj = FaultInjector(prof)
        fired = sum(
            inj.should_fire(FaultPoint.DEVICE_SOLVE) for _ in range(10)
        )
        assert fired == 3
        assert inj.fired_count(FaultPoint.DEVICE_SOLVE) == 3

    def test_raise_maybe(self):
        prof = FaultProfile(
            "t", points={FaultPoint.BIND_CONFLICT: PointConfig(rate=1.0)}
        )
        with pytest.raises(FaultInjected):
            FaultInjector(prof).raise_maybe(FaultPoint.BIND_CONFLICT)

    def test_unconfigured_point_never_fires(self):
        inj = FaultInjector(FaultProfile("t"))
        assert not any(
            inj.should_fire(FaultPoint.DEVICE_SOLVE) for _ in range(100)
        )

    def test_corrupt_assignments_flags_out_of_range(self):
        prof = FaultProfile(
            "t", points={FaultPoint.SOLVE_GARBAGE: PointConfig(rate=1.0)}
        )
        a = np.arange(6, dtype=np.int32)
        out = FaultInjector(prof).corrupt_assignments_maybe(
            FaultPoint.SOLVE_GARBAGE, a
        )
        assert (out != a).any()
        assert (out >= 6).any() or (out < -1).any()

    def test_global_install(self):
        assert get_injector() is None
        inj = FaultInjector(FaultProfile("t"))
        install_injector(inj)
        assert get_injector() is inj
        install_injector(None)
        assert get_injector() is None

    def test_builtin_profiles_load(self):
        for name in builtin_profiles():
            p = load_profile(name, seed=3)
            assert p.seed == 3
        with pytest.raises(KeyError):
            load_profile("no-such-profile")


class TestCircuitBreaker:
    def test_full_cycle(self):
        now = [0.0]
        br = CircuitBreaker(
            "xla", failure_threshold=2, cooloff_seconds=5.0,
            probe_batches=1, clock=lambda: now[0],
        )
        assert br.state == CLOSED and br.allow()
        br.record_failure()
        assert br.state == CLOSED
        br.record_failure()
        assert br.state == OPEN and not br.allow()
        now[0] = 5.1
        assert br.state == HALF_OPEN
        assert br.allow()  # the probe
        assert not br.allow()  # only probe_batches probes admitted
        br.record_success()
        assert br.state == CLOSED and br.allow()

    def test_failed_probe_reopens(self):
        now = [0.0]
        br = CircuitBreaker(
            "xla", failure_threshold=1, cooloff_seconds=1.0,
            clock=lambda: now[0],
        )
        br.record_failure()
        assert br.state == OPEN
        now[0] = 1.5
        assert br.allow()
        br.record_failure()
        assert br.state == OPEN and not br.allow()

    def test_success_resets_consecutive_count(self):
        br = CircuitBreaker("xla", failure_threshold=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == CLOSED

    def test_force_open(self):
        br = CircuitBreaker("xla", failure_threshold=99)
        br.force_open()
        assert br.state == OPEN


class TestWatchdog:
    def test_fast_call_passes_through(self):
        assert Watchdog().call(lambda: 42, timeout=5.0) == 42

    def test_timeout_raises(self):
        wd = Watchdog()
        t0 = time.monotonic()
        with pytest.raises(SolveTimeout):
            wd.call(lambda: time.sleep(2.0), timeout=0.1, tier="xla")
        assert time.monotonic() - t0 < 1.0

    def test_exception_relayed(self):
        with pytest.raises(ValueError):
            Watchdog().call(
                lambda: (_ for _ in ()).throw(ValueError("boom")),
                timeout=5.0,
            )

    def test_no_timeout_runs_on_caller_thread(self):
        tid = []
        Watchdog().call(
            lambda: tid.append(threading.get_ident()), timeout=0
        )
        assert tid == [threading.get_ident()]

    def test_compile_time_does_not_count_against_the_deadline(self):
        """A cold compile inside the guarded call (JAX fires trace-end,
        then backend-compile-end, on the compiling thread) extends the
        deadline by its own length; only non-compile time times out."""
        import jax.monitoring as mon

        def compile_for(seconds):
            mon.record_event_duration_secs(
                "/jax/core/compile/jaxpr_trace_duration", 0.0
            )
            time.sleep(seconds)
            mon.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", seconds
            )

        wd = Watchdog()
        assert wd.call(
            lambda: (compile_for(0.5), "solved")[1], timeout=0.2
        ) == "solved"
        with pytest.raises(SolveTimeout):
            wd.call(
                lambda: (compile_for(0.3), time.sleep(1.0)), timeout=0.2
            )


class TestRetryPolicy:
    def test_exponential_backoff_capped(self):
        p = RetryPolicy(
            max_attempts=5, backoff_seconds=0.1, backoff_multiplier=2.0,
            max_backoff_seconds=0.3,
        )
        assert p.backoff_for_attempt(1) == pytest.approx(0.1)
        assert p.backoff_for_attempt(2) == pytest.approx(0.2)
        assert p.backoff_for_attempt(3) == pytest.approx(0.3)
        assert p.backoff_for_attempt(9) == pytest.approx(0.3)


class TestSolverLadder:
    def _ladder(self, **kw):
        kw.setdefault("solve_timeout_seconds", 2.0)
        kw.setdefault("cooloff_seconds", 0.2)
        kw.setdefault("failure_threshold", 1)
        kw.setdefault("retry", RetryPolicy(max_attempts=1))
        kw.setdefault("sleep", lambda s: None)
        return SolverLadder(RobustnessConfig(**kw))

    def test_first_tier_wins(self):
        lad = self._ladder()
        tier, out = lad.run([(TIER_XLA, lambda: "ok")])
        assert (tier, out) == (TIER_XLA, "ok")
        assert lad.solves_by_tier[TIER_XLA] == 1

    def test_steps_down_on_error(self):
        lad = self._ladder()

        def boom():
            raise RuntimeError("device down")

        tier, out = lad.run(
            [(TIER_XLA, boom), (TIER_HOST_GREEDY, lambda: "host")]
        )
        assert (tier, out) == (TIER_HOST_GREEDY, "host")
        assert lad.breakers[TIER_XLA].state == OPEN

    def test_open_breaker_skips_tier(self):
        lad = self._ladder()
        lad.breakers[TIER_XLA].force_open()
        calls = []

        def never():
            calls.append(1)
            return "x"

        tier, _ = lad.run(
            [(TIER_XLA, never), (TIER_HOST_GREEDY, lambda: "host")]
        )
        assert tier == TIER_HOST_GREEDY and not calls

    def test_exhaustion_raises(self):
        lad = self._ladder()

        def boom():
            raise RuntimeError("down")

        with pytest.raises(LadderExhausted):
            lad.run([(TIER_XLA, boom)])

    def test_retry_in_place_before_stepping_down(self):
        lad = self._ladder(retry=RetryPolicy(max_attempts=3))
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise RuntimeError("transient")
            return "ok"

        tier, out = lad.run([(TIER_XLA, flaky)])
        assert out == "ok" and len(attempts) == 3
        assert lad.breakers[TIER_XLA].state == CLOSED

    def test_timeout_force_opens_and_steps_down(self):
        lad = self._ladder(solve_timeout_seconds=0.1)
        tier, out = lad.run(
            [
                (TIER_XLA, lambda: time.sleep(1.0) or "late"),
                (TIER_HOST_GREEDY, lambda: "host"),
            ]
        )
        assert (tier, out) == (TIER_HOST_GREEDY, "host")
        assert lad.breakers[TIER_XLA].state == OPEN

    def test_breaker_closes_after_cooloff_probe(self):
        lad = self._ladder(cooloff_seconds=0.05)

        def boom():
            raise RuntimeError("down")

        lad.run([(TIER_XLA, boom), (TIER_HOST_GREEDY, lambda: "h")])
        assert lad.breakers[TIER_XLA].state == OPEN
        time.sleep(0.1)
        tier, _ = lad.run(
            [(TIER_XLA, lambda: "back"), (TIER_HOST_GREEDY, lambda: "h")]
        )
        assert tier == TIER_XLA
        assert lad.breakers[TIER_XLA].state == CLOSED


class TestHonestTiers:
    """A Pallas failure is never re-run in place under the pallas name:
    solve_packed raises, the ladder steps down, and the batch is booked
    under the tier that produced its answer."""

    @staticmethod
    def _pieces(n=128, b=64, r=4):
        rng = np.random.default_rng(0)
        alloc = np.zeros((n, r), np.int32)
        alloc[:, 0] = 8000
        alloc[:, 1] = 16 << 20
        alloc[:, 3] = 110
        req = np.zeros((b, r), np.int32)
        req[:, 0] = rng.choice([100, 500], b)
        req[:, 3] = 1
        return [
            ("req", req),
            ("nzr", req[:, :2].copy()),
            ("midx", np.zeros(b, np.int32)),
            ("active", np.ones(b, np.int32)),
            ("rows", np.ones((8, n), np.int32)),
            ("alloc", alloc),
            ("valid", np.ones(n, np.int32)),
            ("req_state", np.zeros((n, r), np.int32)),
            ("nzr_state", np.zeros((n, 2), np.int32)),
        ]

    def test_solve_packed_raises_when_the_pallas_path_throws(
        self, monkeypatch
    ):
        from kubernetes_tpu.ops import assignment

        pieces = self._pieces()
        # off-TPU the compiled kernel cannot lower: forcing the
        # candidate predicate on makes the Pallas path throw for real
        monkeypatch.setattr(
            assignment, "pallas_candidate", lambda *a, **k: True
        )
        with pytest.raises(Exception, match="interpret mode"):
            assignment.solve_packed(pieces, None, None, None, None)
        # the same call with the ladder's xla-tier switch solves
        out = assignment.solve_packed(
            pieces, None, None, None, None, allow_pallas=False
        )
        assert (np.asarray(out[0]) >= 0).all()

    def test_ladder_books_the_batch_under_xla(self, monkeypatch):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.informer import InformerFactory
        from kubernetes_tpu.ops import assignment
        from kubernetes_tpu.scheduler.scheduler import new_scheduler
        from kubernetes_tpu.testing import make_node, make_pod
        from kubernetes_tpu.utils import metrics

        monkeypatch.setattr(
            assignment, "pallas_candidate", lambda *a, **k: True
        )
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(
            client, informers, batch=True, max_batch=64,
            robustness_config=RobustnessConfig(
                retry=RetryPolicy(max_attempts=1),
            ),
        )
        for i in range(8):
            client.create_node(
                make_node(f"node-{i}")
                .capacity(cpu="8", memory="16Gi", pods=110).obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        fallbacks0 = metrics.solver_fallbacks.value(
            tier="xla", reason="pallas_error"
        )
        try:
            for i in range(16):
                client.create_pod(
                    make_pod(f"p-{i}").container(cpu="100m").obj()
                )
            deadline = time.time() + 10
            while (
                sched.queue.active_count() < 16 and time.time() < deadline
            ):
                time.sleep(0.01)
            assert sched.schedule_batch(timeout=1.0) == 16
            sched.wait_for_inflight_binds(timeout=30)
        finally:
            sched.stop()
            informers.stop()
        tiers = sched.ladder.solves_by_tier
        assert tiers["pallas"] == 0 and tiers["xla"] == 1, tiers
        assert metrics.solver_fallbacks.value(
            tier="xla", reason="pallas_error"
        ) == fallbacks0 + 1
        bound = [p for p in client.list_pods()[0] if p.spec.node_name]
        assert len(bound) == 16


class TestPallasCanary:
    """A compiled kernel is held to the XLA scan at warm-up before the
    run loop trusts it (BatchScheduler._pallas_canary): on the chip one
    constrained specialization compiled fine and placed wrongly."""

    def test_disagreeing_specialization_loses_its_tier(self, monkeypatch):
        import jax

        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.informer import InformerFactory
        from kubernetes_tpu.ops import assignment
        from kubernetes_tpu.ops import pallas_constrained, pallas_solver
        from kubernetes_tpu.scheduler.scheduler import new_scheduler
        from kubernetes_tpu.testing import make_node
        from kubernetes_tpu.utils import metrics

        # stand-ins for the compiled kernels (a CPU cannot lower them):
        # the greedy one is right, the constrained one is wrong for the
        # spread+affinity specialization only
        bad_caps = pallas_constrained.live_caps(True, True, False)

        def fake_constrained(*args, config, caps=None, **_kw):
            a, req, nzr = assignment.greedy_assign_constrained(
                *args, config=config
            )
            if caps == bad_caps:
                a = jax.numpy.where(a >= 0, 0, a)
            return a, req, nzr

        # traces of the REAL kernels (TestHonestTiers) must not be reused
        jax.clear_caches()
        monkeypatch.setattr(assignment, "_PALLAS_DISTRUST", set())
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            pallas_solver, "pallas_greedy_solve",
            assignment.greedy_assign_compact,
        )
        monkeypatch.setattr(
            pallas_constrained, "pallas_constrained_solve", fake_constrained
        )
        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        sched = new_scheduler(client, informers, batch=True, max_batch=64)
        for i in range(48):
            client.create_node(
                make_node(f"node-{i}")
                .capacity(cpu="8", memory="16Gi", pods=110).obj()
            )
        informers.start()
        informers.wait_for_cache_sync()
        before = metrics.solver_fallbacks.value(
            tier="xla", reason="pallas_canary_mismatch"
        )
        try:
            sched.warmup()
            nt = sched.tensor_cache.update(sched.algorithm.snapshot)
            n, r = nt.capacity, nt.dims.num_dims
            assert assignment._PALLAS_DISTRUST == {("constrained", 64, n)}
            assert sched._device_tiers("constrained", 64, n, r, 8) == [
                "xla"
            ]
            # the kernel that agreed keeps its tier
            assert sched._device_tiers("greedy", 64, n, r, 8) == [
                "pallas", "xla",
            ]
            assert metrics.solver_fallbacks.value(
                tier="xla", reason="pallas_canary_mismatch"
            ) == before + 1
        finally:
            sched.stop()
            informers.stop()
            # the stand-ins were traced into the process-wide jit caches
            jax.clear_caches()


class TestHostGreedyParity:
    def test_matches_device_solver(self):
        """The host tier must replay the same placements as the
        unconstrained device scan (same fit, same scores, same
        lowest-index tie-break)."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.assignment import (
            GreedyConfig,
            greedy_assign_compact,
        )

        rng = np.random.default_rng(0)
        n, r, b_sz = 16, 5, 24
        allocatable = np.zeros((n, r), dtype=np.int32)
        allocatable[:, 0] = rng.integers(4000, 16000, n)  # mCPU
        allocatable[:, 1] = rng.integers(1 << 20, 1 << 22, n)  # KiB
        allocatable[:, 3] = 110  # pods
        requested = np.zeros_like(allocatable)
        nzr = np.zeros((n, 2), dtype=np.int32)
        valid = np.ones(n, dtype=bool)
        pod_req = np.zeros((b_sz, r), dtype=np.int32)
        pod_req[:, 0] = rng.integers(100, 2000, b_sz)
        pod_req[:, 1] = rng.integers(1 << 14, 1 << 17, b_sz)
        pod_req[:, 3] = 1
        pod_nzr = pod_req[:, :2].copy()
        mask_rows = np.ones((2, n), dtype=bool)
        mask_rows[1, : n // 2] = False
        mask_index = rng.integers(0, 2, b_sz).astype(np.int32)
        active = np.ones(b_sz, dtype=bool)
        active[-2:] = False

        cfg = GreedyConfig()
        dev_a, dev_req, dev_nzr = greedy_assign_compact(
            jnp.asarray(allocatable), jnp.asarray(requested),
            jnp.asarray(nzr), jnp.asarray(valid), jnp.asarray(pod_req),
            jnp.asarray(pod_nzr), jnp.asarray(mask_rows),
            jnp.asarray(mask_index), jnp.asarray(active), config=cfg,
        )
        host_a, host_req, host_nzr = host_greedy_assign(
            allocatable, requested, nzr, valid, pod_req, pod_nzr,
            mask_rows, mask_index, active, config=cfg,
        )
        np.testing.assert_array_equal(np.asarray(dev_a), host_a)
        np.testing.assert_array_equal(np.asarray(dev_req), host_req)
        np.testing.assert_array_equal(np.asarray(dev_nzr), host_nzr)


class TestInformerRelist:
    def test_relist_reconverges_after_drop(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.client import Client
        from kubernetes_tpu.client.informer import InformerFactory
        from kubernetes_tpu.testing import make_pod
        from kubernetes_tpu.utils import metrics

        server = APIServer()
        client = Client(server)
        informers = InformerFactory(server)
        inf = informers.pods()
        client.create_pod(make_pod("a").container(cpu="1").obj())
        inf.pump()
        assert len(inf.list()) == 1
        # fire a guaranteed watch drop: events created while the stream
        # is down must still converge via the relist diff
        client.create_pod(make_pod("b").container(cpu="1").obj())
        client.delete_pod("default", "a")
        before = metrics.watch_relists.value(kind="Pod")
        install_injector(FaultInjector(FaultProfile(
            "t", points={FaultPoint.WATCH_DROP: PointConfig(rate=1.0)},
        )))
        inf.pump()  # drop fires -> relist
        install_injector(None)
        assert metrics.watch_relists.value(kind="Pod") == before + 1
        names = {p.metadata.name for p in inf.list()}
        assert names == {"b"}
        # handlers saw the synthetic diff: one more pump stays converged
        inf.pump()
        assert {p.metadata.name for p in inf.list()} == {"b"}


class TestConfigSurface:
    def test_loader_parses_robustness_and_faults(self):
        from kubernetes_tpu.config.loader import load_config_from_dict

        cfg = load_config_from_dict({
            "robustness": {
                "solveTimeout": "30s",
                "failureThreshold": 5,
                "cooloff": "2s",
                "probeBatches": 2,
                "retryMaxAttempts": 4,
                "retryBackoff": "10ms",
            },
            "faultInjection": {
                "enabled": True,
                "profile": "chaos-default",
                "seed": 42,
                "points": {
                    "device_solve": {"rate": 0.5, "maxFires": 7},
                    "device_solve_hang": {
                        "rate": 0.1, "hangSeconds": "1500ms",
                    },
                },
            },
        })
        rb = cfg.robustness
        assert rb.solve_timeout_seconds == 30.0
        assert rb.failure_threshold == 5
        assert rb.cooloff_seconds == 2.0
        assert rb.probe_batches == 2
        assert rb.retry_max_attempts == 4
        assert rb.retry_backoff_seconds == pytest.approx(0.01)
        fi = cfg.fault_injection
        assert fi.enabled and fi.profile == "chaos-default"
        assert fi.seed == 42
        assert fi.points["device_solve"].rate == 0.5
        assert fi.points["device_solve"].max_fires == 7
        assert fi.points["device_solve_hang"].hang_seconds == 1.5
        # round-trips into the runtime objects
        rc = RobustnessConfig.from_configuration(rb)
        assert rc.retry.max_attempts == 4
        from kubernetes_tpu.robustness.faults import (
            injector_from_configuration,
        )

        inj = injector_from_configuration(fi)
        assert inj is not None
        assert inj.profile.points["device_solve"].rate == 0.5
        # profile points not overridden are kept
        assert FaultPoint.BIND_CONFLICT in inj.profile.points

    def test_validation_rejects_bad_knobs(self):
        from kubernetes_tpu.config.loader import load_config_from_dict
        from kubernetes_tpu.config.validation import validate_config

        cfg = load_config_from_dict({
            "robustness": {"failureThreshold": 0},
            "faultInjection": {
                "enabled": True,
                "profile": "not-a-profile",
                "points": {"bogus_point": {"rate": 2.0}},
            },
        })
        errors = validate_config(cfg)
        assert any("failureThreshold" in e for e in errors)
        assert any("not-a-profile" in e for e in errors)
        assert any("bogus_point" in e for e in errors)
        assert any("rate" in e for e in errors)

    def test_disabled_injection_returns_none(self):
        from kubernetes_tpu.config.loader import load_config_from_dict
        from kubernetes_tpu.robustness.faults import (
            injector_from_configuration,
        )

        cfg = load_config_from_dict({})
        assert injector_from_configuration(cfg.fault_injection) is None
