"""chip_smoke.py off the chip: it refuses to run, and its phases --
driven here at a size a CPU finishes, expecting the only device tier a
CPU has -- still hold their checks, so the script cannot rot between
chip runs."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
import chip_smoke  # noqa: E402

SMALL = chip_smoke.Sizes(
    nodes=96, zones=4, burst_pods=600, max_batch=128, parity_pods=128,
    spread_apps=2, spread_per_app=24, anti_apps=2, anti_per_app=16,
    pool_nodes=16, high_pods=24, mesh_devices=4, mesh_pods=300,
    timeout_s=120,
)


def test_refuses_to_run_without_a_tpu(monkeypatch, capsys):
    from kubernetes_tpu.utils import compile_cache

    monkeypatch.setattr(
        compile_cache, "configure_compile_cache", lambda: ""
    )
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform: cpu" in out
    # no result line: nothing on stdout parses as the final JSON object
    for line in out.splitlines():
        assert not line.startswith("{"), line


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def _main_on_a_fake_tpu(monkeypatch, capsys, run):
    """``main`` with JAX reporting one TPU and ``run`` stubbed: what is
    under test is what it prints and returns, not the phases."""
    import jax

    from kubernetes_tpu.utils import compile_cache

    monkeypatch.setattr(
        compile_cache, "configure_compile_cache", lambda: ""
    )
    monkeypatch.setattr(jax, "devices", lambda: [_FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run", run)
    code = chip_smoke.main([])
    return code, capsys.readouterr().out.splitlines()


def test_last_line_is_exactly_ok_and_device(monkeypatch, capsys):
    code, lines = _main_on_a_fake_tpu(
        monkeypatch, capsys,
        lambda *a: {"warmup_s": 1.0, "plain": {"bound": 1}},
    )
    assert code == 0
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    # everything else the run learned rides the line before
    assert lines[-2].startswith("report: ")
    report = json.loads(lines[-2][len("report: "):])
    assert report["phases"] == {"plain": {"bound": 1}}
    assert report["warmup_s"] == 1.0 and "compile_cache" in report


def test_failed_phase_exits_nonzero_with_ok_false(monkeypatch, capsys):
    def run(*a):
        raise chip_smoke.SmokeFailure("plain: 9 of 10 pods bound")

    code, lines = _main_on_a_fake_tpu(monkeypatch, capsys, run)
    assert code != 0
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert "9 of 10" in lines[-2]


def test_single_device_phases_hold_their_checks():
    rng = np.random.default_rng(0)
    stack = chip_smoke.Stack(SMALL, expect_tier="xla")
    try:
        stack.warm_and_start()
        plain = chip_smoke.phase_plain(stack, rng)
        assert plain["bound"] == SMALL.burst_pods
        assert plain["tiers"]["xla"] > 0 and plain["pods_fallback"] == 0
        json.dumps(plain)

        # the tier check is what fails a run whose device path is hidden
        # (on the chip: KTPU_PALLAS=0)
        stack.expect_tier = "pallas"
        with pytest.raises(chip_smoke.SmokeFailure, match="tier pallas"):
            stack.check_tiers({}, "plain")
        stack.expect_tier = "xla"

        constrained = chip_smoke.phase_constrained(stack, rng)
        assert constrained["worst_zone_skew"] <= 1
        assert constrained["tiers"]["xla"] > 0

        preempt = chip_smoke.phase_preempt(stack, rng)
        assert preempt["high_priority_bound"] == SMALL.high_pods
        assert preempt["device_preemptions"] > 0
        assert preempt["evicted"] > 0
        json.dumps(preempt)

        # the capacity replay is independent of the scheduler's books:
        # a pod that asked for more than its node holds is caught
        victim = next(iter(stack.bound_pods()))
        stack.created[victim] = (chip_smoke.NODE_CPU_MILLI + 1, 0)
        with pytest.raises(chip_smoke.SmokeFailure, match="over allocatable"):
            stack.check_capacity("tampered")
    finally:
        stack.stop()


def test_mesh_phase_holds_its_checks():
    import jax

    if len(jax.devices()) < SMALL.mesh_devices:
        pytest.skip("needs 4 (virtual) devices")
    mesh = chip_smoke.phase_mesh(
        SMALL, np.random.default_rng(1), expect_tier="xla"
    )
    # the shard_map program ran (``tier``), and the ledger counts it on
    # the tier of what it ran without its kernel
    assert mesh["devices"] == 4 and mesh["tier"] == "pallas"
    assert mesh["tiers"]["pallas"] == 0 and mesh["tiers"]["xla"] > 0
    assert mesh["state_uploads"] <= 1 and mesh["carry_divergences"] == 0
    json.dumps(mesh)


def test_fewer_than_four_devices_is_not_a_failure(capsys):
    report = chip_smoke.run(SMALL, ["mesh"], seed=0, device_count=1)
    assert report["mesh"] == "not run (1 device)"
    assert "mesh: not run (1 device)" in capsys.readouterr().out
