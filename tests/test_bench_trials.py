"""Unit tests for bench.py's trial protocol helpers (the noise-robust
median headline; see the bench module docstring)."""

import importlib.util
import json
import os

import pytest

_BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py"
)
_spec = importlib.util.spec_from_file_location("bench_module", _BENCH_PATH)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _trial(n, pps, p99):
    return {
        "trial": n, "pods_per_sec": pps, "p99_pod_to_bind_ms": p99,
    }


def test_median_odd_count():
    trials = [_trial(1, 100.0, 50), _trial(2, 300.0, 20), _trial(3, 200.0, 30)]
    assert bench.pick_median_trial(trials)["trial"] == 3


def test_median_even_count_picks_conservative_middle():
    trials = [
        _trial(1, 100.0, 50), _trial(2, 400.0, 10),
        _trial(3, 200.0, 30), _trial(4, 300.0, 20),
    ]
    # lower middle of the throughput ranking: 200 pods/s
    assert bench.pick_median_trial(trials)["trial"] == 3


def test_median_single_trial():
    trials = [_trial(1, 123.0, 45)]
    assert bench.pick_median_trial(trials) is trials[0]


def test_noisy_outlier_cannot_move_headline():
    """The satellite's point: one noisy capture (slow trial, huge p99)
    must not become the recorded number."""
    trials = [
        _trial(1, 24000.0, 400.0),
        _trial(2, 5000.0, 900.0),  # driver hiccup
        _trial(3, 24500.0, 390.0),
    ]
    med = bench.pick_median_trial(trials)
    assert med["trial"] == 1
    assert med["p99_pod_to_bind_ms"] < 500


def test_trials_flag_defaults():
    import argparse

    ap = argparse.ArgumentParser()
    # mirror of bench.main's registration: default 3 measured trials
    ap.add_argument("--trials", type=int, default=3)
    assert ap.parse_args([]).trials == 3


# -- exit codes and run context (ISSUE 21) ---------------------------------


@pytest.fixture
def no_cache_config(monkeypatch):
    """bench.main places the persistent compile cache; keep the test
    session's own compile configuration untouched."""
    from kubernetes_tpu.utils import compile_cache

    monkeypatch.setattr(
        compile_cache, "configure_compile_cache", lambda: ""
    )


def _last_payload(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_incomplete_burst_exits_nonzero(monkeypatch, capsys, no_cache_config):
    """A burst that does not fully schedule ends the process non-zero,
    and even the error payload names the device and the tier ledger."""
    monkeypatch.setenv("BENCH_NODES", "8")
    monkeypatch.setenv("BENCH_PODS", "16")
    monkeypatch.setenv("BENCH_BATCH", "64")

    def incomplete(sched, client, server, num_pods, trial):
        raise AssertionError(
            f"only 3/{num_pods} pods scheduled in trial {trial}"
        )

    monkeypatch.setattr(bench, "run_burst_trial", incomplete)
    assert bench.main(["--trials", "1"]) == 1
    payload = _last_payload(capsys)
    assert payload["error"] == "only 3/16 pods scheduled in trial 0"
    assert payload["platform"] == "cpu"
    assert payload["device_kind"] and payload["device_count"] >= 1
    assert set(payload["solves_by_tier"]) == {
        "pallas", "xla", "host_greedy", "sequential",
    }
    # the warm burst did solve, on the only tier a CPU has
    assert payload["solves_by_tier"]["xla"] > 0
    assert payload["native_hotpath"] is True


def test_no_tpu_is_refused_unless_cpu_is_asked_for_by_name(
    monkeypatch, capsys, no_cache_config
):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main(["--trials", "1"]) == 1
    payload = _last_payload(capsys)
    assert "no TPU found" in payload["error"]
    assert payload["platform"] == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._accelerator_error() == ""
