"""The scheduler binary surface (cmd/kube-scheduler analogue): flag
parsing, config/policy layering, feature gates."""

import pytest

from kubernetes_tpu.__main__ import build_parser, parse_feature_gates


def test_flags_parse():
    args = build_parser().parse_args(
        [
            "--config", "cfg.yaml",
            "--healthz-bind-address", "127.0.0.1:10251",
            "--leader-elect",
            "--feature-gates", "TPUBatchSolver=true,EvenPodsSpread=false",
            "--percentage-of-nodes-to-score", "50",
            "-v",
        ]
    )
    assert args.config == "cfg.yaml"
    assert args.leader_elect is True
    assert args.percentage_of_nodes_to_score == 50


def test_feature_gates_parse():
    assert parse_feature_gates("A=true, B=false") == {"A": True, "B": False}
    assert parse_feature_gates("") == {}
    with pytest.raises(SystemExit):
        parse_feature_gates("A=maybe")


def test_unknown_gate_rejected():
    from kubernetes_tpu.config.loader import (
        DEFAULT_FEATURE_GATES,
        FeatureGate,
    )

    gates = FeatureGate(DEFAULT_FEATURE_GATES)
    with pytest.raises(ValueError, match="unknown feature gate"):
        gates.set_from_map({"NoSuchGate": True})


def test_binary_boots_and_serves(tmp_path):
    """python -m kubernetes_tpu boots, serves /healthz, schedules a pod
    through the in-proc control plane, and shuts down."""
    import time

    from kubernetes_tpu.config.types import KubeSchedulerConfiguration
    from kubernetes_tpu.scheduler.app import SchedulerApp
    from kubernetes_tpu.testing import make_node, make_pod

    app = SchedulerApp(config=KubeSchedulerConfiguration())
    try:
        host, port = app.start_serving()
        app.client.create_node(
            make_node("n").capacity(cpu="4", memory="8Gi").obj()
        )
        app.start()
        app.client.create_pod(make_pod("p").container(cpu="1").obj())

        import urllib.request

        body = urllib.request.urlopen(
            f"http://{host}:{port}/healthz", timeout=5
        ).read()
        assert body == b"ok"

        deadline = time.time() + 30
        bound = False
        while time.time() < deadline:
            pod = app.client.get_pod("default", "p")
            if pod.spec.node_name:
                bound = True
                break
            time.sleep(0.05)
        metrics_body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5
        ).read().decode()
    finally:
        app.stop()
    assert bound
    assert "scheduler_schedule_attempts_total" in metrics_body


def test_binary_runs_the_configured_solver():
    """SchedulerApp builds through new_scheduler_from_config: the
    tpuSolver block reaches the scheduler the binary runs (it used to be
    dropped, so the binary always ran maxBatch=256 with no mesh)."""
    from kubernetes_tpu.config.loader import load_config_from_dict
    from kubernetes_tpu.config.types import KubeSchedulerConfiguration
    from kubernetes_tpu.scheduler.app import SchedulerApp
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.scheduler import Scheduler

    default = SchedulerApp(config=KubeSchedulerConfiguration()).sched
    assert isinstance(default, BatchScheduler)
    assert default.max_batch == 256
    assert default.solver_mode == "greedy"
    assert default.mesh is None
    assert default.batch_window == 0.01

    cfg = load_config_from_dict({
        "tpuSolver": {
            "maxBatch": 1024, "solverMode": "sinkhorn",
            "batchWindow": "50ms", "meshDevices": 2,
        }
    })
    sched = SchedulerApp(config=cfg).sched
    assert sched.max_batch == 1024
    assert 1024 in sched._warmup_pads
    assert sched.solver_mode == "sinkhorn"
    assert sched.batch_window == pytest.approx(0.05)
    assert sched.mesh is not None and sched.mesh.devices.size == 2

    # the TPUBatchSolver gate off still wins over tpuSolver.enabled
    host = SchedulerApp(config=cfg, batch=False).sched
    assert type(host) is Scheduler
    assert cfg.tpu_solver.enabled is True  # caller's config untouched
