"""Ops-shell tests: metrics exposition, healthz, leader election,
cache debugger, config loading."""

import threading
import time
import urllib.request

import pytest

from kubernetes_tpu.api.types import ObjectMeta
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.config.loader import (
    DEFAULT_FEATURE_GATES,
    FeatureGate,
    load_config_from_dict,
)
from kubernetes_tpu.config.types import LeaderElectionConfiguration
from kubernetes_tpu.scheduler.app import SchedulerApp
from kubernetes_tpu.scheduler.leaderelection import LeaderElector
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics


class TestMetrics:
    def test_counter_and_histogram(self):
        c = metrics.Counter("test_total", "help", ("result",))
        c.inc(result="ok")
        c.inc(result="ok")
        assert c.value(result="ok") == 2
        h = metrics.Histogram("test_seconds", "help", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        assert h.count() == 2
        text = "\n".join(h.collect())
        assert 'le="0.1"' in text and "test_seconds_sum" in text

    def test_registry_expose(self):
        text = metrics.registry.expose()
        assert "scheduler_schedule_attempts_total" in text
        assert "scheduler_e2e_scheduling_duration_seconds" in text


class TestSchedulerApp:
    def test_healthz_metrics_and_scheduling(self):
        app = SchedulerApp()
        host, port = app.start_serving()
        client = app.client
        client.create_node(make_node("n").capacity(cpu="8", memory="16Gi").obj())
        app.start()
        client.create_pod(make_pod("p").container(cpu="1").obj())
        deadline = time.time() + 10
        while time.time() < deadline:
            if client.get_pod("default", "p").spec.node_name:
                break
            time.sleep(0.05)
        app.sched.wait_for_inflight_binds()

        base = f"http://{host}:{port}"
        assert urllib.request.urlopen(base + "/healthz").read() == b"ok"
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert 'scheduler_schedule_attempts_total{result="scheduled"}' in body
        assert "scheduler_scheduler_cache_size" in body
        dump = urllib.request.urlopen(base + "/debug/cache").read().decode()
        assert "node n" in dump
        app.stop()

    def test_cache_comparer_consistent(self):
        app = SchedulerApp()
        client = app.client
        client.create_node(make_node("n").capacity(cpu="4", memory="8Gi").obj())
        app.start()
        client.create_pod(make_pod("p").container(cpu="1").obj())
        deadline = time.time() + 10
        while time.time() < deadline:
            if client.get_pod("default", "p").spec.node_name:
                break
            time.sleep(0.05)
        app.sched.wait_for_inflight_binds()
        time.sleep(0.3)  # let informer events settle into the cache
        result = app.debugger.comparer.compare()
        assert all(not v for v in result.values()), result
        problems = app.debugger.tensor_comparer.compare()
        assert not problems
        app.stop()


class TestLeaderElection:
    def _elector(self, client, name, events, cfg):
        return LeaderElector(
            client,
            cfg,
            identity=name,
            on_started_leading=lambda: events.append(("lead", name)),
            on_stopped_leading=lambda: events.append(("stop", name)),
        )

    def test_single_leader_and_failover(self):
        server = APIServer()
        client = Client(server)
        cfg = LeaderElectionConfiguration(
            leader_elect=True,
            lease_duration_seconds=0.5,
            renew_deadline_seconds=0.4,
            retry_period_seconds=0.05,
        )
        events = []
        a = self._elector(client, "a", events, cfg)
        b = self._elector(client, "b", events, cfg)
        ta = threading.Thread(target=a.run, daemon=True)
        tb = threading.Thread(target=b.run, daemon=True)
        ta.start()
        time.sleep(0.2)
        tb.start()
        time.sleep(0.3)
        assert a.is_leader and not b.is_leader
        # leader dies: stop renewing
        a.stop()
        ta.join(timeout=2)
        deadline = time.time() + 5
        while time.time() < deadline and not b.is_leader:
            time.sleep(0.05)
        assert b.is_leader, "standby never took over"
        b.stop()

    def test_release_hands_off_immediately(self):
        server = APIServer()
        client = Client(server)
        cfg = LeaderElectionConfiguration(
            lease_duration_seconds=30.0,  # long: only release can hand off
            renew_deadline_seconds=10.0,
            retry_period_seconds=0.05,
        )
        events = []
        a = self._elector(client, "a", events, cfg)
        ta = threading.Thread(target=a.run, daemon=True)
        ta.start()
        deadline = time.time() + 2
        while time.time() < deadline and not a.is_leader:
            time.sleep(0.02)
        assert a.is_leader
        a.stop()
        a.release()
        lease = server.get("Lease", "kube-system", "kube-scheduler")
        assert lease.holder_identity == ""


class TestConfigLoader:
    def test_load_full_config(self):
        raw = {
            "percentageOfNodesToScore": 50,
            "leaderElection": {"leaderElect": True, "leaseDuration": 5},
            "profiles": [
                {
                    "schedulerName": "tpu-scheduler",
                    "plugins": {
                        "score": {
                            "enabled": [{"name": "NodeResourcesMostAllocated",
                                         "weight": 5}],
                            "disabled": [{"name": "NodeResourcesLeastAllocated"}],
                        }
                    },
                    "pluginConfig": [
                        {"name": "InterPodAffinity",
                         "args": {"hard_pod_affinity_weight": 10}},
                    ],
                }
            ],
            "extenders": [
                {"urlPrefix": "http://127.0.0.1:9999", "filterVerb": "filter",
                 "managedResources": [{"name": "example.com/fpga"}]}
            ],
            "featureGates": {"TPUBatchSolver": False},
        }
        cfg = load_config_from_dict(raw)
        assert cfg.percentage_of_nodes_to_score == 50
        assert cfg.leader_election.leader_elect
        assert cfg.leader_election.lease_duration_seconds == 5
        prof = cfg.profiles[0]
        assert prof.scheduler_name == "tpu-scheduler"
        assert prof.plugins.score.enabled[0].weight == 5
        assert prof.plugin_config["InterPodAffinity"][
            "hard_pod_affinity_weight"] == 10
        assert cfg.extenders[0].managed_resources == ["example.com/fpga"]

    def test_feature_gates(self):
        fg = FeatureGate(DEFAULT_FEATURE_GATES)
        assert fg.enabled("TPUBatchSolver")
        fg.set_from_map({"TPUBatchSolver": False})
        assert not fg.enabled("TPUBatchSolver")
        with pytest.raises(ValueError):
            fg.set_from_map({"NoSuchGate": True})


class TestDurationParsing:
    def test_go_style_durations(self):
        from kubernetes_tpu.config.loader import _duration_seconds

        assert _duration_seconds("30s") == 30.0
        assert _duration_seconds("1m30s") == 90.0
        assert _duration_seconds("500ms") == 0.5
        assert _duration_seconds(5) == 5.0
        assert _duration_seconds("2.5") == 2.5
        with pytest.raises(ValueError):
            _duration_seconds("bogus")

    def test_extender_http_timeout_duration_string(self):
        cfg = load_config_from_dict(
            {"extenders": [{"urlPrefix": "http://x", "httpTimeout": "30s"}]}
        )
        assert cfg.extenders[0].http_timeout_seconds == 30.0
