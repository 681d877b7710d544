"""Exposition-format guards (ISSUE 13 satellites): label-value
escaping, labeled callback gauges, and the end-to-end /metrics scrape
lint -- every line parses, HELP/TYPE precede samples, no duplicate
series, histogram _count equals the +Inf bucket. Catches the two
metrics.py fixes regressing, with the real HTTP handler in the loop.
"""

import json
import re
import time
import urllib.request

import pytest

from kubernetes_tpu.scheduler.app import SchedulerApp
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics


class TestLabelEscaping:
    def test_quote_backslash_newline_escape(self):
        c = metrics.Counter("esc_total", "help", ("point",))
        c.inc(point='node "a"\\zone\nline2')
        line = [ln for ln in c.collect() if not ln.startswith("#")][0]
        assert line == (
            'esc_total{point="node \\"a\\"\\\\zone\\nline2"} 1.0'
        )
        # the escaped form survives a strict sample-line parse
        assert _SAMPLE_RE.match(line), line

    def test_plain_values_unchanged(self):
        c = metrics.Counter("esc2_total", "help", ("tier",))
        c.inc(tier="pallas")
        line = [ln for ln in c.collect() if not ln.startswith("#")][0]
        assert line == 'esc2_total{tier="pallas"} 1.0'

    def test_histogram_labels_escape_too(self):
        h = metrics.Histogram(
            "esc_seconds", "help", ("name",), buckets=(1.0,)
        )
        h.observe(0.5, name='x"y')
        for ln in h.collect():
            if ln.startswith("#"):
                continue
            assert _SAMPLE_RE.match(ln), ln


class TestCallbackGauges:
    def test_constructor_fn_with_labels_rejected(self):
        with pytest.raises(ValueError):
            metrics.Gauge("bad_gauge", "help", ("q",), fn=lambda: 1.0)

    def test_per_label_callbacks_collect(self):
        g = metrics.Gauge("cb_gauge", "help", ("q",))
        g.register_callback(lambda: 0.25, q="0.5")
        g.register_callback(lambda: 0.75, q="0.99")
        lines = [ln for ln in g.collect() if not ln.startswith("#")]
        assert 'cb_gauge{q="0.5"} 0.25' in lines
        assert 'cb_gauge{q="0.99"} 0.75' in lines
        assert g.value(q="0.5") == 0.25
        # a set() under the same labels does not shadow the callback
        g.set(99.0, q="0.5")
        assert g.value(q="0.5") == 0.25
        assert len(
            [ln for ln in g.collect() if 'q="0.5"' in ln]
        ) == 1

    def test_unlabeled_callback_still_works(self):
        g = metrics.Gauge("plain_cb", "help", fn=lambda: 7.0)
        assert g.value() == 7.0
        assert "plain_cb 7.0" in g.collect()


# one Prometheus text-format sample line: name{labels} value
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*")*\})? '
    r'-?[0-9.e+\-]+(\.[0-9]+)?$'
)


def _lint_exposition(body: str):
    """The scrape lint: every line parses, HELP/TYPE precede their
    family's samples, no duplicate series, histogram _count == +Inf
    bucket. Returns (families_seen, problems)."""
    problems = []
    seen_series = set()
    headered = set()  # families with HELP+TYPE already emitted
    help_seen = set()
    type_of = {}
    inf_buckets = {}
    counts = {}
    for ln in body.splitlines():
        if not ln:
            continue
        if ln.startswith("# HELP "):
            help_seen.add(ln.split()[2])
            continue
        if ln.startswith("# TYPE "):
            parts = ln.split()
            fam = parts[2]
            type_of[fam] = parts[3]
            if fam not in help_seen:
                problems.append(f"TYPE before HELP: {fam}")
            headered.add(fam)
            continue
        if ln.startswith("#"):
            problems.append(f"unknown comment line: {ln!r}")
            continue
        if not _SAMPLE_RE.match(ln):
            problems.append(f"unparseable sample: {ln!r}")
            continue
        series = ln.rsplit(" ", 1)[0]
        name = series.split("{", 1)[0]
        fam = re.sub(r"_(bucket|sum|count)$", "", name)
        if fam not in headered and name not in headered:
            problems.append(f"sample before HELP/TYPE: {ln!r}")
        if series in seen_series:
            problems.append(f"duplicate series: {series!r}")
        seen_series.add(series)
        value = float(ln.rsplit(" ", 1)[1])
        if name.endswith("_bucket") and 'le="+Inf"' in series:
            key = re.sub(r',?le="\+Inf"', "", series).replace(
                "_bucket", ""
            ).replace("{}", "")
            inf_buckets[key] = value
        elif name.endswith("_count") and type_of.get(fam) == "histogram":
            counts[series.replace("_count", "")] = value
    for key, n in counts.items():
        if key not in inf_buckets:
            problems.append(f"histogram without +Inf bucket: {key!r}")
        elif inf_buckets[key] != n:
            problems.append(
                f"histogram {key!r}: _count {n} != +Inf bucket "
                f"{inf_buckets[key]}"
            )
    return headered, problems


class TestMetricsEndpointE2E:
    def test_scrape_lints_clean_during_burst(self):
        """Scrape the real SchedulerApp HTTP handler after a small
        burst (histograms, labeled counters, callback gauges, and the
        fault-point label with a quoted value all live) and lint the
        payload."""
        app = SchedulerApp()
        host, port = app.start_serving()
        client = app.client
        for i in range(8):
            client.create_node(
                make_node(f"n{i}").capacity(cpu="16", memory="32Gi")
                .obj()
            )
        app.start()
        names = [f"m-{i}" for i in range(60)]
        for n in names:
            client.create_pod(make_pod(n).container(cpu="100m").obj())
        deadline = time.time() + 30
        while time.time() < deadline:
            pods, _ = client.list_pods()
            if all(p.spec.node_name for p in pods):
                break
            time.sleep(0.05)
        app.sched.wait_for_inflight_binds()
        # a label value with quote/backslash/newline must survive the
        # scrape (the _fmt_labels escaping fix, end-to-end)
        metrics.faults_injected.inc(point='evil "point"\\with\nnewline')

        base = f"http://{host}:{port}"
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        families, problems = _lint_exposition(body)
        assert not problems, problems[:10]
        # the new series are live
        assert "scheduler_tpu_state_uploads_total" in body
        assert "scheduler_pod_to_bind_quantile_seconds" in body
        assert 'q="0.99"' in body
        # blast-radius containment families (ISSUE 14): registered in
        # the default registry so dashboards can alert on a quarantine
        # or audit mismatch the moment the first one books
        assert "scheduler_tpu_bisections_total" in body
        assert "scheduler_tpu_bisect_subsolves_total" in body
        assert "scheduler_ladder_exhausted_crashloops_total" in body
        assert "scheduler_quarantine_pods_total" in body
        assert "scheduler_quarantine_parked" in body
        assert "scheduler_quarantine_releases_total" in body
        assert "scheduler_tpu_carry_audit_sweeps_total" in body
        assert "scheduler_tpu_carry_audit_mismatches_total" in body
        assert "scheduler_tpu_device_lost_total" in body
        assert "scheduler_tpu_device_rebuild_ms" in body
        # multi-tenant fairness families (ISSUE 15): the quota ledger
        # counters and the DRF dominant-share gauge ride the default
        # registry so a starving tenant or a leaking ledger alerts from
        # the first scrape
        assert "scheduler_quota_admissions_total" in body
        assert "scheduler_quota_refunds_total" in body
        assert "scheduler_quota_parked" in body
        assert "scheduler_quota_releases_total" in body
        assert "scheduler_tenant_dominant_share" in body
        # hollow-node / closed-bind-loop families (ISSUE 17): ack path,
        # heartbeat plane, and the zombie-recovery arc are all
        # registered in the default registry so a silent kubelet shows
        # up on a dashboard before the rebind sweep fires
        assert "scheduler_hollow_acks_total" in body
        assert "scheduler_hollow_heartbeats_total" in body
        assert "scheduler_bind_acks_total" in body
        assert "scheduler_bind_ack_latency_seconds" in body
        assert "scheduler_bind_ack_timeouts_total" in body
        assert "scheduler_rebinds_total" in body
        assert "scheduler_bind_ack_pending" in body
        assert "scheduler_bind_ack_suspect_nodes_tainted_total" in body
        assert "scheduler_node_heartbeat_lapses_total" in body
        assert "scheduler_taint_evictions_total" in body
        # pipelined speculative dispatch (ISSUE 18): the rewind ledger
        # must be scrapeable even at zero samples (HELP/TYPE emit
        # unconditionally) so dashboards can alert on rewind storms
        assert "scheduler_speculative_launches_total" in body
        assert "scheduler_speculative_rewinds_total" in body
        # and the quantile gauge carries a real estimate post-burst
        p99 = metrics.pod_to_bind_quantile.value(q="0.99")
        assert p99 > 0.0

        # the flight-recorder debug endpoint next door: valid JSON with
        # the burst's spans
        fr = urllib.request.urlopen(
            base + "/debug/flightrecorder"
        ).read().decode()
        doc = json.loads(fr)
        assert isinstance(doc["spans"], list)
        assert isinstance(doc["marks"], list)
        assert any(
            s["tier"] in ("pallas", "xla", "host_greedy")
            for s in doc["spans"]
        )
        app.stop()
