"""Every cell end to end at its rehearsal size, in this process, on
whatever device the test run has (``--rehearsal`` skips the look for a
chip and nothing else); the same run with the timed path broken
underneath, which has to come out as not correct; and a temporary copy
of the benchmark that gains a configuration, a mix, a generator, two
per-layer metrics and a four-chip cell as new files and new entries only,
and still keeps the benchmark's own rules (``benchmark_rules.py``)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import benchmark_rules as rules
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(capsys, workload, trace, seed=2**31 + 7, extra=()):
    with rules.one_traced_run_at_a_time(ROOT):
        rc = harness.main([
            "--workload", workload, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--rehearsal", *extra,
        ])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    return json.loads(out[-1]), out


def expected_metrics(workload, group):
    return {
        m["name"] for m in BENCH[group] if workload in rules.cells_of(BENCH, m)
    }


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_runs_at_rehearsal_size(capsys, workload, trace):
    line, out = run_cell(capsys, workload, trace)
    keys = LINE_KEYS | ({"breakdown"} if trace else set())
    assert set(line) == keys
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        device |= {"busy_s", "window_s"}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(line["breakdown"]["device_ops"]) <= 10
        # a metric whose file says ``on_chip_only`` reads device events
        # that a CPU's trace does not have, and is left out of the line
        declared = expected_metrics(workload, "per_layer")
        chip_only = rules.on_chip_only(ROOT, declared)
        assert chip_only.isdisjoint(line["metrics"])
        want = declared - chip_only
        assert line["device"]["busy_s"] > 0
        # warm-up by the cell's own traffic covered every program
        assert line["metrics"]["compiles_in_window"]["value"] == 0
    else:
        want = expected_metrics(workload, "end_to_end")
    assert set(line["device"]) == device
    assert set(line["metrics"]) == want
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
    # every number compared is printed beside its limit
    compared = [l for l in out if l.startswith("compare ")]
    assert len(compared) >= 8 and all("(limit " in l for l in compared)
    # the window's own placements were held against the reference
    assert any(l.startswith("compare window against the reference")
               for l in compared)


def test_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """The answer altered where it is produced: the solver scores by
    MostAllocated where the configuration states LeastAllocated. The
    program stays consistent with itself (cache, carry, API), every pod
    binds and fits, and only the comparisons with the reference can
    tell: the window's own placements, and the check wave."""
    import dataclasses

    real_init = harness.Run.__init__

    def crooked(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        self.sched.solver_config = dataclasses.replace(
            self.sched.solver_config, least_allocated_weight=0,
            most_allocated_weight=1,
        )

    monkeypatch.setattr(harness.Run, "__init__", crooked)
    line, out = run_cell(capsys, "basic-5000.burst-10k", 0)
    assert line["correct"] is False and line["failed"] == 0
    failed = [l for l in out if l.startswith("compare ") and l.endswith("FAILED")]
    assert [l.split(":")[0] for l in failed] == [
        "compare window against the reference", "compare check wave plain",
    ]


class StalledRun:
    """What ``end_to_end`` reads of a run: a 20 s window of 19 waves of
    100 pods that each drained in 0.5 s, and one that stalled for 10."""

    mix = {"params": {"deadline_s": 60}}
    window_start, window_end = 100.0, 120.0
    window_names = [f"p{k}" for k in range(2000)]

    def latencies_ms(self):
        return [500.0] * 1900 + [10000.0] * 100


def test_the_rate_is_every_pod_over_the_whole_window():
    """Not a median of waves, which a stalled wave does not move."""
    values, attempted, unbound = harness.end_to_end(StalledRun(), 12.0)
    assert (attempted, unbound) == (2000, 0)
    assert values["bound_pods_per_s"] == pytest.approx(2000 / 20.0)
    assert values["pod_to_bind_p99_ms"] == pytest.approx(10000.0)
    assert values["setup_s"] == 12.0


def test_proving_flags_are_not_on_the_benchmarks_command(capsys):
    for flag in (["--control"], ["--override", "params.rate=1"],
                 ["--keep-trace", "x"]):
        with pytest.raises(SystemExit):
            harness.main(["--workload", "basic-5000.burst-10k", "--seed", "1",
                          "--seconds", "1", "--trace", "0", "--rehearsal",
                          *flag])
    capsys.readouterr()


def test_the_proving_run_reads_the_control_beside_each_comparison():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.proving.run", "--workload",
         "basic-5000.burst-10k", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearsal", "--control",
         "--override", "params.warmup_waves=2"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = proc.stdout.splitlines()
    assert json.loads(out[-1])["correct"] is True
    assert sum(l.startswith("control window") for l in out) == 2
    assert sum(l.startswith("control plain") for l in out) == 2


def test_no_result_without_a_tpu(capsys):
    rc = harness.main(["--workload", "basic-5000.burst-10k", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0 and "correct" not in out


NEW_GENERATOR = '''
from chipbench.generators import waves

def warmup(run, params):
    waves.warmup(run, params)

def prepare(run, params, seconds):
    return None

def window(run, params, prepared, seconds):
    waves.one_wave(run, params)  # exactly one wave, whatever the seconds
'''

NEW_READER = '''
def read(sample, args):
    return float(sum(w["in_window"] for w in sample["run"].waves))
'''


def test_a_cell_is_added_by_new_files_and_entries_only(tmp_path):
    """What a later PR does, in a copy: a configuration, a mix, a
    generator, a reader, a cell on four chips (a rehearsal does not look
    for chips), and two per-layer metrics appended at the end, of which
    one reads a kernel that only the chip's trace has. The cell joins
    every ``workloads`` list but those of the one-chip kernels' metrics
    and of metrics whose file names what a cell must have (``needs``:
    family pods, which this plain cell has not), which it cannot report.
    The copy then has to keep every rule the real file is held to, run,
    and hold no edited file."""
    cell = "tiny-40.one-wave"
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", copy)
    for base in BENCH["paths"]:
        shutil.copytree(ROOT / base, copy / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        p: p.read_bytes() for p in copy.rglob("*")
        if p.is_file() and p.name != "BENCHMARK.json"
    }
    config = json.loads((copy / "chipbench/configs/basic-5000.json").read_text())
    config["source"] = "a test's own deployment"
    config["rehearsal"]["cluster"]["nodes"] = 40
    (copy / "chipbench/configs/tiny-40.json").write_text(json.dumps(config))
    mix = json.loads((copy / "chipbench/traffic/burst-10k.json").read_text())
    mix["generator"] = "single"
    del mix["window_check"]  # a mix may leave that comparison out
    (copy / "chipbench/traffic/one-wave.json").write_text(json.dumps(mix))
    (copy / "chipbench/generators/single.py").write_text(NEW_GENERATOR)
    (copy / "chipbench/readers/wave_count.py").write_text(NEW_READER)
    counted = {"name": "waves_run", "unit": "count", "better": "higher",
               "source": "host_clock", "layer": "harness",
               "moves": "pod_to_bind_p99_ms", "workloads": [cell]}
    kernel = {"name": "shard_kernel_us_per_step", "unit": "us",
              "better": "lower", "source": "device_trace", "layer": "kernel",
              "moves": "pod_to_bind_p50_ms", "workloads": [cell]}
    for metric, rest in (
        (counted, {"reader": "wave_count", "args": {}}),
        (kernel, {"reader": "kernel_time", "on_chip_only": True,
                  "args": {"pattern": "^pallas_shard_candidate"}}),
    ):
        (copy / f"chipbench/layer_metrics/{metric['name']}.json").write_text(
            json.dumps(dict(metric, **rest))
        )
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    listed = [m["name"] for m in bench["per_layer"]]
    one_chip_kernels = rules.on_chip_only(copy, listed)
    not_for_plain = one_chip_kernels | rules.needs_something(copy, listed)
    bench["configs"].append({
        "name": "tiny-40", "source": config["source"],
        "file": "chipbench/configs/tiny-40.json", "reduced": [], "why": "test",
    })
    bench["workloads"].append({
        "name": cell, "config": "tiny-40", "traffic": "one-wave", "chips": 4,
        "why": "test",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] not in not_for_plain:
            m["workloads"].append(cell)
    bench["per_layer"] += [counted, kernel]
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    for rule in rules.STRUCTURE:
        rule(bench, copy)
    for name in rules.NEW:
        rules.declared_since_pr24(bench, copy, name)

    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", cell,
         "--seed", "5", "--seconds", "1", "--trace", "1", "--rehearsal"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["waves_run"] == {"value": 1.0, "unit": "count"}
    declared = {m["name"] for m in bench["per_layer"]
                if cell in rules.cells_of(bench, m)}
    assert declared & not_for_plain == set()
    assert set(line["metrics"]) == declared - {kernel["name"]}
    assert "window against the reference: not compared" in proc.stdout
    for path, body in before.items():  # no file that was there was edited
        assert path.read_bytes() == body
