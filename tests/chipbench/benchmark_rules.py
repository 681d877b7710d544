"""``BENCHMARK.json``'s own rules as functions of ``(bench, root)``: the
file's contents as loaded, and the checkout that holds it. The tests of
the real tree call them on the repo, and
``test_a_cell_is_added_by_new_files_and_entries_only`` calls them on a
copy that has gained a cell and metrics, so a rule that an addition
cannot keep without editing what was there fails here, in the PR that
writes it."""

import contextlib
import fcntl
import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

#: PR 24's block of ``per_layer``, in its order, and the cells it was
#: declared for
NEW = [
    "ingest_ms_per_batch", "bind_ms_per_batch", "pack_state_ms_per_batch",
    "pack_pods_ms_per_batch", "pack_masks_ms_per_batch", "gc_pause_ms_per_s",
    "queue_wait_ms_per_pod", "queue_wait_max_ms", "idle_under_pack_pct",
    "idle_under_commit_pct", "idle_under_bind_pct", "idle_under_ingest_pct",
    "idle_under_gc_pct", "idle_scheduler_waiting_pct",
]
FIRST_CELLS = ["basic-5000.burst-10k", "spread-anti-5000.burst-5k",
               "basic-5000.arrivals-steady"]


@contextlib.contextmanager
def one_traced_run_at_a_time(root: Path):
    """A traced run keeps its slice under ``<root>/.chipbench_trace/<cell>``
    and clears that directory as it starts: right for the benchmark, which
    runs one cell at a time in a checkout, and a collision for two test
    files that trace one cell in the repo's own root from two workers.
    They take this lock around the run."""
    (root / ".chipbench_trace").mkdir(exist_ok=True)
    with open(root / ".chipbench_trace" / "lock", "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        yield


def line_ok(text) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def spec_of(root: Path, name: str) -> dict:
    """A per-layer metric's own file."""
    return json.loads(
        (root / "chipbench" / "layer_metrics" / f"{name}.json").read_text()
    )


def cells_of(bench: dict, metric: dict) -> set:
    """The cells a metric is declared for: its list, or every cell."""
    return set(metric.get("workloads", [w["name"] for w in bench["workloads"]]))


def on_chip_only(root: Path, names) -> set:
    """Those of ``names`` whose file says ``"on_chip_only": true``: they
    read device events of a kernel or a collective, which only the chip's
    trace has, so a CPU rehearsal leaves them out of its line."""
    return {n for n in names if spec_of(root, n).get("on_chip_only") is True}


def needs_something(root: Path, names) -> set:
    """Those of ``names`` whose file says what a cell must have to
    report them (``"needs": "family_pods"``): a cell without it gives
    the reader nothing, so an addition joins their lists only where it
    has it."""
    return {n for n in names if "needs" in spec_of(root, n)}


def top_level_shape(bench: dict, root: Path) -> None:
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (root / p).is_dir()
    assert len(bench["command"]) <= 32 and all(line_ok(w) for w in bench["command"])
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def configs(bench: dict, root: Path) -> None:
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((root / c["file"]).read_text())
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in ("source", "cluster", "pod_classes", "wire", "guarantees",
                    "expect_tier", "assumed", "layout", "rehearsal"):
            assert key in body, (c["file"], key)
        assert len(body["source"]) <= 200


def workloads(bench: dict, root: Path) -> None:
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(names)
    known = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in known
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        mix = json.loads(
            (root / "chipbench/traffic" / f"{w['traffic']}.json").read_text()
        )
        assert (root / "chipbench/generators" / f"{mix['generator']}.py").is_file()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(names) // 2)


def metrics(bench: dict, root: Path) -> None:
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line_ok(m["layer"])
        # each of its cells reports the end-to-end metric it should move
        assert cells_of(bench, m) <= cells_of(bench, e2e[m["moves"]]), m["name"]
        spec = spec_of(root, m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert (root / "chipbench/readers" / f"{spec['reader']}.py").is_file()
        assert isinstance(spec.get("on_chip_only", False), bool)
        if "needs" in spec:  # not every cell has it: the cells are named
            assert line_ok(spec["needs"]) and "workloads" in m, m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert cells_of(bench, m) <= cells
    for cell in cells:  # setup_s, one more end-to-end metric, one per layer
        mine = [m for m in bench["end_to_end"] if cell in cells_of(bench, m)]
        assert len(mine) >= 2
        assert any(cell in cells_of(bench, m) for m in bench["per_layer"])


def comparisons(bench: dict, root: Path) -> None:
    """Every comparison a configuration names (or gets for naming none)
    has its file, at the cell's own size and at the rehearsal's, and
    every tier ledger it names is one the harness knows."""
    from chipbench import check, harness

    for c in bench["configs"]:
        body = json.loads((root / c["file"]).read_text())
        for config in (body, harness._overlay(body, body["rehearsal"])):
            names = check.names_of(config)
            assert names[-1] == check.LAST_CHECK and len(set(names)) == len(names)
            for name in names:
                assert NAME.match(name), name
                assert (root / "chipbench/checks" / f"{name}.py").is_file(), (
                    c["file"], name)
            for ledger, tier in check.expected_tiers(config).items():
                assert ledger in harness.LEDGERS, (c["file"], ledger)
                assert tier in harness.TIERS, (c["file"], tier)


#: the rules of the file's structure, each ``rule(bench, root)``
STRUCTURE = (top_level_shape, configs, workloads, metrics, comparisons)


def declared_since_pr24(bench: dict, root: Path, name: str) -> None:
    """What no later PR may undo to one of ``NEW``. The rule: a PR
    appends; nothing that was there moves. So the entry still lists the
    cells it was declared for, beside whichever cells later PRs added to
    its list, and names only cells that exist; and ``NEW`` is one
    contiguous run of ``per_layer``, in its order, wherever later
    entries follow it."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(FIRST_CELLS) <= set(entry["workloads"]) <= cells
    assert entry["moves"] == "pod_to_bind_p50_ms"
    spec = spec_of(root, name)
    assert (root / "chipbench" / "readers" / f"{spec['reader']}.py").is_file()
    names = [m["name"] for m in bench["per_layer"]]
    i = names.index(NEW[0])
    assert names[i:i + len(NEW)] == NEW
