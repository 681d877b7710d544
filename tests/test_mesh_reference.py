"""The mesh path held to the plain reference (``chipbench/reference.py``:
sequential fit + LeastAllocated + BalancedAllocation in exact integers,
which imports nothing of the program) on ``conftest.py``'s virtual CPU
devices, through the normal path: ``load_config_from_dict`` ->
``new_scheduler_from_config`` -> apiserver -> informers ->
``BatchScheduler``, as ``chipbench.harness.Run`` builds it from the
deployment ``basic-50000`` at its rehearsal sizes.

Closed waves with a bulk delete after each: a wave that binds and is
deleted whole returns every node row to what it held before the wave,
which the carry's handshake used to read as a host that had not yet
seen the wave's commits (``DeviceNodeState._explain_rows``), and the
device went on placing around pods that were gone. Every wave's
placements are compared with ``reference.bands`` at limit 0."""

import numpy as np
import pytest

from chipbench import check, harness, reference
from chipbench.generators import waves
from chipbench.proving.shards import shard_counts
# a wave's deletes change this many rows at most before the handshake
# uploads the whole state instead of scattering rows
from kubernetes_tpu.scheduler.device_state import DELTA_ROW_BUCKET

CELL = "basic-50000.mesh-burst-20k"
DEVICES = 4
WAVES = 4


def mesh_cell(nodes: int, init_pods: int, wave: int) -> dict:
    cell = harness.load_cell(harness.ROOT, CELL, rehearsal=True)
    assert cell["config"]["wire"]["tpuSolver"]["meshDevices"] == DEVICES
    cell["config"]["cluster"]["nodes"] = nodes
    cell["config"]["cluster"]["init_pods"]["count"] = init_pods
    cell["mix"]["params"]["wave"][0]["pods_per_app"] = wave
    return cell


def outside_by_wave(run) -> list:
    """For every wave: the pods outside what the scoring rule allows
    their node from the state before the wave, whatever the order, the
    batching and the tie-break."""
    out = []
    nodes = run.config["cluster"]["nodes"]
    for wave in run.waves:
        names, snapshot = wave["names"], wave["snapshot"]
        mine = set(names)
        before = check.nodes_before(run, {
            name: node for name, node in snapshot.items() if name not in mine
        })
        got = np.zeros(nodes, dtype=np.int64)
        for name in names:
            got[check.node_index(snapshot[name])] += 1
        cpu, mem = check.wave_size(run, names)
        pod = reference.PodClass(cpu=cpu, mem=mem * check.MIB)
        lo, hi = reference.bands(before, pod, len(names))
        out.append(reference.outside(got, lo, hi) + len(names) - int(got.sum()))
    return out


def drive(cell: dict, seed: int = 2**31 + 11):
    """One warm-up wave, then ``WAVES`` waves, each deleted whole."""
    run = harness.Run(cell, seed)
    try:
        run.build_cluster()
        params = cell["mix"]["params"]
        waves.one_wave(run, params)
        del run.waves[:]
        sched = run.sched
        before = (sched.state_uploads, sched.delta_rows_uploaded)
        for _ in range(WAVES):
            waves.one_wave(run, params)
        sched.wait_for_inflight_binds(timeout=30)
        return {
            "outside": outside_by_wave(run),
            "uploads": sched.state_uploads - before[0],
            "rows_scattered": sched.delta_rows_uploaded - before[1],
            "tiers": dict(sched.ladder.solves_by_tier),
            "program": sched.mesh_solver_tier,
            "pods_fallback": sched.pods_fallback,
            # node rows by the mesh shard that holds them, and each
            # wave's pods by the shard they landed on
            "rows_by_shard": shard_counts(
                run, {n: n for n in sched.tensor_cache._names if n}
            ),
            "won_by_shard": [
                shard_counts(run, {n: w["snapshot"][n] for n in w["names"]})
                for w in run.waves
            ],
            # node rows a wave's pods landed on: what its deletes change
            "rows": [
                len({w["snapshot"][name] for name in w["names"]})
                for w in run.waves
            ],
        }
    finally:
        run.stop()


@pytest.mark.parametrize("nodes,init_pods", [
    # 256 rows, 64 a shard: rows 100.. are padding, so two of the four
    # shards hold no valid row
    pytest.param(100, 30, id="sparse-shards"),
    # 384 rows, 96 a shard: every shard holds valid rows
    pytest.param(300, 200, id="every-shard"),
])
@pytest.mark.parametrize("program", ["shard_map", "gspmd"])
@pytest.mark.parametrize("wave", [
    pytest.param(40, id="scatter"),  # fewer rows than DELTA_ROW_BUCKET
    pytest.param(150, id="upload"),  # more
])
def test_every_wave_equals_the_reference(monkeypatch, wave, program,
                                         nodes, init_pods):
    if program == "gspmd":
        monkeypatch.setenv("KTPU_MESH_PALLAS", "0")
    got = drive(mesh_cell(nodes, init_pods, wave))
    assert got["outside"] == [0] * WAVES
    assert got["pods_fallback"] == 0
    # which of the mesh's two programs ran, and what the ledger calls it:
    # on a CPU the shard_map tier runs without its kernel
    assert got["program"] == ("pallas" if program == "shard_map" else "xla")
    assert got["tiers"]["pallas"] == 0 and got["tiers"]["xla"] > WAVES
    assert got["tiers"]["host_greedy"] == got["tiers"]["sequential"] == 0
    # how a wave's deletes reached the resident carry
    if wave < DELTA_ROW_BUCKET:
        assert max(got["rows"]) < DELTA_ROW_BUCKET
        assert got["uploads"] == 0
        assert got["rows_scattered"] >= sum(got["rows"][:-1])
    else:
        assert min(got["rows"]) > DELTA_ROW_BUCKET
        assert got["uploads"] >= WAVES - 1


def test_pods_win_on_every_shard_that_holds_a_valid_row():
    """300 nodes, 96 rows a shard: the last shard holds 12 valid rows.
    200 init pods take the lowest empty nodes, so a wave of 150 first
    takes the empty nodes at the top (the last two shards) and then
    second places from the bottom (the first two)."""
    got = drive(mesh_cell(300, 200, 150))
    assert got["outside"] == [0] * WAVES
    assert len(got["rows_by_shard"]) == DEVICES
    assert got["rows_by_shard"] == [96, 96, 96, 12]
    for won in got["won_by_shard"]:
        assert all(won) and sum(won) == 150, won
