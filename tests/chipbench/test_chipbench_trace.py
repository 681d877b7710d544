"""The reduction from a profiler trace to busy seconds, top operations
and idle gaps, on a small trace recorded on the chip and kept with the
benchmark (chipbench/testdata/)."""

from pathlib import Path

import pytest

from chipbench import tracing

DATA = Path(__file__).resolve().parents[2] / "chipbench" / "testdata"


def test_union_merges_overlaps():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == [
        [0, 3], [5, 8], [10, 11]
    ]


@pytest.fixture(scope="module")
def reduced():
    """A traced 8 s slice of basic-5000.burst-10k on one v5e chip (my chip
    run, PR 23): 26 calls of the basic solver kernel."""
    phases = [("early", 100.0, 103.0), ("inner", 101.0, 102.0),
              ("late", 103.0, 108.5)]
    return tracing.reduce(str(DATA / "burst-10k-8s.xplane.pb"), phases, 100.0)


def test_recorded_trace_reduces(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(8.0323, abs=1e-3)
    assert reduced["busy_s"] == pytest.approx(0.12779, abs=1e-4)
    assert len(reduced["device_ops"]) == 10
    seconds = [s for _, s in reduced["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    name, total = reduced["device_ops"][0]
    assert name == "pallas_greedy_solve.1"  # the HLO text is cut to its name
    assert reduced["ops"][name][0] == 26
    assert total / 26 == pytest.approx(4.8545e-3, rel=1e-3)


def test_idle_gaps_are_laid_over_the_harness_phases(reduced):
    idle = dict(reduced["idle_gaps"])
    assert set(idle) == {"early", "inner", "late"}
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-6
    )
    # the innermost span that covers a gap's middle owns it
    assert idle["inner"] == pytest.approx(1.0, abs=0.1)
    assert idle["early"] == pytest.approx(2.0, abs=0.1)


def test_kernel_readers_find_the_kernel(reduced):
    import json

    from chipbench.readers import kernel_roofline, kernel_time

    root = DATA.parent.parent
    spec = json.loads(
        (root / "chipbench/layer_metrics/solve_kernel_ms_per_batch.json").read_text()
    )
    config = json.loads((root / "chipbench/configs/basic-5000.json").read_text())
    sample = {"trace": reduced, "root": root,
              "device": {"kind": "TPU v5 lite"}, "cell": {"config": config}}
    assert kernel_time.read(sample, spec["args"]) == pytest.approx(4.8545, rel=1e-3)
    share = kernel_roofline.read(sample, spec["args"])
    assert 0 < share < 0.1  # latency-bound: far under 1% of the HBM roofline
    with pytest.raises(KeyError):
        kernel_roofline.read(dict(sample, device={"kind": "cpu"}), spec["args"])
    assert kernel_time.read(dict(sample, trace=None), spec["args"]) is None


def test_a_trace_without_a_device_plane_is_refused_outside_a_rehearsal(tmp_path):
    """On the CPU the profiler writes no ``/device:`` plane. Only a
    rehearsal may read host events in its place: a chip run whose trace
    lost the plane gets no device reading at all."""
    import jax.numpy as jnp

    slicer = tracing.Slice(str(tmp_path), 0.2)
    slicer.start()
    jnp.arange(4096.0).sum().block_until_ready()
    slicer.join()
    with pytest.raises(RuntimeError, match="no /device: plane"):
        tracing.reduce(slicer.path())
    reduced = tracing.reduce(slicer.path(), rehearsal=True)
    assert reduced["window_s"] == pytest.approx(0.2, abs=0.1)
