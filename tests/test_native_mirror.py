"""Differential tests for native mirror_scatter (ISSUE 18).

The bind-echo -> shadow-mirror hot loop (native/_hotpath.c
mirror_scatter) compacts a batch's placed rows and scatter-adds their
demand into the committer's shadow expectation in one C pass. Its
pure-Python twin is scheduler/device_state._mirror_scatter_py; the
randomized suite here drives both over seeded assignment batches (NO_NODE
sprinkle, duplicate targets, empty batches) and asserts bit-equal
shadows AND compacted outputs. The validate-before-mutate contract is
pinned separately: an out-of-range assignment must raise before ANY
shadow byte changes, so the dispatcher's fallback-to-twin never
double-applies a delta.
"""

import numpy as np
import pytest

from kubernetes_tpu import native
from kubernetes_tpu.ops.assignment import NO_NODE
from kubernetes_tpu.scheduler.device_state import (
    _mirror_scatter,
    _mirror_scatter_py,
)

needs_native = pytest.mark.skipif(
    native.hotpath is None or native.hotpath.mirror_scatter is None,
    reason="native extension unavailable",
)


def _rand_case(rng):
    b = int(rng.integers(0, 48))
    r = int(rng.integers(1, 7))
    n = int(rng.integers(1, 40))
    a = rng.integers(-1, n, size=max(b, 1)).astype(np.int32)[:b]
    a[rng.random(b) < 0.3] = NO_NODE
    req = rng.integers(0, 5000, size=(b, r)).astype(np.int32)
    nzr = rng.integers(0, 5000, size=(b, 2)).astype(np.int32)
    req_shadow = rng.integers(0, 10000, size=(n, r)).astype(np.int32)
    nzr_shadow = rng.integers(0, 10000, size=(n, 2)).astype(np.int32)
    return a, b, req, nzr, req_shadow, nzr_shadow


@needs_native
class TestMirrorScatterDifferential:
    def test_randomized_bit_equal(self):
        fn = native.hotpath.mirror_scatter
        rng = np.random.default_rng(18)
        nonempty = 0
        for _ in range(300):
            a, b, req, nzr, rs, ns = _rand_case(rng)
            rs_c, ns_c = rs.copy(), ns.copy()
            py = _mirror_scatter_py(a, b, req, nzr, rs, ns)
            rows_out = np.empty(b, dtype=np.int64)
            req_out = np.empty((b, req.shape[1]), dtype=np.int32)
            nzr_out = np.empty((b, 2), dtype=np.int32)
            k = fn(
                np.ascontiguousarray(a[:b], dtype=np.int32),
                np.ascontiguousarray(req[:b]),
                np.ascontiguousarray(nzr[:b]),
                rs_c, ns_c, rows_out, req_out, nzr_out,
            )
            assert np.array_equal(rs, rs_c)
            assert np.array_equal(ns, ns_c)
            if py is None:
                assert k == 0
            else:
                nonempty += 1
                assert k == py[0].size
                assert np.array_equal(rows_out[:k], py[0])
                assert np.array_equal(req_out[:k], py[1])
                assert np.array_equal(nzr_out[:k], py[2])
        assert nonempty > 100  # the fuzz actually exercised placements

    def test_duplicate_targets_accumulate(self):
        # two pods landing on the SAME node must both add (np.add.at
        # semantics) -- the classic fancy-index += bug the twin avoids
        fn = native.hotpath.mirror_scatter
        a = np.array([2, 2, NO_NODE, 2], dtype=np.int32)
        req = np.full((4, 3), 10, dtype=np.int32)
        nzr = np.full((4, 2), 7, dtype=np.int32)
        rs = np.zeros((5, 3), dtype=np.int32)
        ns = np.zeros((5, 2), dtype=np.int32)
        k = fn(a, req, nzr, rs, ns, np.empty(4, np.int64),
               np.empty((4, 3), np.int32), np.empty((4, 2), np.int32))
        assert k == 3
        assert rs[2].tolist() == [30, 30, 30]
        assert ns[2].tolist() == [21, 21]
        assert rs[[0, 1, 3, 4]].sum() == 0

    def test_out_of_range_raises_before_mutating(self):
        fn = native.hotpath.mirror_scatter
        a = np.array([1, 99], dtype=np.int32)
        req = np.ones((2, 3), dtype=np.int32)
        nzr = np.ones((2, 2), dtype=np.int32)
        rs = np.zeros((4, 3), dtype=np.int32)
        ns = np.zeros((4, 2), dtype=np.int32)
        with pytest.raises(ValueError):
            fn(a, req, nzr, rs, ns, np.empty(2, np.int64),
               np.empty((2, 3), np.int32), np.empty((2, 2), np.int32))
        assert rs.sum() == 0 and ns.sum() == 0

    def test_empty_batch(self):
        fn = native.hotpath.mirror_scatter
        rs = np.zeros((3, 2), dtype=np.int32)
        ns = np.zeros((3, 2), dtype=np.int32)
        k = fn(np.empty(0, np.int32), np.empty((0, 2), np.int32),
               np.empty((0, 2), np.int32), rs, ns,
               np.empty(0, np.int64), np.empty((0, 2), np.int32),
               np.empty((0, 2), np.int32))
        assert k == 0


class TestMirrorScatterDispatch:
    def test_env_off_routes_to_twin(self, monkeypatch):
        # KTPU_NATIVE_INGEST=0 is the configured path: no fallback booked
        monkeypatch.setenv("KTPU_NATIVE_INGEST", "0")
        rng = np.random.default_rng(7)
        a, b, req, nzr, rs, ns = _rand_case(rng)
        rs_c, ns_c = rs.copy(), ns.copy()
        out = _mirror_scatter(a, b, req, nzr, rs_c, ns_c)
        py = _mirror_scatter_py(a, b, req, nzr, rs, ns)
        assert np.array_equal(rs, rs_c) and np.array_equal(ns, ns_c)
        if py is None:
            assert out is None
        else:
            for got, want in zip(out, py):
                assert np.array_equal(got, want)

    @needs_native
    def test_env_on_matches_twin(self, monkeypatch):
        monkeypatch.setenv("KTPU_NATIVE_INGEST", "1")
        rng = np.random.default_rng(11)
        for _ in range(20):
            a, b, req, nzr, rs, ns = _rand_case(rng)
            rs_c, ns_c = rs.copy(), ns.copy()
            out = _mirror_scatter(a, b, req, nzr, rs_c, ns_c)
            py = _mirror_scatter_py(a, b, req, nzr, rs, ns)
            assert np.array_equal(rs, rs_c) and np.array_equal(ns, ns_c)
            if py is None:
                assert out is None
            else:
                for got, want in zip(out, py):
                    assert np.array_equal(got, want)
