"""The pod-to-bind sketch's native fold (``p2_fold`` in
native/_hotpath.c) against its twin, ``P2Quantile.observe``: every
marker height, position and desired position float for float, whatever
the stream and however it is cut into batches."""

import random

import pytest

from kubernetes_tpu.utils import metrics
from kubernetes_tpu.utils.quantiles import P2Quantile, QuantileSet

native = pytest.importorskip("kubernetes_tpu.native")
if native.hotpath is None:  # pragma: no cover - build failure environment
    pytest.skip("native module unavailable", allow_module_level=True)

N = 9_000  # two batches of 4,096 and a rest


def _stream(kind, seed=20260):
    rng = random.Random(seed)
    if kind == "uniform":
        return [rng.random() for _ in range(N)]
    if kind == "lognormal":  # the latency-like shape: heavy right tail
        return [rng.lognormvariate(-2.0, 0.7) for _ in range(N)]
    if kind == "sorted":
        return sorted(rng.random() for _ in range(N))
    if kind == "descending":
        return sorted((rng.random() for _ in range(N)), reverse=True)
    if kind == "constant":
        return [0.25] * N
    if kind == "duplicates":  # four values, ties at every marker
        return [float(rng.randrange(4)) for _ in range(N)]
    if kind == "integers":  # ints among floats, as observe() takes them
        return [rng.randrange(100) if i % 3 else rng.random() * 100
                for i in range(N)]
    raise AssertionError(kind)


def _state(qs):
    """Everything an estimator holds, in exact floats."""
    return [
        (est.q, est._n, list(est._init), list(est._heights),
         list(est._pos), list(est._desired))
        for est in qs._est.values()
    ]


def _twin(values, quantiles=(0.5, 0.99)):
    qs = QuantileSet(quantiles)
    for est in qs._est.values():
        for x in values:
            est.observe(x)
    return qs


def _folded(values, batch, quantiles=(0.5, 0.99)):
    qs = QuantileSet(quantiles)
    for at in range(0, len(values), batch):
        qs.observe_many(values[at:at + batch])
    return qs


STREAMS = ["uniform", "lognormal", "sorted", "descending", "constant",
           "duplicates", "integers"]
# the first-five boundary falls inside a batch (4 then 4, 6, 37, 4,096),
# at the end of one (5, 1) and before one (every batch after it)
BATCHES = [1, 4, 5, 6, 37, 4096]


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("kind", STREAMS)
def test_the_fold_equals_the_twin_float_for_float(kind, batch):
    values = _stream(kind)
    folded, twin = _folded(values, batch), _twin(values)
    assert _state(folded) == _state(twin)
    assert folded.count == twin.count == N
    for q in (0.5, 0.99):
        assert folded.value(q) == twin.value(q)


@pytest.mark.parametrize("q", [0.01, 0.25, 0.5, 0.9, 0.99, 0.999])
def test_every_quantile_folds_alike(q):
    values = _stream("lognormal", seed=q)
    assert _state(_folded(values, 37, (q,))) == _state(_twin(values, (q,)))


@pytest.mark.parametrize("head", [0, 1, 4, 5, 6])
def test_a_fold_after_scalar_observes_and_a_reset(head):
    """The scalar ``observe`` (the per-pod bind path) and the fold share
    one state; ``reset()`` starts both from nothing."""
    values = _stream("uniform", seed=head)[:500]
    qs = QuantileSet()
    qs.observe_many(_stream("lognormal")[:300])
    qs.reset()
    assert qs.count == 0 and qs.value(0.5) == 0.0
    for x in values[:head]:
        qs.observe(x)
    qs.observe_many(values[head:200])
    qs.observe(values[200])
    qs.observe_many(tuple(values[201:]))  # any sequence, not only a list
    assert _state(qs) == _state(_twin(values))
    assert qs.count == 500


def test_fewer_than_five_values_stay_exact_sample_quantiles():
    qs = QuantileSet()
    qs.observe_many([3.0, 1.0, 2.0])
    assert qs.count == 3 and qs.value(0.5) == 2.0
    qs.observe_many([])
    assert qs.count == 3


def test_a_value_that_is_no_number_changes_nothing():
    """Everything is read before anything is written: the batch that
    cannot be folded leaves every estimator where it was."""
    values = _stream("uniform")[:100]
    qs = _folded(values, 37)
    before = _state(qs)
    with pytest.raises(TypeError):
        qs.observe_many([0.5, "late", 0.7])
    assert _state(qs) == before
    assert QuantileSet(()).observe_many([1.0, 2.0]) is None  # no estimator


def test_the_native_call_checks_what_it_is_given():
    fold = native.hotpath.p2_fold
    good = ([1.0, 2.0, 3.0, 4.0, 5.0], [1.0, 2.0, 3.0, 4.0, 5.0],
            [1.0, 2.0, 3.0, 4.0, 5.0], (0.0, 0.25, 0.5, 0.75, 1.0))
    with pytest.raises(ValueError):
        fold((good,), [1.0], 2)
    with pytest.raises(TypeError):
        fold(((good[0][:4],) + good[1:],), [1.0], 0)
    with pytest.raises(TypeError):
        fold(((tuple(good[0]),) + good[1:],), [1.0], 0)  # written back
    with pytest.raises(TypeError):
        fold((good[:3],), [1.0], 0)
    assert fold((good,), [2.5], 1) is None  # nothing from ``start`` on
    assert good[0] == [1.0, 2.0, 3.0, 4.0, 5.0]


def test_the_twin_is_the_configured_path_or_a_counted_fallback(monkeypatch):
    values = _stream("lognormal")[:1000]
    want = _state(_twin(values))
    counter = metrics.ingest_native_fallbacks
    before = counter.value(site="p2-fold")
    # KTPU_NATIVE_INGEST=0 asks for the twin: nothing is booked
    monkeypatch.setenv("KTPU_NATIVE_INGEST", "0")
    assert _state(_folded(values, 37)) == want
    assert counter.value(site="p2-fold") == before
    # native wanted and absent (a failed build): the twin, counted a call
    monkeypatch.delenv("KTPU_NATIVE_INGEST")
    monkeypatch.setitem(native._INGEST_FNS, "p2_fold", None)
    assert _state(_folded(values, 250)) == want
    assert counter.value(site="p2-fold") == before + 4


def test_the_scalar_estimator_is_untouched_by_the_fold():
    """``P2Quantile`` alone (no set, no lock) is the twin and nothing
    else: the fold reads and writes its lists, never its code."""
    est = P2Quantile(0.5)
    for x in (5.0, 1.0, 4.0, 2.0, 3.0, 6.0):
        est.observe(x)
    assert est.count == 6 and est.value() == est._heights[2]
