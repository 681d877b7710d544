"""The traffic generators repeat exactly from a seed, and a seed changes
the order of the work, never the work."""

import types

import numpy as np

from chipbench.generators import arrivals, waves


def test_arrival_schedule_is_the_same_gaps_in_another_order():
    a = arrivals.offsets(800.0, 5.0, 11, np.random.default_rng(1))
    again = arrivals.offsets(800.0, 5.0, 11, np.random.default_rng(1))
    b = arrivals.offsets(800.0, 5.0, 11, np.random.default_rng(2**31 + 5))
    assert (a == again).all()
    assert len(a) == len(b) == 4000
    assert (a != b).any()
    gaps = lambda x: np.sort(np.diff(np.concatenate([[0.0], x])))
    assert np.allclose(gaps(a), gaps(b), rtol=0, atol=1e-12)
    assert 0 < a[0] and a[-1] < 5.0 and b[-1] < 5.0
    assert (np.diff(a) > 0).all()


class FakeRun:
    """What waves._build needs of a Run."""

    def __init__(self, seed):
        self.rng = np.random.default_rng(seed)

    def make_pods(self, cls, count, app):
        return [types.SimpleNamespace(cls=cls, app=app, i=i) for i in range(count)]


PARAMS = {
    "wave": [{"class": "spread", "apps": 2, "pods_per_app": 5},
             {"class": "anti", "apps": 1, "pods_per_app": 4}],
    "shuffle": True,
}


def key(p):
    return (p.cls, p.app, p.i)


def test_wave_is_the_same_multiset_shuffled_by_the_seed():
    a = [key(p) for p in waves._build(FakeRun(3), PARAMS)]
    again = [key(p) for p in waves._build(FakeRun(3), PARAMS)]
    b = [key(p) for p in waves._build(FakeRun(4), PARAMS)]
    assert a == again and a != b and sorted(a) == sorted(b)
    assert len(a) == 14
    plain = [key(p) for p in waves._build(FakeRun(3), dict(PARAMS, shuffle=False))]
    assert plain == sorted(plain, key=lambda k: (k[0] != "spread", k[1], k[2]))
