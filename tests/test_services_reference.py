"""``chipbench/services_reference.py`` against a brute-force sequential
scheduler of the two plugins, written pod by pod and map by map as the
two source files have them (default_pod_topology_spread.go,
interpodaffinity/scoring.go), on 6 to 12 nodes: every node's total is
the brute force's; the certificate accepts every order of tie-breaks the
brute force can produce and refuses a placement it cannot; the broken
rules of the controls are what they say; the Zipf shares and the
residents' deal are the file's."""

import random
from fractions import Fraction

import numpy as np
import pytest

from chipbench import reference, services_reference as sr

HOST = "kubernetes.io/hostname"
CPU, MEM = 250, 512 << 20
WEIGHT = 100


class Brute:
    """Pods as records, scored as the source's loops score them."""

    def __init__(self, rng, nodes, zones, services):
        self.rng = rng
        self.n, self.services = nodes, services
        self.zone = [f"zone-{i % zones}" for i in range(nodes)]
        self.cap = [(rng.choice([4000, 8000]), rng.choice([8, 16]) << 30,
                     rng.choice([6, 110])) for _ in range(nodes)]
        self.pods = []  # {"node", "service" (None: a bare pod), "deleting"}
        for i in range(nodes):
            for _ in range(rng.randrange(3)):
                self.pods.append({"node": i, "service": None})
        for _ in range(rng.randrange(2 * nodes)):
            self.pods.append({"node": rng.randrange(nodes),
                              "service": rng.randrange(services)})

    def used(self, i):
        mine = [p for p in self.pods if p["node"] == i]
        return len(mine) * CPU, len(mine) * MEM, len(mine)

    def feasible(self, i):
        cpu, mem, pods = self.used(i)
        cap = self.cap[i]
        return (cpu + CPU <= cap[0] and mem + MEM <= cap[1]
                and pods + 1 <= cap[2])

    def resource(self, i):
        """LeastAllocated and BalancedAllocation, requested counted with
        the incoming pod; the quotients exact, as ``reference.scores``."""
        cpu, mem, _ = self.used(i)
        cap_cpu, cap_mem, _ = self.cap[i]
        req = (cpu + CPU, mem + MEM)
        least = sum(
            (c - r) * 100 // c if r <= c else 0
            for c, r in zip((cap_cpu, cap_mem), req)
        ) // 2
        f_cpu, f_mem = Fraction(req[0], cap_cpu), Fraction(req[1], cap_mem)
        balanced = 0 if f_cpu >= 1 or f_mem >= 1 else int(
            (1 - abs(f_cpu - f_mem)) * 100)
        return least + balanced

    def totals(self, k):
        """node -> total, over the nodes that pass the filters."""
        filtered = [i for i in range(self.n) if self.feasible(i)]
        # -- DefaultPodTopologySpread: Score, then NormalizeScore
        count = {
            i: sum(1 for p in self.pods
                   if p["node"] == i and p["service"] == k)
            for i in filtered
        }
        by_zone, max_node = {}, 0
        for i in filtered:
            max_node = max(max_node, count[i])
            by_zone[self.zone[i]] = by_zone.get(self.zone[i], 0) + count[i]
        max_zone = max(by_zone.values(), default=0)
        spread = {}
        for i in filtered:
            f = 100.0
            if max_node > 0:
                f = 100.0 * (float(max_node - count[i]) / float(max_node))
            zone_score = 100.0
            if max_zone > 0:
                zone_score = 100.0 * (
                    float(max_zone - by_zone[self.zone[i]]) / float(max_zone))
            f = f * (1.0 - 2.0 / 3.0) + (2.0 / 3.0) * zone_score
            spread[i] = int(f)
        # -- InterPodAffinity: PreScore's topologyScore, Score, Normalize
        topo = {}  # (key, value) -> summed weight
        for e in self.pods:
            value = f"node-{e['node']}"
            # the incoming pod's anti-affinity term over the existing pod
            if e["service"] == k:
                topo[(HOST, value)] = topo.get((HOST, value), 0) - WEIGHT
            # the existing pod's term over the incoming pod
            if e["service"] is not None and e["service"] == k:
                topo[(HOST, value)] = topo.get((HOST, value), 0) - WEIGHT
        raw = {i: topo.get((HOST, f"node-{i}"), 0) for i in filtered}
        hi = max([0] + list(raw.values()))
        lo = min([0] + list(raw.values()))
        affinity = {
            i: int(100.0 * (float(raw[i] - lo) / float(hi - lo)))
            if hi > lo else 0
            for i in filtered
        }
        return {i: self.resource(i) + spread[i] + affinity[i]
                for i in filtered}

    def place_any(self, k):
        """A node of the top class, any of them."""
        totals = self.totals(k)
        if not totals:
            return -1
        best = max(totals.values())
        node = self.rng.choice([i for i, t in totals.items() if t == best])
        self.pods.append({"node": node, "service": k})
        return node

    def state(self) -> sr.State:
        n = self.n
        used = [self.used(i) for i in range(n)]
        nodes = reference.Nodes(
            cap_cpu=np.array([c[0] for c in self.cap], dtype=np.int64),
            cap_mem=np.array([c[1] for c in self.cap], dtype=np.int64),
            cap_pods=np.array([c[2] for c in self.cap], dtype=np.int64),
            used_cpu=np.array([u[0] for u in used], dtype=np.int64),
            used_mem=np.array([u[1] for u in used], dtype=np.int64),
            used_pods=np.array([u[2] for u in used], dtype=np.int64),
            zone=np.array([int(z.split("-")[1]) for z in self.zone]),
        )
        counts = np.zeros((self.services, n), dtype=np.int64)
        for p in self.pods:
            if p["service"] is not None:
                counts[p["service"], p["node"]] += 1
        return sr.State(
            nodes, reference.PodClass(cpu=CPU, mem=MEM), counts, WEIGHT)


def brute(seed):
    rng = random.Random(seed)
    return Brute(rng, rng.randrange(6, 13), rng.randrange(1, 4),
                 rng.randrange(2, 5))


@pytest.mark.parametrize("seed", range(12))
def test_every_nodes_total_is_the_brute_forces(seed):
    b = brute(seed)
    for step in range(14):
        k = b.rng.randrange(b.services)
        state = b.state()
        feasible, total = sr.totals(state, k)
        want = b.totals(k)
        assert sorted(np.nonzero(feasible)[0].tolist()) == sorted(want)
        for i, t in want.items():
            assert int(total[i]) == t, (seed, step, i)
        b.place_any(k)


@pytest.mark.parametrize("seed", range(12))
def test_the_certificate_accepts_every_order_the_brute_force_produces(seed):
    b = brute(100 + seed)
    before = b.state()
    arrivals = [b.rng.randrange(b.services) for _ in range(20)]
    placed = [b.place_any(k) for k in arrivals]
    assert sr.certify(before, arrivals, placed) == 0
    # and the reference's own lowest-index order is one of them
    b2 = brute(100 + seed)
    own = sr.schedule(b2.state(), arrivals)
    assert sr.certify(b2.state(), arrivals, own) == 0


@pytest.mark.parametrize("seed", range(8))
def test_the_certificate_refuses_a_placement_no_tie_break_produces(seed):
    b = brute(200 + seed)
    before = b.state()
    arrivals = [b.rng.randrange(b.services) for _ in range(16)]
    placed, moved = [], 0
    for step, k in enumerate(arrivals):
        totals = b.totals(k)
        best = max(totals.values())
        worse = [i for i, t in totals.items() if t < best]
        if step % 5 == 2 and worse:
            node = b.rng.choice(worse)  # below the top class
            b.pods.append({"node": node, "service": k})
            moved += 1
        else:
            node = b.place_any(k)
        placed.append(node)
    assert moved > 0
    # each such pod is counted, and the replay goes on from where the
    # pod was put, so no pod after it is counted for it
    assert sr.certify(before, arrivals, placed) == moved
    # a pod bound nowhere though a node was feasible, and one on a node
    # that does not pass the filters
    b = brute(200 + seed)
    state = b.state()
    full = [i for i in range(b.n) if not b.feasible(i)]
    assert sr.certify(b.state(), [0], [-1]) == 1
    if full:
        assert sr.certify(state, [0], [full[0]]) == 1


def test_the_broken_rules_are_what_they_say():
    b = brute(7)
    state = b.state()
    k = 1
    _, full = sr.totals(state, k)
    _, deaf_spread = sr.totals(state, k, sr.Rule(spread=False))
    _, deaf_aff = sr.totals(state, k, sr.Rule(affinity=False))
    feasible = state.feasible()
    spread = sr.spread_score(state.counts[k], feasible, state.nodes.zone,
                             state.zones)
    aff = sr.affinity_score(-2 * WEIGHT * state.counts[k], feasible)
    assert np.array_equal(full - deaf_spread, spread)
    assert np.array_equal(full - deaf_aff, aff)
    _, shifted = sr.totals(state, k, sr.Rule(shift=1))
    _, other = sr.totals(state, (k + 1) % b.services)
    assert np.array_equal(shifted, other)
    # the residents' half alone: before the wave places a pod the
    # owners' weight is 0, and the incoming pod's own term is all
    _, half = sr.totals(state, k, sr.Rule(residents_symmetric=False))
    assert np.array_equal(
        half - deaf_aff,
        sr.affinity_score(-WEIGHT * state.counts[k], feasible))
    # one term a pod: the weight cancels in the normalize
    assert np.array_equal(
        aff, sr.affinity_score(-7 * state.counts[k], feasible))
    with pytest.raises(ValueError):
        sr.totals(state, k, sr.Rule(precision="float32"))


def test_the_shares_and_the_deal_are_the_files():
    shares = sr.zipf_shares(20000, 48, 1.0, 20261051)
    assert shares.sum() == 20000 and len(shares) == 48
    assert (int(shares.max()), int(shares.min())) == (4486, 93)
    wave = sr.zipf_shares(5000, 48, 1.0, 20261051)
    assert (wave.sum(), int(wave.max()), int(wave.min())) == (5000, 1121, 23)
    # the same ranks to the same services, whatever the count
    assert np.array_equal(np.argsort(-shares, kind="stable")[:24],
                          np.argsort(-wave, kind="stable")[:24])
    # another seed deals the ranks to other services, the multiset stays
    other = sr.zipf_shares(20000, 48, 1.0, 7)
    assert sorted(other) == sorted(shares) and not np.array_equal(
        other, shares)
    seats = sr.resident_nodes(shares, 5000, 20261051)
    per_node = np.bincount(np.concatenate(seats), minlength=5000)
    assert per_node.min() == per_node.max() == 4
    for rows in seats:  # a service's replicas on distinct nodes
        assert len(set(rows.tolist())) == len(rows)
