"""``chipbench/image_reference.py``: the plain reference that knows the
nodes' images, and the comparison ``image-locality-5000`` is held to.

- the module imports nothing of the program;
- ImageLocality as the source states it equals the program's plugin on
  the deployment's own catalogue, row for row;
- the lemma by brute force: on small clusters, every order of arrival
  and every way of breaking ties gives a placement the comparison reads
  0 for, with resource scores that rise and fall too;
- the three controls at a small size: the reference deaf to images, the
  reference that scores each app by another app's row, and float32
  against the exact integers;
- the program against the reference on seeded clusters: the device's
  placements read 0, and the same scheduler deaf to ``ImageLocality``
  does not.
"""

import ast
import itertools
import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import image_reference as ref
from chipbench import reference

ROOT = Path(__file__).resolve().parents[1]
MIB = 1 << 20
SPEC = {
    "seed": 7, "registry": "registry.example", "apps": 6,
    "size_mib": [40, 2000], "holder_share": [0.05, 0.95],
    "max_per_node": 50, "infra": [["registry.example/infra/pause:3.2", 1]],
}


def test_the_module_imports_nothing_of_the_program():
    tree = ast.parse((ROOT / "chipbench" / "image_reference.py").read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
            names |= {f"{node.module}.{a.name}" for a in node.names}
    assert not any(n.startswith("kubernetes_tpu") for n in names), names
    assert {n.split(".")[0] for n in names} <= {
        "__future__", "dataclasses", "numpy", "chipbench"}


def nodes_of(n, cpu=32000, mem=64 << 30, pods=110, used=None):
    used = np.zeros(n, dtype=np.int64) if used is None else used
    return reference.Nodes(
        cap_cpu=np.full(n, cpu, dtype=np.int64),
        cap_mem=np.full(n, mem, dtype=np.int64),
        cap_pods=np.full(n, pods, dtype=np.int64),
        used_cpu=used * 250, used_mem=used * 512 * MIB, used_pods=used.copy(),
        zone=np.arange(n, dtype=np.int64) % 2,
    )


PLAIN = reference.PodClass(cpu=250, mem=512 * MIB)


# -- ImageLocality as the source states it -----------------------------------


def test_the_deployments_rows_equal_the_programs_plugin():
    """The catalogue of ``image-locality-5000`` through the program's own
    ``ImageLocality`` on a snapshot of 5,000 nodes that report it, against
    ``image_scores``: equal on every (app, node). The source cuts each
    image's scaled size to whole bytes and divides in whole numbers; the
    plugin keeps floats: on this catalogue they agree everywhere."""
    from kubernetes_tpu.cache.cache import SchedulerCache
    from kubernetes_tpu.cache.snapshot import Snapshot
    from kubernetes_tpu.framework.interface import CycleState
    from kubernetes_tpu.plugins.imagelocality import ImageLocality
    from kubernetes_tpu.testing import make_node, make_pod

    config = json.loads(
        (ROOT / "chipbench/configs/image-locality-5000.json").read_text()
    )
    n = config["cluster"]["nodes"]
    cat = ref.catalogue(config["images"], n)
    assert len(cat.apps) == 48 and cat.holds.shape == (48, n)
    per_node = cat.holds.sum(axis=0) + len(cat.infra)
    assert per_node.max() <= 50 and 25 <= per_node.mean() <= 35
    assert 140_000 <= cat.pairs() <= 160_000
    want = ref.image_scores(cat)
    assert 30 <= (want.max(axis=1) > 0).sum() < 48  # some score 0 everywhere
    assert want.max() == 100
    cache = SchedulerCache()
    for j in range(n):
        w = make_node(f"node-{j}")
        for image, size in cat.node_images(j):
            w.image(image, size)
        cache.add_node(w.obj())
    snap = cache.update_snapshot(Snapshot())
    state = CycleState()
    state.write("__snapshot__", snap)
    plugin = ImageLocality()
    rng = np.random.default_rng(0)
    for k in range(48):
        pod = make_pod(f"p{k}").container(image=cat.apps[k]).obj()
        for j in rng.choice(n, size=40, replace=False):
            got, _ = plugin.score(state, pod, f"node-{int(j)}")
            assert got == want[k, j], (k, j)
        holders = snap.image_holders()[cat.apps[k]]
        assert holders.count == cat.holds[k].sum()
    # no pod names an infra image, and none is the wrappers' default
    assert len(cat.infra) == 6
    assert not {"pause"} & {name for name, _ in cat.infra}


def test_the_kubelets_cap_cuts_a_nodes_smallest_images():
    spec = dict(SPEC, apps=30, holder_share=[0.9, 0.95], max_per_node=11)
    cat = ref.catalogue(spec, 40)
    assert (cat.holds.sum(axis=0) <= 10).all()
    assert (cat.holds.sum(axis=0) == 10).any()  # some node was cut
    j = int(np.argmax(cat.holds.sum(axis=0)))
    assert len(cat.node_images(j)) == 11
    sizes = [s for _, s in cat.node_images(j)]
    assert sizes == sorted(sizes, reverse=True)


# -- the lemma by brute force -------------------------------------------------


def every_placement(r, scores, free, arrivals):
    """Every placement the rule can reach for ``arrivals`` (apps, in
    order) under every way of breaking ties: ``r`` [N, depth] the
    resource score of a node's next place, ``scores`` [A, N]."""
    n = r.shape[0]
    seen = set()

    def walk(k, held, got):
        if k == len(arrivals):
            seen.add(got)
            return
        a = arrivals[k]
        open_ = [j for j in range(n) if held[j] < free[j]]
        if not open_:
            seen.add(got)
            return
        total = {j: r[j, held[j]] + scores[a, j] for j in open_}
        best = max(total.values())
        for j in open_:
            if total[j] == best:
                held2 = held[:j] + (held[j] + 1,) + held[j + 1:]
                got2 = tuple(
                    row[:j] + (row[j] + 1,) + row[j + 1:] if i == a else row
                    for i, row in enumerate(got)
                )
                walk(k + 1, held2, got2)

    walk(0, (0,) * n, tuple((0,) * n for _ in range(scores.shape[0])))
    return seen


@pytest.mark.parametrize("seed", range(8))
def test_the_lemma_holds_for_every_order_and_every_tie_break(
        seed, monkeypatch):
    """Four nodes, three apps, six pods: every order of the six and
    every tie-break, by exhaustion. The resource scores are drawn, not
    computed: falling rows, rows with ties, and (odd seeds) rows that
    rise again, which the lemma's ``M`` and ``U`` are for. The
    comparison reads 0 for every placement the rule can reach (what it
    reads for the others is the controls' matter)."""
    rng = np.random.default_rng(seed)
    n, apps, count = 4, 3, 6
    free = rng.integers(1, 4, n)
    depth = int(free.max()) + 1
    r = np.sort(rng.integers(0, 6, (n, depth)), axis=1)[:, ::-1].copy()
    if seed % 2:
        r[rng.integers(n), rng.integers(1, depth)] += 3  # not monotone
    scores = rng.integers(0, 4, (apps, n)) * rng.integers(0, 2, (apps, n))
    nodes = nodes_of(n, pods=1 << 20)
    nodes.cap_pods = free.astype(np.int64)  # room is the pod count's
    monkeypatch.setattr(
        ref, "place_scores",
        lambda nodes_, pod, d, precision="exact": (
            r[:, :d] if d <= depth
            else np.pad(r, ((0, 0), (0, d - depth)))),
    )
    base = np.repeat(np.arange(apps), count // apps)
    reachable = set()
    for order in set(itertools.permutations(base.tolist())):
        reachable |= every_placement(r, scores, free, order)
    assert len(reachable) >= 1
    for got in reachable:
        assert ref.unexplained(nodes, PLAIN, scores, np.array(got)) == 0, got


def test_every_node_of_these_deployments_scores_lower_with_every_pod():
    """``M`` and ``U`` are ``R`` where ``R`` does not rise: so it is on
    every load the ballast grid, the init pods and a window's pods give
    a node of 32 CPU / 64Gi, in whole numbers and in float32 alike."""
    for bc, bm, init in itertools.product(range(8), range(8), range(3)):
        k = np.arange(1, 111)
        cpu = bc * 1000 + bm * 100 + init * 250 + k * 250
        mem = (bc * 128 + bm * 2048 + init * 512 + k * 512) * MIB
        exact = reference.scores(32000, 64 << 30, cpu, mem, "exact")
        assert (np.diff(exact) <= 0).all()
        assert (reference.scores(32000, 64 << 30, cpu, mem, "float32")
                == exact).all()


# -- the controls, at a small size ------------------------------------------


def small_window(seed, n=60, apps=12, count=900):
    rng = np.random.default_rng(seed)
    cat = ref.catalogue(dict(SPEC, seed=seed, apps=apps,
                             holder_share=[0.2, 0.9]), n)
    nodes = nodes_of(n, used=rng.integers(0, 6, n))
    arrivals = ref.zipf_apps(count, apps, 1.0, seed)
    return cat, nodes, ref.image_scores(cat), arrivals


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_rule_reads_0_and_the_three_controls_read_what_they_should(seed):
    cat, nodes, scores, arrivals = small_window(seed)
    assert (scores.max(axis=1) > 0).sum() >= 6
    got, left = ref.schedule(nodes, PLAIN, scores, arrivals)
    assert left == 0 and got.sum() == len(arrivals)
    assert ref.unexplained(nodes, PLAIN, scores, got) == 0
    # another order of the same pods: 0 again, and not the same counts
    shuffled = np.random.default_rng(seed).permutation(arrivals)
    other, _ = ref.schedule(nodes, PLAIN, scores, shuffled)
    assert ref.unexplained(nodes, PLAIN, scores, other) == 0
    # (a) deaf to images: many outside
    deaf, _ = ref.schedule(nodes, PLAIN, np.zeros_like(scores), arrivals)
    assert ref.unexplained(nodes, PLAIN, scores, deaf) >= len(arrivals) // 30
    # (b) each app scored by the next app's row: many outside
    apps = scores.shape[0]
    mixed = ref.image_scores(cat, rows=(np.arange(apps) + 1) % apps)
    blind, _ = ref.schedule(nodes, PLAIN, mixed, arrivals)
    assert ref.unexplained(nodes, PLAIN, scores, blind) >= len(arrivals) // 30
    # (c) float32 is the exact integers here
    f32, _ = ref.schedule(nodes, PLAIN, scores, arrivals, "float32")
    assert (f32 == got).all()
    # pods beyond a node's room, and pods that were bound nowhere
    over = got.copy()
    over[0, 0] += 200
    assert ref.unexplained(nodes, PLAIN, scores, over) >= 200 - 110


def test_bfloat16_is_told_apart_where_nodes_differ_and_not_where_they_fill_alike():
    """The module's own word on precision: on nodes that start alike the
    counts end the same whatever the last bits (the open-loop window);
    on nodes that differ the bfloat16 placement is read as outside."""
    cat, nodes, scores, arrivals = small_window(5)
    low, _ = ref.schedule(nodes, PLAIN, scores, arrivals, "bfloat16")
    assert ref.unexplained(nodes, PLAIN, scores, low) > 0


# -- the program against the reference ---------------------------------------


class _KeepFirstRng:
    def randrange(self, n):
        return 1 if n > 1 else 0

    def randint(self, a, b):
        return b


def run_program(seed, deaf=False, n=40, apps=20, count=240):
    """A seeded cluster whose nodes report the catalogue, ``count`` pods
    of Zipf-drawn apps through ``BatchScheduler`` in batches of 64, and
    what the comparison makes of where they landed."""
    from kubernetes_tpu.apiserver.server import APIServer
    from kubernetes_tpu.client.client import Client
    from kubernetes_tpu.client.informer import InformerFactory
    from kubernetes_tpu.scheduler.scheduler import new_scheduler
    from kubernetes_tpu.testing import make_node, make_pod

    cat = ref.catalogue(dict(SPEC, seed=seed, apps=apps,
                             holder_share=[0.2, 0.9]), n)
    scores = ref.image_scores(cat)
    arrivals = ref.zipf_apps(count, apps, 1.0, seed)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(client, informers, batch=True, max_batch=64,
                          rng=_KeepFirstRng())
    if deaf:
        prof = next(iter(sched.profiles.values()))
        weights = dict(prof.score_plugin_weights())
        weights["ImageLocality"] = 0
        prof.score_plugin_weights = lambda: weights
    for j in range(n):
        w = make_node(f"node-{j}").capacity(cpu="32", memory="64Gi", pods=110)
        for image, size in cat.node_images(j):
            w.image(image, size)
        client.create_node(w.obj())
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    sched.start()
    for i, a in enumerate(arrivals):
        client.create_pod(
            make_pod(f"p{i}").labels(app=f"app-{a}")
            .container(cpu="250m", memory="512Mi", image=cat.apps[int(a)])
            .obj()
        )
    deadline = time.time() + 120
    while time.time() < deadline:
        pods, _ = client.list_pods()
        if sum(1 for p in pods if p.spec.node_name) == count:
            break
        time.sleep(0.05)
    sched.wait_for_inflight_binds()
    sched.stop()
    informers.stop()
    got = np.zeros((apps, n), dtype=np.int64)
    for p in client.list_pods()[0]:
        assert p.spec.node_name
        a = int(p.metadata.labels["app"].split("-")[1])
        got[a, int(p.spec.node_name.split("-")[1])] += 1
    return sched, ref.unexplained(nodes_of(n), PLAIN, scores, got)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_program_reads_0_on_seeded_clusters(seed):
    sched, outside = run_program(seed)
    assert sched.pods_fallback == 0
    assert sched.family_facts.score_live >= 1
    assert sched.family_facts.score_sigs >= 17  # past the old cap
    assert outside == 0


def test_the_program_deaf_to_image_locality_is_read_as_outside():
    sched, outside = run_program(11, deaf=True)
    assert sched.pods_fallback == 0
    assert outside >= 24  # a tenth of the pods and more
