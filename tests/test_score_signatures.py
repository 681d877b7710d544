"""A batch whose pods name many images is solved on the device.

Until PR 48 a live score family carried at most 16 static rows, keyed
on the pod's images, its preferred node affinity, its tolerations and
its controller, and the seventeenth sent the whole batch to the host
path (``pods_fallback``). Now a live family always carries
``MAX_SCORE_SIGS`` rows (one shape: what warm-up compiles is what every
live batch runs), a pod's signature holds only the parts whose family is
live for the batch, and a batch that still asks for more rows is cut
where the cap is met, both parts staying on the device.

Held here, at a CPU's size: batches of 17, 48 and 64 image lists place
pod for pod as the sequential host oracle does (KeepFirst tie RNG) on
the XLA scan, on a mesh, and in the fused kernel (interpret mode) against
the XLA scan; the batch past the cap is cut and counted; a batch that
cannot be cut goes to the host path and is counted; warm-up leaves no
shape of a live batch uncompiled.
"""

import math
import random
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.cache.cache import SchedulerCache
from kubernetes_tpu.cache.snapshot import Snapshot
from kubernetes_tpu.client.client import Client
from kubernetes_tpu.client.informer import InformerFactory
from kubernetes_tpu.ops.assignment import (
    GreedyConfig,
    caps_for_families,
    greedy_assign_constrained,
    jit_cache_sizes,
)
from kubernetes_tpu.ops.affinity import noop_affinity_tensors
from kubernetes_tpu.ops.family_facts import FamilyFacts
from kubernetes_tpu.ops.host_masks import static_mask_compact
from kubernetes_tpu.ops.pallas_constrained import (
    VMEM_BUDGET,
    constrained_vmem_bytes,
    live_caps,
    pallas_constrained_solve,
)
from kubernetes_tpu.ops.scoring import (
    MAX_SCORE_SIGS,
    SIG_BUCKET,
    ScoreEnvelopeCut,
    noop_score_tensors,
    pack_score_batch,
    pad_score_tensors,
)
from kubernetes_tpu.ops.topology import noop_spread_tensors
from kubernetes_tpu.scheduler import batch as batch_mod
from kubernetes_tpu.scheduler.scheduler import new_scheduler
from kubernetes_tpu.tensors import NodeTensorCache, pack_pod_batch
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.utils import metrics

MIB = 1024 * 1024
NODES = 48
WEIGHTS = {"ImageLocality": 1, "NodeAffinity": 1, "TaintToleration": 1}


class _KeepFirstRng:
    def randrange(self, n):
        return 1 if n > 1 else 0

    def randint(self, a, b):
        return b


def _image(k: int) -> str:
    return f"registry.example/app-{k}:v1"


def _nodes(rng, apps: int, soft_taints: bool = False):
    """Nodes of distinct shapes; each app's image on a seeded share of
    them between a quarter and all, at a size of its own, so that every
    app's row differs from the next and scores above 0."""
    out = []
    shares = [rng.uniform(0.25, 0.95) for _ in range(apps)]
    sizes = [rng.randint(200, 1900) * MIB for _ in range(apps)]
    for i in range(NODES):
        w = (
            make_node(f"n{i}")
            .labels(zone=f"z{i % 3}")
            .capacity(cpu=str(8 + i % 23), memory=f"{16 + (i * 7) % 41}Gi",
                      pods=110)
        )
        for k in range(apps):
            if rng.random() < shares[k]:
                w.image(_image(k), sizes[k])
        if soft_taints and i % 5 == 2:
            w.taint("best-effort", "true", effect="PreferNoSchedule")
        out.append(w.obj())
    return out


def _pods(rng, apps: int, each: int = 2, tolerating: bool = False):
    out = []
    for i in range(apps * each):
        w = (
            make_pod(f"m{i}")
            .labels(app=f"app-{i % apps}")
            .creation_timestamp(float(i))
            .container(cpu=f"{rng.choice([100, 300, 700])}m",
                       memory=f"{rng.choice([128, 384])}Mi",
                       image=_image(i % apps))
        )
        if tolerating and i >= apps:  # each app's later pods
            w.toleration(key="best-effort", operator="Exists",
                         effect="PreferNoSchedule")
        out.append(w.obj())
    return out


def _wait_decided(client, sched, count, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        pods, _ = client.list_pods()
        pending = [
            p for p in pods
            if not p.spec.node_name and not p.status.conditions
        ]
        if len(pods) >= count and not pending:
            sched.wait_for_inflight_binds()
            return client.list_pods()[0]
        time.sleep(0.05)
    raise AssertionError("pods not decided in time")


def _spread_pods(count: int):
    """Pods that name no image any node holds and must spread over the
    zones: a constrained batch the score family has no part in."""
    return [
        make_pod(f"s{i}").labels(app="spread").creation_timestamp(float(i))
        .container(cpu="100m", memory="128Mi")
        .spread_constraint(1, "zone", match_labels={"app": "spread"}).obj()
        for i in range(count)
    ]


def _run(seed, apps, batch, mesh=None, warm=0, soft_taints=False,
         spread_first=0):
    """One batch of ``apps`` image lists, two pods each. ``warm``: call
    ``warmup()`` first, then land a batch of that many lists, and
    return the solver programs compiled over the batch that follows.
    ``spread_first``: that many ``_spread_pods`` are the batch before
    it, inside the count."""
    rng = random.Random(seed)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=batch, max_batch=256, mesh=mesh,
        percentage_of_nodes_to_score=100, rng=_KeepFirstRng(),
    )
    for node in _nodes(rng, apps, soft_taints):
        client.create_node(node)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    pods = _pods(rng, apps, tolerating=soft_taints)
    before = None
    if warm:
        sched.warmup()
        sched.start()
        first = _pods(rng, warm)
        for p in first:
            p.metadata.name = "w" + p.metadata.name
            client.create_pod(p)
        _wait_decided(client, sched, len(first))
        before = dict(jit_cache_sizes(mesh))
    for p in _spread_pods(spread_first):
        client.create_pod(p)
    if spread_first:
        _wait_decided(client, sched, 2 * warm + spread_first)
    for p in pods:
        client.create_pod(p)
    if not warm:
        sched.start()
    done = _wait_decided(
        client, sched, len(pods) + 2 * warm + spread_first
    )
    sched.stop()
    informers.stop()
    placed = {p.metadata.name: p.spec.node_name for p in done}
    assert all(placed.values())
    grew = None
    if warm:
        grew = {
            sig: n - before[sig]
            for sig, n in jit_cache_sizes(mesh).items() if n > before[sig]
        }
    return placed, sched, grew


@pytest.mark.parametrize("apps", [17, 48, 64])
def test_a_batch_of_many_image_lists_places_as_the_host_oracle(apps):
    """One batch, ``apps`` distinct image lists, every one a live row:
    on the device, pod for pod the sequential path's placements. On PR
    47's tree the seventeenth signature sent every pod to the host path
    (``pods_fallback`` = the batch)."""
    cuts = metrics.score_signature_caps.value(action="cut")
    placed, sched, _ = _run(7, apps, batch=True)
    assert sched.pods_fallback == 0
    assert sched.envelope_fallbacks == 0
    assert sched.score_signature_cuts == 0
    assert metrics.score_signature_caps.value(action="cut") == cuts
    facts = sched.family_facts
    assert facts.score_live == sched.batches_solved == 1
    assert facts.score_sigs == facts.score_image_sigs_live == apps
    assert sched.ladder.solves_by_tier["xla"] == 1
    oracle, _, _ = _run(7, apps, batch=False)
    assert placed == oracle


def test_many_image_lists_on_a_mesh_place_as_the_host_oracle():
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip(f"need 4 devices, have {len(devices)}")
    mesh = Mesh(np.array(devices[:4]), axis_names=("nodes",))
    placed, sched, _ = _run(11, 48, batch=True, mesh=mesh)
    assert sched.pods_fallback == 0
    assert sched.family_facts.score_sigs == 48
    oracle, _, _ = _run(11, 48, batch=False)
    assert placed == oracle


def test_the_batch_past_the_cap_is_cut_and_stays_on_the_device():
    """80 live image lists, two pods each, in one pop: cut at the pod
    that asked for the 65th row, and what is left (the 16 other lists,
    then all 80 again) cut once more; the three parts solved one after
    the other on the device, counted, and placed as the sequential path
    places them."""
    cuts = metrics.score_signature_caps.value(action="cut")
    hosted = metrics.score_signature_caps.value(action="host")
    placed, sched, _ = _run(3, 80, batch=True)
    assert sched.pods_fallback == 0
    assert sched.envelope_fallbacks == 0
    assert sched.score_signature_cuts == 2
    assert sched.score_signature_host == 0
    assert metrics.score_signature_caps.value(action="cut") == cuts + 2
    assert metrics.score_signature_caps.value(action="host") == hosted
    assert sched.batches_solved == 3
    facts = sched.family_facts
    assert facts.score_live == 3
    # 64 pods of 64 lists, 64 pods of 16 + 48, and the 32 lists left
    assert facts.score_sigs == 2 * MAX_SCORE_SIGS + 32
    oracle, _, _ = _run(3, 80, batch=False)
    assert placed == oracle


def _run_twice(seed, apps, batch):
    """Two batches of ``apps`` image lists, two pods each, the second
    created when the first is decided."""
    rng = random.Random(seed)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=batch, max_batch=256,
        percentage_of_nodes_to_score=100, rng=_KeepFirstRng(),
    )
    for node in _nodes(rng, apps):
        client.create_node(node)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    for wave in range(2):
        for p in _pods(rng, apps):
            p.metadata.name = f"w{wave}-{p.metadata.name}"
            client.create_pod(p)
        if not wave:
            sched.start()
        done = _wait_decided(client, sched, 2 * apps * (wave + 1))
    sched.stop()
    informers.stop()
    placed = {p.metadata.name: p.spec.node_name for p in done}
    assert all(placed.values())
    return placed, sched


def test_a_second_batch_takes_its_rows_from_the_first_and_places_as_the_oracle():
    """The kept node-side rows through the dispatcher: the first batch
    builds the zone rows and its 48 lists' rows, its binds move no Node
    object, and every later batch is served from the store; placements
    are the sequential path's, pod for pod."""
    apps = 48
    placed, sched = _run_twice(23, apps, batch=True)
    assert sched.pods_fallback == 0
    facts = sched.family_facts
    assert facts.score_live == sched.batches_solved >= 2
    assert facts.score_image_sigs_live >= 2 * apps
    # nothing was built twice: the zones and each list once
    assert facts.score_node_rows - facts.score_node_rows_reused == 1 + apps
    assert facts.score_node_rows_reused >= 1 + apps
    oracle, _ = _run_twice(23, apps, batch=False)
    assert placed == oracle


def _snapshot(seed, apps, soft_taints=False):
    rng = random.Random(seed)
    cache = SchedulerCache()
    for node in _nodes(rng, apps, soft_taints):
        cache.add_node(node)
    snap = cache.update_snapshot(Snapshot())
    return rng, snap, NodeTensorCache().update(snap)


def test_the_packer_says_where_to_cut():
    rng, snap, nt = _snapshot(5, 70)
    pods = _pods(rng, 70, each=1)
    with pytest.raises(ScoreEnvelopeCut) as cap:
        pack_score_batch(pods, snap, nt, None, WEIGHTS)
    assert cap.value.fit == MAX_SCORE_SIGS
    facts = FamilyFacts()
    got = pack_score_batch(
        pods[:cap.value.fit], snap, nt, None, WEIGHTS, facts=facts
    )
    assert got.direct_rows.shape == (MAX_SCORE_SIGS, nt.capacity)
    assert facts.score_sigs == MAX_SCORE_SIGS
    assert sorted(set(got.pod_sig.tolist())) == list(range(MAX_SCORE_SIGS))


def test_a_batch_that_cannot_be_cut_goes_to_the_host_path_and_is_counted():
    """A gang's batch is decided whole and a bisection's half is the
    caller's to split: past the cap they take the host route, under
    their own count."""
    rng = random.Random(9)
    server = APIServer()
    client = Client(server)
    informers = InformerFactory(server)
    sched = new_scheduler(
        client, informers, batch=True, max_batch=256,
        percentage_of_nodes_to_score=100, rng=_KeepFirstRng(),
    )
    for node in _nodes(rng, 70):
        client.create_node(node)
    informers.start()
    informers.wait_for_cache_sync()
    sched.queue.run()
    pods = _pods(rng, 70, each=1)
    for p in pods:
        client.create_pod(p)
    hosted = metrics.score_signature_caps.value(action="host")
    deadline = time.time() + 30
    infos = []
    while len(infos) < len(pods) and time.time() < deadline:
        infos += sched.queue.pop_batch(256, timeout=0.5)
    assert len(infos) == len(pods)
    pending = sched._dispatch_solve(
        infos, sched.queue.scheduling_cycle, raise_on_exhaust=True
    )
    assert pending is None  # routed: every pod through attempt_schedule
    assert sched.score_signature_host == 1
    assert sched.score_signature_cuts == 0
    assert sched.envelope_fallbacks == 1
    assert sched.pods_fallback == len(pods)
    assert metrics.score_signature_caps.value(action="host") == hosted + 1
    sched.wait_for_inflight_binds()
    sched.stop()
    informers.stop()
    assert all(p.spec.node_name for p in client.list_pods()[0])


def test_a_signature_holds_only_what_is_live_for_the_batch():
    """Pods that differ in their tolerations share a row on a cluster
    without a PreferNoSchedule taint, and take a row each with one: the
    parts of a signature are those a live family reads."""
    for soft_taints, sigs in ((False, 20), (True, 40)):
        rng, snap, nt = _snapshot(13, 20, soft_taints)
        pods = _pods(rng, 20, each=2, tolerating=True)
        facts = FamilyFacts()
        got = pack_score_batch(pods, snap, nt, None, WEIGHTS, facts=facts)
        assert facts.score_sigs == sigs
        assert bool(got.taint_rows.any()) == soft_taints
    # an image list no node holds takes no row of its own
    rng, snap, nt = _snapshot(13, 20)
    pods = _pods(rng, 20, each=1)
    for i, p in enumerate(pods[:10]):
        p.spec.containers[0].image = f"registry.example/nowhere-{i}:v1"
    facts = FamilyFacts()
    pack_score_batch(pods, snap, nt, None, WEIGHTS, facts=facts)
    assert facts.score_image_sigs == 20
    assert facts.score_image_sigs_live == 10
    assert facts.score_sigs == 11  # ten live lists, one row of zeros


def _packed(seed, apps):
    """The constrained call's operands for one live batch of ``apps``
    image lists, as the dispatcher packs them."""
    rng, snap, nt = _snapshot(seed, apps)
    pods = _pods(rng, apps)
    batch = pack_pod_batch(pods, nt.dims)
    mask_rows, mask_index = static_mask_compact(pods, snap, nt)
    b = batch.size
    padded = batch_mod.POD_BUCKET * math.ceil(b / batch_mod.POD_BUCKET)
    order = batch.order
    req = np.zeros((padded, nt.dims.num_dims), dtype=np.int32)
    nzr = np.zeros((padded, 2), dtype=np.int32)
    midx = np.zeros(padded, dtype=np.int32)
    active = np.zeros(padded, dtype=bool)
    req[:b] = batch.requests[order]
    nzr[:b] = batch.non_zero_requests[order]
    midx[:b] = mask_index[order]
    active[:b] = True
    u = batch_mod.MASK_ROW_BUCKET * math.ceil(
        mask_rows.shape[0] / batch_mod.MASK_ROW_BUCKET
    )
    rows = np.zeros((u, nt.capacity), dtype=bool)
    rows[:mask_rows.shape[0]] = mask_rows
    sc = pack_score_batch(
        [pods[int(i)] for i in order], snap, nt, None, WEIGHTS
    )
    common = (
        nt.allocatable, nt.requested, nt.non_zero_requested, nt.valid,
        req, nzr, rows, midx, active,
    )
    return (
        common,
        tuple(noop_spread_tensors(padded, nt.capacity)),
        tuple(noop_affinity_tensors(padded, nt.capacity)),
        tuple(pad_score_tensors(sc, padded)),
    )


@pytest.mark.parametrize("apps", [17, 48, 64])
def test_the_fused_kernel_takes_the_rows_as_the_xla_scan_does(apps):
    """The Pallas tier at the live shape, in interpret mode, under the
    specialization the dispatcher picks for a batch whose only live
    family is the score family."""
    common, sp_t, af_t, sc_t = _packed(21, apps)
    assert sc_t[0].shape[0] == MAX_SCORE_SIGS
    assert int(sc_t[3].max()) == apps - 1
    caps = caps_for_families(sp_t, af_t, sc_t, False, False, True)
    a1, r1, z1 = greedy_assign_constrained(
        *common, sp_t, af_t, sc_t, config=GreedyConfig()
    )
    a2, r2, z2 = pallas_constrained_solve(
        *common, sp_t, af_t, sc_t, config=GreedyConfig(), interpret=True,
        caps=caps,
    )
    assert (np.asarray(a1)[:2 * apps] >= 0).all()
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_the_vmem_gate_counts_the_live_rows_at_the_cells_shape():
    """At 5,632 node slots the three ``[64, n]`` operands are 4.3 MB of
    the estimate; the score family alone and all three families at their
    default caps stay under the 13 MiB gate (the chip compiles both:
    PERF.md section 6, PR 48), and the placeholders of an absent family
    stay ``SIG_BUCKET`` rows, so a batch without the family pays
    nothing for the cap."""
    n, r, u, z, v_sp = 5632, 4, 8, 64, 5632
    only = live_caps(False, False, True)
    every = live_caps(True, True, True)
    live = constrained_vmem_bytes(n, r, u, MAX_SCORE_SIGS, z, v_sp, only)
    assert live - constrained_vmem_bytes(
        n, r, u, SIG_BUCKET, z, v_sp, only
    ) == 3 * (MAX_SCORE_SIGS - SIG_BUCKET) * 4 * n
    assert live < VMEM_BUDGET
    assert constrained_vmem_bytes(
        n, r, u, MAX_SCORE_SIGS, z, v_sp, every
    ) < VMEM_BUDGET
    assert noop_score_tensors(64, n)[0].shape == (SIG_BUCKET, n)
    assert noop_score_tensors(64, n, live_shape=True)[0].shape == (
        MAX_SCORE_SIGS, n
    )


@pytest.mark.parametrize("few,many", [(3, 48), (20, 64)])
def test_warm_up_leaves_no_shape_of_a_live_batch_uncompiled(few, many):
    """After ``warmup()`` and a first live batch of few image lists
    (which brings the node state to the device: the layouts' matter, not
    the rows'), a live batch of many compiles nothing: a live family has
    one shape whatever the batch names, and warm-up's layouts are built
    at it. Until PR 48 the second batch met ``[12, n]`` or ``[16, n]``
    rows where warm-up had compiled ``[4, n]``."""
    _placed, sched, grew = _run(17, many, batch=True, warm=few)
    assert sched.pods_fallback == 0
    assert sched.family_facts.score_live >= 2
    assert sched.family_facts.score_sigs >= few + many
    assert grew == {}


def test_a_mesh_warms_the_family_absent_and_live():
    """On a mesh an absent family rides as real arrays, so the score
    family's two shapes are two signatures of the one constrained
    program: its placeholders' ``SIG_BUCKET`` rows (what a constrained
    batch without the family uploads, as before PR 48) and a live
    batch's ``MAX_SCORE_SIGS``. ``warmup()`` compiles both: a batch of
    spread pods that name no image, then one of 48 image lists, compile
    nothing."""
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip(f"need 2 devices, have {len(devices)}")
    mesh = Mesh(np.array(devices[:2]), axis_names=("nodes",))
    _placed, sched, grew = _run(
        19, 48, batch=True, mesh=mesh, warm=3, spread_first=24
    )
    assert sched.pods_fallback == 0
    facts = sched.family_facts
    # the spread batch(es) packed families and found no score to carry
    assert facts.score_live < sched.batches_solved
    assert facts.score_sigs >= 3 + 48
    assert grew == {}
