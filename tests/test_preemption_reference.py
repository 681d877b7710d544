"""The program's preemption wave against the benchmark's plain reference
(``chipbench/preempt_reference.py``), which shares no code with it and
no reading of the rule: ``Preemptor.preempt_batch`` on the jnp twin of
the device wave, on seeded random small clusters with mixed priorities,
preemptors that need one, two or three victims, and nominations made
before the wave. (``tests/test_preemption_wave.py`` holds the wave to
the program's own host oracle.)

The reference follows the program's choices (``follow``), so that the
two stay in step, and each choice is held to it: the node is one of
those rules 1 to 5 leave tied, and the victims are the ones the
reprieve gives up on that node. Every resident has a start time of its
own, so ``MoreImportantPod`` is a total order and rule 5 decides what
rules 1 to 4 leave."""

import random
import time

import pytest

from chipbench import preempt_reference as ref
from kubernetes_tpu.scheduler.preemption import Preemptor
from kubernetes_tpu.testing import make_node, make_pod
from test_preemption_wave import _env, _fail, _queue

MIB = 1 << 20
NODES = 10


class Recording(Preemptor):
    """Keeps each preemptor's own victims: ``preempt_batch`` returns the
    wave's victims as one set."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.chosen = {}

    def _apply_preemption(self, prof, pod, node_name, victims, **kwargs):
        self.chosen[pod.metadata.name] = [v.metadata.name for v in victims]
        return super()._apply_preemption(
            prof, pod, node_name, victims, **kwargs)


def random_cluster(rng):
    """Ten nodes of 8 CPU, each filled to under 1000m free with pods of
    four sizes and four priorities."""
    t0 = time.time() - 20_000
    starts = rng.sample(range(10_000), 200)
    nodes = [
        make_node(f"n{i}").capacity(cpu="8", memory="32Gi", pods=32).obj()
        for i in range(NODES)
    ]
    pods = []
    for i in range(NODES):
        free = 8000
        while free >= 1000:
            cpu = rng.choice([c for c in (500, 1000, 1500, 2000) if c <= free])
            p = (
                make_pod(f"r{len(pods)}").node(f"n{i}")
                .container(cpu=f"{cpu}m", memory="256Mi")
                .priority(rng.choice([0, 0, 5, 10, 50])).obj()
            )
            p.status.start_time = t0 + starts.pop()
            pods.append(p)
            free -= cpu
    return nodes, pods


def as_ref_pod(p):
    return ref.Pod(
        p.metadata.name, p.spec.priority,
        p.spec.containers[0].resources.requests["cpu"],
        p.spec.containers[0].resources.requests["memory"],
        p.status.start_time or 0.0,
    )


def deciding_rules(candidates):
    """Which of rules 1 to 5 told candidates apart that every earlier
    rule had left tied."""
    keys = [ref.node_key(v) for v in candidates.values()]
    decided = set()
    for rule in range(5):
        best = min(k[rule] for k in keys)
        if any(k[rule] != best for k in keys):
            decided.add(rule + 1)
        keys = [k for k in keys if k[rule] == best]
    return decided


def hold_to_reference(nodes, pods, wave, nominations,
                      cap=(8000, 32 << 30, 32)):
    """Run the wave through the program and hold every choice to the
    reference; returns the rules that decided some preemptor's node."""
    algorithm, fw = _env(pods, nodes)
    items = [(p, _fail(algorithm, fw, p)) for p in wave]
    queue = _queue(fw)
    for pod, node in nominations:
        queue.update_nominated_pod_for_node(pod, node)
    program = Recording(algorithm, queue, None)
    chosen, _ = program.preempt_batch(fw, items)
    assert program.wave_solver_tier == "xla"  # the jnp twin, on a CPU
    assert program.device_preemptions == len(wave)
    assert program.last_wave["searched"] == len(wave)
    assert program.searched_again == {}

    ref_nodes = [
        ref.Node(n.metadata.name, *cap, [
            as_ref_pod(p) for p in pods
            if p.spec.node_name == n.metadata.name
        ]) for n in nodes
    ]
    before = {}
    for pod, node in nominations:
        before.setdefault(node, []).append(as_ref_pod(pod))
    preemptors = [as_ref_pod(p) for p in wave]
    decisions = ref.wave(
        ref_nodes, preemptors, nominated=before, follow=chosen)
    for pod, node, decision in zip(wave, chosen, decisions):
        name = pod.metadata.name
        assert (node == "") == (decision.tied == []), (name, decision.tied)
        if node:
            assert node in decision.tied, (name, node, decision.tied)
            assert sorted(program.chosen[name]) == sorted(
                v.name for v in decision.victims), (name, node)

    # which rules decided, read from the reference alone
    decided = set()
    noms = {n.name: list(before.get(n.name, ())) for n in ref_nodes}
    for preemptor, node, decision in zip(preemptors, chosen, decisions):
        candidates = {}
        for n in ref_nodes:
            found = ref.select_victims(n, preemptor, noms[n.name])
            if found is not None:
                candidates[n.name] = found
        assert ref.pick_node(candidates) == decision.tied
        if candidates:
            decided |= deciding_rules(candidates)
        if node:
            noms[node].append(preemptor)
    return decided


def random_case(seed):
    rng = random.Random(1000 + seed)
    nodes, pods = random_cluster(rng)
    nominations = [
        (make_pod(f"nom{i}").container(cpu=f"{cpu}m", memory="256Mi")
         .priority(prio).obj(), f"n{rng.randrange(NODES)}")
        for i, (cpu, prio) in enumerate(((1000, 90), (500, 60)))
    ]
    wave = [
        make_pod(f"wave{j}")
        .container(cpu=f"{rng.choice([1000, 2000, 3000])}m", memory="512Mi")
        .priority(rng.choice([100, 80, 80, 40])).obj()
        for j in range(6)
    ]
    wave.sort(key=lambda p: -p.spec.priority)
    return nodes, pods, wave, nominations


#: seed -> the rules that decided in its cluster (each case fills its own)
DECIDED = {}


@pytest.mark.parametrize("seed", range(8))
def test_the_programs_wave_is_one_the_reference_allows(seed):
    DECIDED[seed] = hold_to_reference(*random_case(seed))


def test_rules_two_and_three_each_decided_in_a_random_cluster():
    """Over the seeds above: where the cases ran in another process,
    this one runs them again."""
    for seed in range(8):
        if seed not in DECIDED:
            DECIDED[seed] = hold_to_reference(*random_case(seed))
    assert {2, 3} <= set().union(*DECIDED.values()), DECIDED


def test_rule_four_decides_where_a_second_victim_counts_nothing():
    """Rule 3 counts a victim as priority + 2**31, so two nodes tie on
    it with different numbers of victims only where a victim's priority
    is the lowest there is. Node ``n0`` gives up one pod, ``n1`` the
    same and one of priority -2**31: rules 2 and 3 tie, rule 4 takes
    ``n0``."""
    lowest = -(1 << 31)
    t0 = time.time() - 20_000
    nodes = [
        make_node(f"n{i}").capacity(cpu="8", memory="32Gi", pods=32).obj()
        for i in range(2)
    ]
    shapes = {"n0": [(6000, 50), (2000, 7)],
              "n1": [(6000, 50), (1000, 7), (1000, lowest)]}
    pods = []
    for node, residents in shapes.items():
        for cpu, prio in residents:
            p = (make_pod(f"r{len(pods)}").node(node)
                 .container(cpu=f"{cpu}m", memory="256Mi")
                 .priority(prio).obj())
            p.status.start_time = t0 + len(pods)
            pods.append(p)
    wave = [make_pod("wave0").container(cpu="2000m", memory="512Mi")
            .priority(20).obj()]
    assert hold_to_reference(nodes, pods, wave, []) == {4}


def test_a_run_with_the_rule_broken_underneath_is_not_correct(
        capsys, monkeypatch):
    """The cell at rehearsal size with the preemption pack made to read
    ``mid`` pods (priority 10) as less important than the priority-0
    ``filler``s. The program stays consistent with itself: every
    preemptor binds and fits, one resident leaves for each, none of the
    preemptors' priority, on the tiers expected. Only the comparison
    with the reference can tell: ``mid`` pods leave while fillers are
    left."""
    import json

    from chipbench import harness
    from kubernetes_tpu.ops.preempt_facts import PreemptFacts

    # every pack the wave searches comes through the kept store's
    # ``pack``, the whole build it starts from and the rows it advances
    # alike: both read the priorities crooked
    real = PreemptFacts.pack
    made = []

    def crooked(self, snapshot, nt, pdbs):
        mids = [p for ni in snapshot.list_node_infos() for p in ni.pods
                if p.spec.priority == 10]
        for p in mids:
            p.spec.priority = -1
        try:
            pack = real(self, snapshot, nt, pdbs)
            made.append(pack.made)
            return pack
        finally:
            for p in mids:
                p.spec.priority = 10

    monkeypatch.setattr(PreemptFacts, "pack", crooked)
    rc = harness.main([
        "--workload", "priority-tiers-5000.preempt-1k", "--seed",
        str(2**31 + 32), "--seconds", "1", "--trace", "0", "--rehearsal",
    ])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    line = json.loads(out[-1])
    assert line["correct"] is False and line["failed"] == 0
    failed = [l for l in out if l.startswith("compare ") and l.endswith("FAILED")]
    assert [l.split(":")[0] for l in failed] == [
        "compare window against the reference"]
    assert any(" mid" in l for l in out if l.startswith("victims a wave"))
    assert made[0] == "built" and "advanced" in made[1:], made
