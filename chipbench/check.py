"""What decides ``correct``: the comparisons a configuration names, each a
module ``chipbench/checks/<name>.py`` with one function ``run(run,
control) -> bool`` that prints every number it compares beside its
limit through ``compare``. ``run_checks`` is the one loop over them;
nothing else decides ``correct``.

A configuration's file may carry ``"checks": [...]``. Without the key a
cell gets ``DEFAULT_CHECKS``, in that order:

``replay``
    Every placement of the window against the guarantees the
    configuration states.
``window_reference``
    The window's own placements against the plain reference: for every
    wave of the window (the whole window where nothing is deleted), the
    pods each node received against the fewest and the most the
    published scoring rule lets it receive from the node state before
    the wave. This is the timed path itself, at its own sizes: pods
    without a node selector, scored over the whole cluster.
``check_wave``
    A check wave after the window, scheduled through the API like any
    wave, whose pods go by node selector to a pool of nodes that differ
    in score, compared with the reference in the same way. This is what
    tells float32 scoring from bfloat16 (the control) in every cell;
    ``window_reference`` does so in a burst's wave and not in the
    open-loop window, since on nodes that fill alike water-filling ends
    the same whatever the score's last bits.

``tier`` closes every list: each tier ledger the configuration names
(``expect_tier``, ``expect_tiers``) against what the program booked.

This module keeps the loop and what the comparisons share.
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import reference

MIB = 1 << 20
DEFAULT_CHECKS = ("replay", "window_reference", "check_wave")
LAST_CHECK = "tier"  # always made, after the configuration's own


def parse_cpu_milli(text: str) -> int:
    return int(text[:-1]) if text.endswith("m") else int(float(text) * 1000)


def parse_memory_bytes(text: str) -> int:
    for suffix, shift in (("Ki", 10), ("Mi", 20), ("Gi", 30)):
        if text.endswith(suffix):
            return int(text[:-2]) << shift
    return int(text)


def compare(what: str, value, limit) -> bool:
    ok = value <= limit
    print(f"compare {what}: {value} (limit {limit}) -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def node_index(name: str) -> int:
    return int(name.rsplit("-", 1)[1])


def usage(config: dict, created: dict, snapshot: dict):
    """Per-node cpu, memory and pod count of a snapshot, from the
    classes this harness created the pods with."""
    n = config["cluster"]["nodes"]
    classes = config["pod_classes"]
    cpu = np.zeros(n, dtype=np.int64)
    mem = np.zeros(n, dtype=np.int64)
    pods = np.zeros(n, dtype=np.int64)
    for name, node in snapshot.items():
        cls = classes[created[name]]
        i = node_index(node)
        cpu[i] += cls["cpu_milli"]
        mem[i] += cls["memory_mib"] * MIB
        pods[i] += 1
    return cpu, mem, pods


def nodes_before(run, snapshot: dict) -> reference.Nodes:
    config = run.config
    cluster = config["cluster"]
    shape = cluster["node"]
    n = cluster["nodes"]
    cpu, mem, pods = usage(config, run.created, snapshot)
    return reference.Nodes(
        cap_cpu=np.full(n, parse_cpu_milli(shape["cpu"]), dtype=np.int64),
        cap_mem=np.full(n, parse_memory_bytes(shape["memory"]), dtype=np.int64),
        cap_pods=np.full(n, shape["pods"], dtype=np.int64),
        used_cpu=cpu, used_mem=mem, used_pods=pods,
        zone=np.arange(n, dtype=np.int64) % cluster["zones"],
    )


def wave_size(run, names) -> tuple:
    """(cpu_milli, memory_mib) where every pod of ``names`` asks for the
    same, else None."""
    classes = run.config["pod_classes"]
    sizes = {
        (classes[c]["cpu_milli"], classes[c]["memory_mib"])
        for c in {run.created[n] for n in names}
    }
    return sizes.pop() if len(sizes) == 1 else None


def names_of(config: dict) -> list:
    """The comparisons of a configuration, in the order they are made."""
    return list(config.get("checks", DEFAULT_CHECKS)) + [LAST_CHECK]


def load(name: str):
    """The module of one comparison. A name with no file ends the run:
    a comparison is never skipped."""
    from chipbench.harness import BenchError

    try:
        module = importlib.import_module(f"chipbench.checks.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.checks.{name}":
            raise
        raise BenchError(
            f"the configuration names the comparison {name!r} and "
            f"chipbench/checks/{name}.py is not there"
        ) from None
    if not callable(getattr(module, "run", None)):
        raise BenchError(
            f"chipbench/checks/{name}.py has no run(run, control)"
        )
    return module


def validate(config: dict) -> None:
    """Before anything is built: every comparison the configuration
    names has its file, every ledger it names is one the harness knows,
    and every key of a pod class is read by ``Run.make_pods`` or by one
    of those comparisons, so that a configuration cannot state a
    constraint the harness drops."""
    from chipbench.harness import LEDGERS, POD_CLASS_KEYS, TIERS, BenchError

    read = set(POD_CLASS_KEYS)
    for name in names_of(config):
        read |= set(getattr(load(name), "POD_CLASS_KEYS", ()))
    for cls_name, cls in config["pod_classes"].items():
        for key in cls:
            if key not in read:
                raise BenchError(
                    f"pod class {cls_name!r} states {key!r}, which neither "
                    "make_pods nor a comparison the configuration names "
                    f"reads (read: {sorted(read)})"
                )
    for ledger, tier in expected_tiers(config).items():
        if ledger not in LEDGERS:
            raise BenchError(
                f"expect_tiers names the ledger {ledger!r}; the harness "
                f"knows {sorted(LEDGERS)}"
            )
        if tier not in TIERS:
            raise BenchError(f"ledger {ledger!r}: no tier {tier!r} in {TIERS}")


def expected_tiers(config: dict) -> dict:
    """ledger -> the tier the configuration expects of it; ``batch`` is
    the one ``expect_tier`` names."""
    return {"batch": config["expect_tier"], **config.get("expect_tiers", {})}


def run_checks(run, control: bool) -> bool:
    """Everything that decides ``correct``, after the window closed:
    every comparison is made, whatever the ones before it found."""
    ok = True
    for name in names_of(run.config):
        ok &= bool(load(name).run(run, control))
    return bool(ok)
