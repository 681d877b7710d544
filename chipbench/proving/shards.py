"""A proving run (``proving/run.py``'s arguments) that also prints where
a mesh cell's pods landed, by the mesh shard that holds their node's
row: the last wave of the window and the check wave. Rows are read from
the scheduler's own row table, 1/``meshDevices`` of the padded rows a
shard. Not part of the benchmark's command.

    PYTHONHASHSEED=0 python3 -m chipbench.proving.shards --workload \\
        basic-50000.mesh-burst-20k --seed 7 --seconds 51 --trace 0
"""

import sys


def shard_counts(run, placements: dict) -> list:
    """Pods by shard, from {pod name: node name}."""
    cache = run.sched.tensor_cache
    chips = int(run.config["wire"]["tpuSolver"].get("meshDevices", 1))
    a_shard = cache._alloc.shape[0] // chips
    row = {name: k for k, name in enumerate(cache._names) if name}
    counts = [0] * chips
    for node in placements.values():
        counts[row[node] // a_shard] += 1
    return counts


def main() -> int:
    from chipbench import check
    from chipbench.proving import run as proving_run

    real = check.run_checks

    def and_print_shards(run, control):
        ok = real(run, control)
        cache = run.sched.tensor_cache
        valid = {name: name for name in cache._names if name}
        print(f"rows by shard: {shard_counts(run, valid)} valid of "
              f"{cache._alloc.shape[0]} padded", flush=True)
        waves = [w for w in run.waves if w["in_window"] and "snapshot" in w]
        for what, wave in (("first", waves[:1]), ("last", waves[-1:])):
            for w in wave:
                won = {n: w["snapshot"][n] for n in w["names"]
                       if n in w["snapshot"]}
                print(f"placements by shard, {what} wave of the window: "
                      f"{shard_counts(run, won)}", flush=True)
        checked = {
            p.metadata.name: p.spec.node_name
            for p in run.client.list_pods()[0]
            if p.spec.node_name and p.metadata.name.startswith("check")
        }
        print(f"placements by shard, check wave: "
              f"{shard_counts(run, checked)}", flush=True)
        return ok

    check.run_checks = and_print_shards
    return proving_run.main()


if __name__ == "__main__":
    sys.exit(main())
