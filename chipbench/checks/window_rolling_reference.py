"""The window's own placements against the plain reference, on a cluster
whose nodes were cordoned, drained, deleted and re-joined while the pods
arrived (``generators/arrivals_roll.py``).

``window_reference`` takes every row to hold its node for the whole
window, which a rolled node does not. This comparison leaves out what it
cannot speak for and is exact for what it keeps, whatever the order, the
batching and the tie-break, by ``reference.bands`` alone:

(a) *The nodes no op touched.* A pod that landed on an untouched node was
    the best of all nodes at that instant, so of the untouched ones: the
    window's pods that landed there are a valid run of the sequential rule
    on those nodes alone, from what the apiserver shows they held before.
    Compared: the pods outside the bands, limit 0.
(b) *The re-joined nodes.* The rule takes places in the order of their
    score (a node's k-th place at the least of its scores for its first
    k pods), so once a re-joined node is Ready it takes every pod until
    its next place scores no more than the untouched nodes' water level:
    a node made Ready at least ``ready_before_close_s`` before the close
    holds, at the close, every place of its own that scores above the
    last place the untouched nodes took, and none that scores below the
    next one they have left. Compared: the re-joined nodes outside that
    band, each against the untouched nodes alone, limit 0 (a node the
    scheduler never heard of again, or whose row kept its old load,
    stays below its band).
(c) *The drained pods.* Every pod a drain deleted got a replacement at
    that instant, and every replacement is bound. Compared: replacements
    missing or not bound, limit 0.

What a node did is read from ``run.node_log`` (the harness's own record
of its node ops); a status report changes nothing that places a pod and
touches nothing. The replay's node line holds the binds to
the nodes while they were closed; this module prints the reading that
line's ``node_settle_s`` is set from.
"""

from __future__ import annotations

import bisect

import numpy as np

from chipbench import reference
from chipbench.check import MIB, compare, nodes_before, wave_size

#: what (b) reads of the configuration where ``rolling_check`` is silent
READY_BEFORE_CLOSE_S = 2.0


def touched_nodes(run) -> dict:
    """node -> the instant of its last ``ready`` (None: never made Ready
    again), of every node an op other than a status report touched."""
    out: dict = {}
    for t, name, kind in run.node_log:
        if kind == "report_status":
            continue
        out.setdefault(name, None)
        if kind == "ready":
            out[name] = t
        elif kind in ("cordon", "remove", "join"):
            out[name] = None
    return out


def closed_to_bind_ms(run) -> tuple:
    """The longest interval between a node closing, by the Node watch,
    and a bind there while it was still closed, and how many such binds
    there were: what ``node_settle_s`` has to cover."""
    watcher = run.watcher
    history = {
        name: ([t for t, _ in seen], seen)
        for name, seen in dict(watcher.node_time).items()
        if not all(takes for _, takes in seen)
    }
    longest, count = 0.0, 0
    for pod, node in list(watcher.bind_node.items()):
        if node not in history:
            continue
        times, seen = history[node]
        at = watcher.bind_time[pod]
        k = bisect.bisect_right(times, at) - 1
        if k >= 0 and not seen[k][1]:
            count += 1
            longest = max(longest, at - times[k])
    return longest * 1e3, count


def places(before: reference.Nodes, pod: reference.PodClass,
           depth: int) -> np.ndarray:
    """``reference.bands``' own table: ``[i, k - 1]`` is the score at
    which the rule takes node ``i``'s k-th pod of the window, the least
    of the node's scores for its first k (``reference.scores``, exact);
    -1 beyond the node's room."""
    room = np.clip(np.minimum.reduce([
        (before.cap_cpu - before.used_cpu) // max(pod.cpu, 1),
        (before.cap_mem - before.used_mem) // max(pod.mem, 1),
        before.cap_pods - before.used_pods,
    ]), 0, None)
    k = np.arange(1, max(depth, 1) + 1, dtype=np.int64)[None, :]
    s = reference.scores(
        before.cap_cpu[:, None], before.cap_mem[:, None],
        before.used_cpu[:, None] + k * pod.cpu,
        before.used_mem[:, None] + k * pod.mem,
    )
    return np.where(k <= room[:, None], np.minimum.accumulate(s, axis=1), -1)


def run(run, control: bool) -> bool:
    spec = run.config.get("rolling_check", {})
    grace = float(spec.get("ready_before_close_s", READY_BEFORE_CLOSE_S))
    if not run.snapshots:
        run.snapshot()
    snapshot = run.snapshots[-1]
    rows = run.node_rows
    n = len(rows)
    window = set(run.window_names)
    size = wave_size(run, run.window_names)
    if size is None:
        raise ValueError("a window of pods of different sizes has no bands")
    pod = reference.PodClass(cpu=size[0], mem=size[1] * MIB)

    touched = touched_nodes(run)
    untouched = np.ones(n, dtype=bool)
    caught_up = np.zeros(n, dtype=bool)
    for name, ready_at in touched.items():
        untouched[rows[name]] = False
        if ready_at is not None and ready_at <= run.window_end - grace:
            caught_up[rows[name]] = True
    before = nodes_before(run, {
        name: node for name, node in snapshot.items() if name not in window
    })
    got = np.zeros(n, dtype=np.int64)
    for name in run.window_names:
        if name in snapshot:
            got[rows[snapshot[name]]] += 1

    # (a) the untouched nodes alone
    mine = np.where(untouched, got, 0)
    lo, hi = reference.bands(before, pod, int(mine.sum()), "exact", untouched)
    outside = reference.outside(mine, lo, hi)
    ok = compare(
        "window against the reference: pods on the nodes no op touched "
        f"outside what the scoring rule allows their node ({int(untouched.sum())} "
        f"nodes, {int(mine.sum())} pods of the window's {len(window)})",
        outside, 0,
    )
    if control:
        for precision in ("float32", "bfloat16"):
            other, _ = reference.schedule(
                before, pod, int(mine.sum()), precision, untouched
            )
            print("control window: the reference scheduling the untouched "
                  f"nodes' {int(mine.sum())} pods in {precision} leaves "
                  f"{reference.outside(other, lo, hi)} outside the bands",
                  flush=True)

    # (b) each re-joined node against the untouched nodes' water level
    m = places(before, pod, int(got.max()) + 1)
    taken = np.sort(m[untouched].ravel())[::-1]
    taken = taken[taken >= 0]
    k = int(mine.sum())
    # nothing taken: no place of a re-joined node scores above "none"
    tau = taken[k - 1] if 0 < k <= taken.size else (
        np.iinfo(np.int64).max if k == 0 else -1)
    nu = taken[k] if k < taken.size else -1
    lo = ((m > tau) & (m >= 0)).sum(axis=1)
    hi = ((m >= nu) & (m >= 0)).sum(axis=1)
    off = caught_up & ((got < lo) | (got > hi))
    ok &= compare(
        "window against the reference: re-joined nodes outside their band "
        f"at the close ({int(caught_up.sum())} made Ready {grace} s or more "
        f"before it, of {len(touched)} touched; they hold "
        f"{int(got[caught_up].sum())} pods, short by "
        f"{int(np.clip(lo - got, 0, None)[caught_up].sum())}, over by "
        f"{int(np.clip(got - hi, 0, None)[caught_up].sum())}; the untouched "
        f"nodes' last place taken scores {int(tau)}, their next {int(nu)})",
        int(off.sum()), 0,
    )

    # (c) the drained pods
    bound = run.watcher.bind_time
    drained = missing = unbound = 0
    for roll in getattr(run, "rolls", ()):
        drained += len(roll["drained"])
        missing += max(len(roll["drained"]) - len(roll["replacements"]), 0)
        unbound += sum(1 for name in roll["replacements"] if name not in bound)
    ok &= compare(
        f"drained pods: replacements missing or not bound ({drained} pods "
        f"drained from {len(getattr(run, 'rolls', ()))} nodes, {missing} "
        "without a replacement)", missing + unbound, 0,
    )

    longest_ms, after_close = closed_to_bind_ms(run)
    print(f"node line reading: {after_close} binds to a node the Node watch "
          f"showed closed, the longest {longest_ms:.1f} ms after it closed "
          f"(node_settle_s {run.config.get('node_settle_s', 'default')})",
          flush=True)
    return bool(ok)
