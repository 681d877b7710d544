"""The plain reference for a cluster whose nodes hold the images its pods
name: ``reference.py``'s filters and its two resource scores, plus
``ImageLocality``. Numpy, and nothing of the program.

``ImageLocality`` as the source states it (kubernetes v1.18,
``pkg/scheduler/framework/plugins/imagelocality/image_locality.go``):
for each container of the pod whose image the node holds, the image's
size on that node times the share of the cluster's nodes that hold it,
cut to a whole number of bytes (``scaledImageScore``); the sum over the
containers is clamped to 23 MB .. 1000 MB (``mb`` is 1024 * 1024) and
mapped to 0..100 by a whole-number division (``calculatePriority``); the
plugin has no normalize step and the default provider gives it weight 1.
So a pod of app ``a`` scores node ``j`` at ``R(j) + I[a, j]``, ``R`` the
two resource scores of ``reference.scores`` with the pod counted in.

**The catalogue** (``catalogue``) is the deployment's, made from its
seed alone: the application images, each of one size and on a seeded
share of the nodes, the infrastructure images on every node, and each
node's report cut to its largest ``max_per_node`` (the kubelet's
``nodeStatusMaxImages``). The generator reports it to the apiserver and
the comparison scores with it; neither asks the program what it holds.

**The comparison** (``unexplained``) is exact whatever the order of
arrival, the batching and the tie-break. Its lemma:

    Pods that all ask for the same are placed one at a time, each on a
    feasible node of highest score, ties broken anyhow; a batch is such
    a sequence. Nothing is deleted meanwhile. The resource score of the
    ``p``-th of these pods to land on node ``j``, ``R(j, p)``, depends
    on ``j``'s load alone. Let ``g[j]`` be what ``j`` received by the
    close, ``M(j, p)`` the least of ``R(j, 1..p)`` and ``U(j, p)`` the
    most of ``R(j, p..g[j])``. For an app ``a`` let ``T[a]`` be the most
    of ``M(k, g[k] + 1) + I[a, k]`` over the nodes ``k`` that still have
    room at the close. Then a pod of app ``a`` can have taken place
    ``p`` of node ``j`` only if ``U(j, p) + I[a, j] >= T[a]``.

    Proof. When the pod took place ``p`` of ``j`` it scored ``R(j, p) +
    I[a, j]``, the highest among the feasible nodes. Every node ``k``
    with room at the close was feasible then too (loads only grow), held
    ``c <= g[k]`` of the window's pods and scored ``R(k, c + 1) + I[a,
    k] >= M(k, g[k] + 1) + I[a, k]``. So ``U(j, p) + I[a, j] >= R(j, p)
    + I[a, j] >= T[a]``.

``U(j, .)`` does not rise, so the places of ``j`` that app ``a`` may
hold are a prefix ``1..L[a, j]``, and the pods a node received must fit
its places, one a place, each within its app's prefix: Hall's condition
for nested intervals, which filling the places by rising ``L`` decides.
The number compared is the pods that do not fit, plus those left
unbound: 0 for every scheduler that follows the rule, whatever its
order. Where ``R(j, .)`` itself does not rise (every node of these
deployments: ``tests/test_image_reference.py`` holds it) ``M`` and ``U``
are ``R`` and nothing is given away.

The condition is necessary, not sufficient; what it has power against is
measured, not argued: ``schedule`` places the window's pods under a rule
of the caller's choosing (deaf to images; each app scored by another
app's row; the resource scores in float32 or bfloat16), and the
comparison reads what it makes of that placement.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference

MB = 1024 * 1024
MIN_THRESHOLD = 23 * MB  # image_locality.go:33
MAX_THRESHOLD = 1000 * MB  # image_locality.go:35
MAX_NODE_SCORE = 100


@dataclasses.dataclass
class Catalogue:
    apps: list  # image name of app k
    app_size: np.ndarray  # [A] int64 bytes, the same on every holder
    holds: np.ndarray  # [A, N] bool: node j reports app k's image
    infra: list  # (image name, bytes), on every node, named by no pod

    def node_images(self, j: int) -> list:
        """Node ``j``'s report: ``(name, size_bytes)``, largest first."""
        mine = [(self.apps[k], int(self.app_size[k]))
                for k in np.nonzero(self.holds[:, j])[0]]
        return sorted(self.infra + mine, key=lambda e: (-e[1], e[0]))

    def pairs(self) -> int:
        return int(self.holds.sum()) + len(self.infra) * self.holds.shape[1]


def catalogue(spec: dict, nodes: int) -> Catalogue:
    """The deployment's images from ``spec`` (the configuration's
    ``images``) and its ``seed`` alone: ``apps`` application images
    ``<registry>/app-<k>:v1`` of a size log-uniform in ``size_mib`` and
    on a share of the nodes uniform in ``holder_share`` (a seeded subset
    of exactly that share, rounded, of all ``nodes``), the ``infra``
    images on every node. A node reports its ``max_per_node`` largest;
    what it does not report it does not hold, for the scheduler and for
    this reference alike."""
    rng = np.random.default_rng(int(spec["seed"]))
    count = int(spec["apps"])
    lo, hi = (float(x) for x in spec["size_mib"])
    size = np.exp(rng.uniform(np.log(lo), np.log(hi), count))
    app_size = (size * MB).astype(np.int64)
    s_lo, s_hi = (float(x) for x in spec["holder_share"])
    share = rng.uniform(s_lo, s_hi, count)
    holds = np.zeros((count, nodes), dtype=bool)
    for k in range(count):
        n_hold = min(nodes, max(1, int(round(share[k] * nodes))))
        holds[k, rng.choice(nodes, size=n_hold, replace=False)] = True
    infra = [(str(name), int(float(mib) * MB)) for name, mib in spec["infra"]]
    room = int(spec["max_per_node"]) - len(infra)
    # the kubelet reports its largest images: where a node holds more
    # app images than the room the infra images leave (they are counted
    # in whatever their size, being on every node), the smallest go
    over = np.nonzero(holds.sum(axis=0) > room)[0]
    for j in over:
        mine = np.nonzero(holds[:, j])[0]
        keep = mine[np.argsort(-app_size[mine], kind="stable")[:max(room, 0)]]
        holds[:, j] = False
        holds[keep, j] = True
    registry = spec["registry"]
    return Catalogue(
        apps=[f"{registry}/app-{k}:v1" for k in range(count)],
        app_size=app_size, holds=holds, infra=infra,
    )


def image_scores(cat: Catalogue, rows=None) -> np.ndarray:
    """``I`` [A, N] int64: ImageLocality for a pod of one container that
    names app ``k``'s image, on every node, in whole numbers as the
    source computes it. ``rows`` [A] puts another app's holders and size
    in each app's place (a scheduler blind to which image is which)."""
    holds, size = cat.holds, cat.app_size
    if rows is not None:
        holds, size = holds[rows], size[rows]
    nodes = holds.shape[1]
    spread = holds.sum(axis=1) / float(nodes)  # float64, as the source
    scaled = (size.astype(np.float64) * spread).astype(np.int64)
    total = np.clip(scaled, MIN_THRESHOLD, MAX_THRESHOLD)
    score = MAX_NODE_SCORE * (total - MIN_THRESHOLD) // (
        MAX_THRESHOLD - MIN_THRESHOLD
    )
    return np.where(holds, score[:, None], 0).astype(np.int64)


def zipf_apps(count: int, apps: int, exponent: float, seed: int) -> np.ndarray:
    """``count`` draws of an app 0..``apps``-1, rank ``k`` with weight
    ``1 / (k + 1) ** exponent``."""
    weight = 1.0 / np.arange(1, apps + 1, dtype=np.float64) ** exponent
    return np.random.default_rng(seed).choice(
        apps, size=count, p=weight / weight.sum()
    )


def room(nodes: reference.Nodes, pod: reference.PodClass) -> np.ndarray:
    """[N] how many more such pods each node can take."""
    return np.clip(np.minimum.reduce([
        (nodes.cap_cpu - nodes.used_cpu) // max(pod.cpu, 1),
        (nodes.cap_mem - nodes.used_mem) // max(pod.mem, 1),
        nodes.cap_pods - nodes.used_pods,
    ]), 0, None)


def place_scores(nodes: reference.Nodes, pod: reference.PodClass,
                 depth: int, precision: str = "exact") -> np.ndarray:
    """``R`` [N, depth]: the resource score of the ``p``-th further pod
    on each node, ``p`` = column + 1 (the pod counted in)."""
    k = np.arange(1, depth + 1, dtype=np.int64)[None, :]
    return reference.scores(
        nodes.cap_cpu[:, None], nodes.cap_mem[:, None],
        nodes.used_cpu[:, None] + k * pod.cpu,
        nodes.used_mem[:, None] + k * pod.mem, precision,
    )


def unexplained(nodes: reference.Nodes, pod: reference.PodClass,
                scores: np.ndarray, got: np.ndarray) -> int:
    """The pods of ``got`` [A, N] (app ``a``'s pods that node ``j``
    received over the window, from the state ``nodes`` before it) that
    no order, batching or tie-break of the rule explains, by the
    module's lemma, with ``scores`` the ``I`` of ``image_scores``."""
    got = np.asarray(got, dtype=np.int64)
    apps, n = got.shape
    g = got.sum(axis=0)
    free = room(nodes, pod)
    over = int(np.clip(g - free, 0, None).sum())  # beyond any fit
    depth = int(g.max()) + 1 if n else 1
    r = place_scores(nodes, pod, depth)
    place = np.arange(1, depth + 1, dtype=np.int64)[None, :]
    held = place <= g[:, None]
    low = np.minimum.accumulate(r, axis=1)  # M
    # U: the most of R(j, p..g[j]); places past g[j] do not count
    high = np.maximum.accumulate(
        np.where(held, r, -1)[:, ::-1], axis=1
    )[:, ::-1]
    open_ = g < free
    next_low = low[np.arange(n), np.minimum(g, depth - 1)]
    last = np.zeros((apps, n), dtype=np.int64)
    for a in range(apps):
        if open_.any():
            bar = int((next_low[open_] + scores[a, open_]).max())
        else:
            bar = -1  # a full cluster: nothing else was on offer
        ok = held & (high + scores[a][:, None] >= bar)
        last[a] = ok.sum(axis=1)  # a prefix: U does not rise
    # Hall's condition for nested prefixes: fill by rising ``last``
    by_last = np.argsort(last, axis=0, kind="stable")
    last = np.take_along_axis(last, by_last, axis=0)
    count = np.take_along_axis(got, by_last, axis=0)
    used = np.zeros(n, dtype=np.int64)
    outside = 0
    for rank in range(apps):
        fit = np.clip(np.minimum(count[rank], last[rank] - used), 0, None)
        used += fit
        outside += int((count[rank] - fit).sum())
    return outside + over


def schedule(nodes: reference.Nodes, pod: reference.PodClass,
             scores: np.ndarray, arrivals: np.ndarray,
             precision: str = "exact"):
    """Place one pod for each entry of ``arrivals`` (its app), in that
    order, on the feasible node of highest ``R + scores[app]``, lowest
    index. Returns (``got`` [A, N] int64, how many found no node)."""
    apps, n = scores.shape
    free = room(nodes, pod)
    depth = int(min(free.max() if n else 0, len(arrivals))) + 1
    r = place_scores(nodes, pod, depth, precision)
    held = np.zeros(n, dtype=np.int64)
    now = r[:, 0].copy()
    now[free <= 0] = -(1 << 20)  # full: below any score
    got = np.zeros((apps, n), dtype=np.int64)
    unplaced = 0
    for a in np.asarray(arrivals, dtype=np.int64):
        total = now + scores[a]
        j = int(np.argmax(total))
        if total[j] < 0:
            unplaced += 1
            continue
        got[a, j] += 1
        held[j] += 1
        now[j] = r[j, held[j]] if held[j] < free[j] else -(1 << 20)
    return got, unplaced
