"""The window's placements against the plain reference that knows the
nodes' images (``chipbench/image_reference.py``): no pod is deleted, so
the window is one wave; from the node state the apiserver showed before
it (the snapshot taken at the close, less the window's own pods) and the
deployment's catalogue, the pods each node received from each app must
fit that node's places by the module's lemma, which holds whatever the
order of arrival, the batching and the tie-break. The number compared is
the pods that do not fit plus the pods left unbound; the limit is the
configuration's (``window_image_reference``).

What the comparison reads of the run: ``run.image_apps`` (pod name ->
app, kept by ``generators/arrivals_apps.py`` as it names each pod's
image) and the catalogue, made again here from the configuration's
``images`` and nothing else.

``control``: the reference itself placing the window's pods, in their
due order, (a) deaf to images (the two resource scores alone), (b) blind
to which image is which (each app scored by the next app's row), (c)
under the full rule with the resource scores in float32 and (d) in
bfloat16, each held to the same comparison. (a) and (b) have to read far
above the limit for the comparison to have power against a scheduler
that drops or mixes up the rows; (c) has to read 0: the program states
float32. (d) reads what it reads: on nodes that fill alike the last bits
of a score change the order of arrival and not the counts, as in
``arrivals-steady``, and ``check_wave`` is what tells the precisions
apart in this cell too.
"""

from __future__ import annotations

import numpy as np

from chipbench import image_reference, reference
from chipbench.check import MIB, compare, nodes_before, wave_size


def window_state(run):
    """(nodes before the window, the pods' class, ``I`` [A, N], ``got``
    [A, N], pods of the window that are bound nowhere, the apps of the
    window's pods in their due order)."""
    cat = image_reference.catalogue(
        run.config["images"], len(run.node_rows)
    )
    snapshot = run.snapshots[-1] if run.snapshots else run.snapshot()
    names = run.window_names
    mine = set(names)
    before = nodes_before(run, {
        name: node for name, node in snapshot.items() if name not in mine
    })
    size = wave_size(run, names)
    if size is None:
        raise ValueError("pods of different sizes: the lemma does not hold")
    pod = reference.PodClass(cpu=size[0], mem=size[1] * MIB)
    app_of = run.image_apps
    got = np.zeros((len(cat.apps), len(run.node_rows)), dtype=np.int64)
    unbound = 0
    for name in names:
        if name in snapshot:
            got[app_of[name], run.node_rows[snapshot[name]]] += 1
        else:
            unbound += 1
    due = sorted(names, key=run.due.__getitem__)
    arrivals = np.array([app_of[name] for name in due], dtype=np.int64)
    return cat, before, pod, got, unbound, arrivals


def run(run, control: bool) -> bool:
    spec = run.config["window_image_reference"]
    cat, before, pod, got, unbound, arrivals = window_state(run)
    scores = image_reference.image_scores(cat)
    live = int((scores.max(axis=1) > 0).sum())
    outside = image_reference.unexplained(before, pod, scores, got)
    if control:
        apps = len(cat.apps)
        rules = (
            ("deaf to images", np.zeros_like(scores), "exact"),
            ("each app scored by the next app's row",
             image_reference.image_scores(
                 cat, rows=(np.arange(apps) + 1) % apps), "exact"),
            ("the full rule in float32", scores, "float32"),
            ("the full rule in bfloat16", scores, "bfloat16"),
        )
        for what, rows, precision in rules:
            other, left = image_reference.schedule(
                before, pod, rows, arrivals, precision
            )
            print(f"control window: the reference placing the window's "
                  f"{len(arrivals)} pods {what} leaves "
                  f"{image_reference.unexplained(before, pod, scores, other) + left}"
                  f" outside (limit {spec['limit_pods']})", flush=True)
    return compare(
        "window against the reference that knows the nodes' images: pods no "
        "order, batching or tie-break of the default provider's rule with "
        f"ImageLocality explains ({len(arrivals)} pods of {len(cat.apps)} apps, {live} "
        f"of them with a row above 0, {cat.pairs()} (node, image) pairs, "
        f"{unbound} unbound)",
        outside + unbound, int(spec["limit_pods"]),
    )
