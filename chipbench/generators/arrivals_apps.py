"""``arrivals``' open loop on a cluster whose nodes report the images its
pods name: the same schedule, ticks, deadline and lines, with the three
things that generator cannot be told.

**The nodes' reports.** Before warm-up every node reports its images in
one kubelet status write (``run.node_ops.report_status``): the
deployment's catalogue (``image_reference.catalogue`` of the
configuration's ``images``: the infrastructure images every node holds,
and the application images this node holds), as ``(name, size_bytes)``,
at most ``max_per_node``. The harness builds its nodes without images;
nothing reports again, and no pod that binds adds an image to its node
(no kubelet runs here).

**Each pod's app.** A pod is the harness's own (``run.make_pods`` of the
mix's class) with the app's label ``app=app-<k>`` and the app's image on
its one container. The apps of a stretch of arrivals are ``zipf_exponent``
Zipf draws over the catalogue's apps from the mix's ``app_seed``, put in
another order by the run's seed, as the gaps are: every seed offers the
same pods of each app.

**The precondition.** Each warm-up round begins with one create that
holds a pod of every app: a batch of as many image lists as the
catalogue has apps. A scheduler that answers any of them on the host
path (``pods_fallback`` moved) cannot run this deployment, whose
guarantee is that every batch is solved on the device; the run ends
there, exit code 2, and says so, before it would spend a window finding
out.

What it leaves on the ``Run`` for the cell's comparison:
``run.image_apps``, pod name -> app.
"""

from __future__ import annotations

from chipbench import image_reference
from chipbench.generators import arrivals
from chipbench.harness import BenchError


def report_images(run) -> image_reference.Catalogue:
    cat = image_reference.catalogue(
        run.config["images"], len(run.node_rows)
    )
    start = run.now()
    with run.phase("node_reports"):
        for name, row in run.node_rows.items():
            run.node_ops.report_status(name, images=cat.node_images(row))
    print(f"arrivals apps: {len(run.node_rows)} nodes reported "
          f"{cat.pairs()} (node, image) pairs of {len(cat.apps)} app and "
          f"{len(cat.infra)} infra images in {run.now() - start:.2f}s",
          flush=True)
    return cat


def make_pods(run, params: dict, apps, stem: str) -> list:
    """One pod of the mix's class for each entry of ``apps``, named and
    labelled by the harness, then given its app's label and image."""
    images = run.image_catalogue.apps
    pods = run.make_pods(params["class"], len(apps), stem)
    for pod, k in zip(pods, apps):
        k = int(k)
        pod.metadata.labels["app"] = f"app-{k}"
        pod.spec.containers[0].image = images[k]
        run.image_apps[pod.metadata.name] = k
    return pods


def drawn(run, params: dict, count: int):
    apps = image_reference.zipf_apps(
        count, len(run.image_catalogue.apps),
        float(params["zipf_exponent"]), int(params["app_seed"]),
    )
    return apps[run.rng.permutation(count)]


def every_app_at_once(run, params: dict) -> list:
    """One create, a pod of every app, waited for; ends the run where
    the scheduler sent any of them to the host path."""
    apps = len(run.image_catalogue.apps)
    before = int(run.sched.pods_fallback)
    pods = make_pods(run, params, range(apps), "warmall")
    names = [p.metadata.name for p in pods]
    run.create(pods)
    run.wait_bound(names, params["deadline_s"])
    run.sched.wait_for_inflight_binds(timeout=30)
    moved = int(run.sched.pods_fallback) - before
    if moved:
        raise BenchError(
            f"a batch of {apps} pods that name {apps} images: "
            f"pods_fallback moved by {moved}. This scheduler cannot solve "
            "a batch of that many images on the device, and the "
            "deployment's guarantee is that every batch is (exit code 2 "
            "is the precondition's, generators/arrivals_apps.py)"
        )
    return names


def warmup(run, params: dict) -> None:
    run.image_apps = {}
    run.image_catalogue = report_images(run)
    for _ in range(params["warmup_rounds"]):
        names = every_app_at_once(run, params)
        offs = arrivals.offsets(
            params["rate"], params["warmup_seconds"], params["gap_seed"],
            run.rng,
        )
        pods = make_pods(run, params, drawn(run, params, len(offs)), "warm")
        names += [p.metadata.name for p in pods]
        arrivals._offer(run, params, pods, offs)
        run.wait_bound(names, params["deadline_s"])
        run.delete(names, params["delete_timeout_s"])


def prepare(run, params: dict, seconds: float):
    offs = arrivals.offsets(
        params["rate"], seconds, params["gap_seed"], run.rng
    )
    apps = drawn(run, params, len(offs))
    return make_pods(run, params, apps, "arrive"), offs


window = arrivals.window
