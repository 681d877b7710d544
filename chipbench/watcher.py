"""The client's side of create -> bind: a watch opened before the first
create, reading bind events as the API serves them.

Copied from ``bench.py``'s ``BindWatcher`` (PERF.md lists the original
for deletion) and changed in what it records: the node of every bind and
how many times each pod was bound, so that the check can hold "bound
exactly once, on the node the watch reported"; and when each pod was
seen deleted, by name, so that a comparison can tell the pods the
harness deleted from those that left otherwise (evictions).
"""

from __future__ import annotations

import threading
import time


class BindWatcher:
    """Records, per pod name, when the watch stream showed it bound."""

    def __init__(self, server) -> None:
        self._server = server
        self._watch = server.watch("Pod", since_rv=server.current_rv())
        self.bind_time: dict = {}  # name -> perf_counter at the event
        self.bind_node: dict = {}  # name -> node of the first bind
        self.rebinds: list = []  # (name, first node, later node)
        self.deleted_time: dict = {}  # name -> perf_counter at DELETED
        self._cond = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name="chipbench-watch", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop:
            try:
                events = self._watch.next_batch(timeout=0.2)
            except Exception:  # noqa: BLE001 - 410 Gone: relist, reopen
                if self._stop:
                    return
                pods, rv = self._server.list("Pod")
                self._watch = self._server.watch("Pod", since_rv=rv)
                now = time.perf_counter()
                with self._cond:
                    for pod in pods:
                        if pod.spec.node_name:
                            self._note(pod, now)
                    # a DELETED event the gap swallowed: a pod the watch
                    # saw bound that the list no longer holds
                    listed = {pod.metadata.name for pod in pods}
                    for name in self.bind_time:
                        if name not in listed:
                            self.deleted_time.setdefault(name, now)
                    self._cond.notify_all()
                continue
            if not events:
                continue
            now = time.perf_counter()
            with self._cond:
                for ev in events:
                    if ev.type == "MODIFIED":
                        if ev.object.spec.node_name:
                            self._note(ev.object, now)
                    elif ev.type == "DELETED":
                        self.deleted_time.setdefault(
                            ev.object.metadata.name, now
                        )
                self._cond.notify_all()

    def _note(self, pod, now: float) -> None:
        name = pod.metadata.name
        first = self.bind_node.get(name)
        if first is None:
            self.bind_node[name] = pod.spec.node_name
            self.bind_time[name] = now
        elif first != pod.spec.node_name:
            self.rebinds.append((name, first, pod.spec.node_name))

    def wait_bound(self, names, deadline: float) -> bool:
        """True once every name has a bind event; False at ``deadline``
        (a ``time.perf_counter`` instant)."""
        with self._cond:
            pending = [n for n in names if n not in self.bind_time]
            while pending:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, 0.25))
                pending = [n for n in pending if n not in self.bind_time]
            return True

    def wait_deleted(self, names, deadline: float) -> bool:
        """True once every name has a DELETED event; by name, so that a
        deletion of some other pod (an eviction) ends no wait early."""
        names = list(names)
        done = 0  # every name before this index has its event
        with self._cond:
            while True:
                # each name is looked up once, however often the watch
                # wakes this wait: it holds the lock the watch needs
                while done < len(names) and names[done] in self.deleted_time:
                    done += 1
                if done == len(names):
                    return True
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, 0.25))

    def stop(self) -> None:
        self._stop = True
        self._watch.stop()
        self._thread.join(timeout=5)
