"""The driver's entry points (``__graft_entry__.py``) and the options
PR 29 retired: a batch reaches the device one way, and no environment
variable picks another."""

import os
import re
import sys

import jax
import numpy as np
import pytest

from kubernetes_tpu.utils import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _program_sources():
    for base, _dirs, files in os.walk(os.path.join(REPO_ROOT, "kubernetes_tpu")):
        for name in files:
            if name.endswith((".py", ".c")):
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as f:
                    yield os.path.relpath(path, REPO_ROOT), f.read()


@pytest.fixture
def graft_entry(monkeypatch):
    """The module, with the compile cache left as the session has it."""
    monkeypatch.setattr(
        compile_cache, "configure_compile_cache", lambda: None
    )
    monkeypatch.syspath_prepend(REPO_ROOT)
    import __graft_entry__

    yield __graft_entry__
    sys.modules.pop("__graft_entry__", None)


@pytest.mark.parametrize("variable", [
    "KTPU_CARRY_COMPRESS",
    "KTPU_MESH_DELTA",
    "KTPU_SCAN_UNROLL",
    "KTPU_MESH_MASK_SHARD_MIN_BYTES",
])
def test_no_program_source_names_a_retired_variable(variable):
    word = re.compile(rf"\b{variable}\b")
    named_in = [path for path, text in _program_sources() if word.search(text)]
    assert named_in == []


def test_entry_places_its_example_and_books_its_cpu(graft_entry):
    fn, args = graft_entry.entry()
    requested, pod_req = args[1], args[4]
    assert pod_req.shape[0] == 64
    assignments, req_out, _nzr_out = jax.jit(fn)(*args)
    assignments = np.asarray(assignments)
    assert ((assignments >= 0) & (assignments < requested.shape[0])).all()
    booked = np.asarray(req_out)[:, 0].sum() - requested[:, 0].sum()
    assert booked == pod_req[:, 0].sum()


def test_dryrun_multichip_names_no_stateless_sharded_solver(graft_entry):
    names = graft_entry.dryrun_multichip.__code__.co_names
    assert "make_sharded_solver" not in names
    assert "mesh_packed_cache_size" in names  # the production mesh path
