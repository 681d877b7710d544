"""Native host data plane (SURVEY.md section 2.4).

The C extension (_hotpath.c) is compiled ON FIRST IMPORT with the
toolchain baked into the image (g++ against the running interpreter's
headers -- no pip, no pybind11). The built artefact is keyed on the
CONTENT of _hotpath.c (a hash in its file name), never on mtimes: a
tree copied with someone else's binary, or with whatever mtimes the
copy gave it, rebuilds from the source it actually holds instead of
loading a binary of unknown origin.

A build or import failure degrades to the pure-Python implementations
in api/selectors.py, which carry identical semantics (differentially
fuzzed in tests/test_native_selectors.py) -- loudly: the failure is
logged at WARNING with the compiler's output and kept in
``build_error`` for bench payloads and chip_smoke.py to report.
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_hotpath.c")
_EXT = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
_MODULE = "kubernetes_tpu.native._hotpath"


def artefact_path(src: str = _SRC, out_dir: str = _DIR) -> str:
    """Where the extension built from ``src``'s current content lives:
    ``_hotpath_<sha256[:16]><EXT_SUFFIX>`` under ``out_dir``."""
    with open(src, "rb") as f:
        key = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(out_dir, f"_hotpath_{key}{_EXT}")


def _build(src: str, so: str) -> None:
    """Compile ``src`` to ``so``. Raises RuntimeError naming every
    compiler tried, with its stderr."""
    include = sysconfig.get_paths()["include"]
    tmp = so + f".build.{os.getpid()}"
    errors = []
    for cc in ("g++", "cc", "gcc"):
        try:
            subprocess.run(
                [
                    cc, "-O2", "-shared", "-fPIC", "-x", "c",
                    f"-I{include}", src, "-o", tmp,
                ],
                check=True,
                capture_output=True,
                timeout=120,
            )
            # atomic publish: concurrent importers never dlopen a
            # half-written binary
            os.replace(tmp, so)
            return
        except FileNotFoundError:
            errors.append(f"{cc}: not found")
        except subprocess.CalledProcessError as e:
            stderr = e.stderr.decode(errors="replace")[-2000:]
            errors.append(f"{cc}: exit {e.returncode}: {stderr}")
        except (subprocess.TimeoutExpired, OSError) as e:
            errors.append(f"{cc}: {e}")
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass
    raise RuntimeError(
        f"native build of {src} failed: " + "; ".join(errors)
    )


def ensure_built(src: str = _SRC, out_dir: str = _DIR) -> str:
    """Path of the extension for ``src``'s current content, building it
    when that exact artefact is absent. Artefacts of other contents (and
    the old un-keyed name) are removed after a successful build. Raises
    RuntimeError with the compilers' output when the build fails."""
    so = artefact_path(src, out_dir)
    if os.path.exists(so):
        return so
    _build(src, so)
    for stale in glob.glob(os.path.join(out_dir, "_hotpath*" + _EXT)):
        if stale != so:
            try:
                os.remove(stale)
            except OSError:
                pass
    return so


def _load(so: str):
    spec = importlib.util.spec_from_file_location(_MODULE, so)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


hotpath = None
#: "" when the extension built and loaded; otherwise why the Python
#: twins are running
build_error = ""
try:
    hotpath = _load(ensure_built())
except Exception as e:  # noqa: BLE001 - pure-Python fallback, reported
    hotpath = None
    build_error = f"{type(e).__name__}: {e}"
    logger.warning(
        "native extension unavailable, running the Python twins: %s",
        build_error,
    )

#: single source of truth for the native clone fast path: callers do
#: ``from kubernetes_tpu.native import cow_clone`` and fall back to
#: copy.copy chains when it is None (build/import failure, stale .so)
cow_clone = getattr(hotpath, "cow_clone", None)
#: one-call commit-path loops (see _hotpath.c "bulk commit spine")
assume_clones = getattr(hotpath, "assume_clones", None)
bind_assumed_bulk = getattr(hotpath, "bind_assumed_bulk", None)
commit_gather = getattr(hotpath, "commit_gather", None)

# -- the ingest plane (see _hotpath.c "ingest spine"), the snapshot
# -- refresh's two walks ("snapshot refresh spine") and what a bound pod
# -- leaves behind -------------------------------------------------------
#
# Gated separately from the commit-path loops by KTPU_NATIVE_INGEST
# (default on): =0 forces the pure-Python twins at every ingest call
# site, the differential-test and A/B-bench switch. The env var is read
# PER CALL of ``ingest_fn`` (cheap: once per frame/batch, not per pod)
# so tests can flip it without re-importing the world.

_INGEST_FNS = {
    name: getattr(hotpath, name, None)
    for name in (
        "ingest_decode", "ingest_apply", "ingest_stamp",
        "pack_gather", "queue_shape", "mirror_scatter",
        "node_info_clones", "node_rows_gather",
        "p2_fold", "scheduled_events",
    )
}


def ingest_on() -> bool:
    """True when the native ingest plane is not disabled by env."""
    return os.environ.get("KTPU_NATIVE_INGEST", "1") not in ("0", "false")


def ingest_native_active() -> bool:
    """True when ingest calls will actually run the C path (env on AND
    the extension built) -- the machine-readable bench label."""
    return ingest_on() and _INGEST_FNS.get("ingest_apply") is not None


def ingest_fn(name: str):
    """(callable_or_None, expected): the native ingest entry point, or
    None with ``expected`` telling the caller whether running the
    Python twin counts as a FALLBACK (native wanted but unavailable --
    the caller books scheduler_ingest_native_fallbacks_total) or as the
    configured path (KTPU_NATIVE_INGEST=0)."""
    if not ingest_on():
        return None, False
    return _INGEST_FNS.get(name), True
