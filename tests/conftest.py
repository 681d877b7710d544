"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip). The env vars must
be set before jax initializes its backends.
"""

import os
import sys

# -- tier-0 syntax gate --------------------------------------------------
# ast-parse the whole tree before pytest collects anything: an
# uncollectable module then fails the run fast with its file name
# instead of 21 opaque collection errors (tools/check_syntax.py).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO_ROOT, "tools"))
import check_syntax  # noqa: E402

_syntax_failures = check_syntax.check_tree(base_dir=_REPO_ROOT)
# native-extension probe (tier-0 like the ast gate): the extension must
# either build+import whole or degrade to the pure-Python twins cleanly
# (hotpath None, ingest plane inactive, fallbacks counted) -- a crash or
# a half-exported stale .so fails the run here, with a name, instead of
# surfacing as dozens of opaque test failures
_syntax_failures += check_syntax.probe_native_extension(base_dir=_REPO_ROOT)
if _syntax_failures:
    _lines = "\n".join(f"  {p}: {e}" for p, e in _syntax_failures)
    raise SystemExit(
        f"tier-0 syntax gate failed ({len(_syntax_failures)} file(s) do "
        f"not parse on Python {sys.version.split()[0]}):\n{_lines}"
    )

os.environ.setdefault("JAX_PLATFORMS", "cpu")
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# tests run on the CPU even where an accelerator is present
jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; the lifecycle storm e2es opt out
    # of the tier-1 budget via this marker
    config.addinivalue_line(
        "markers", "slow: long-running e2e excluded from tier-1"
    )
