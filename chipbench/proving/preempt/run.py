"""The proving run of ``priority-5000.preempt-wave`` on the chip: grow a
copy of the benchmark under ``.scratch/priority-5000/`` (``grow.py``)
and run the benchmark's own command there, once for each argument
string, through ``proving/runs.py``; the runs' output comes back under
``chiprun_out/<tag>/``. This parent never touches JAX.

    python3 chipbench/proving/preempt/run.py <tag> \\
        "--seed 31001 --seconds 51 --trace 0" "--seed 31002 --seconds 51 --trace 1"
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from chipbench.proving.preempt import grow  # noqa: E402


def main() -> int:
    tag, runs = sys.argv[1], sys.argv[2:]
    copy = ROOT / ".scratch" / "priority-5000"
    shutil.rmtree(copy, ignore_errors=True)
    before = grow.copy_benchmark(ROOT, copy)

    grow.add_cell(copy)
    print(f"copy at {copy}: files edited {grow.edited_files(before)}",
          flush=True)
    env = dict(os.environ, PYTHONPATH=str(ROOT))
    rc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench/proving/runs.py"), tag]
        + [f"--workload {grow.CELL} {args}" for args in runs],
        cwd=copy, env=env,
    ).returncode
    out = ROOT / "chiprun_out" / tag
    shutil.rmtree(out, ignore_errors=True)
    shutil.copytree(copy / "chiprun_out" / tag, out)
    return rc


if __name__ == "__main__":
    sys.exit(main())
