"""One run of one cell with what only proving runs need, none of which
the benchmark's own command (``python3 -m chipbench``) takes:

- ``--override params.<key>=<json value>``: laid over the mix's file,
  e.g. ``params.rate=2000`` for the knee sweep;
- ``--control``: also read the lower-precision control beside each
  comparison with the reference (PERF.md section 2 has the readings);
- ``--keep-trace <dir>``: copy the traced run's ``.xplane.pb`` there.

    PYTHONHASHSEED=0 python3 -m chipbench.proving.run --workload ... \\
        --seed ... --seconds ... --trace 0 --control

``PYTHONHASHSEED=0`` is what ``python3 -m chipbench`` gives itself;
``proving/runs.py`` sets it for every run it starts.
"""

import json
import sys
import time

_PROCESS_START = time.perf_counter()


def main() -> int:
    from chipbench import harness

    ap = harness.public_arguments("python3 -m chipbench.proving.run")
    ap.add_argument("--override", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args()
    over = None
    if args.override:
        path, value = args.override.split("=", 1)
        over = json.loads(value)
        for key in reversed(path.split(".")):
            over = {key: over}
    return harness.run_one(
        args, _PROCESS_START, mix_over=over, control=args.control,
        keep_trace=args.keep_trace,
    )


if __name__ == "__main__":
    sys.exit(main())
