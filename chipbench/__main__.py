import os
import sys
import time

_PROCESS_START = time.perf_counter()  # set-up is timed from here

if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        # one hash seed for every run: set and dict orders, and with them
        # the host's work, repeat from run to run. The interpreter reads
        # the variable only as it starts, so start it again (the same
        # process: nothing is left behind, and JAX is not loaded yet).
        # CLOCK_MONOTONIC is system-wide, so the first start still counts.
        os.environ["PYTHONHASHSEED"] = "0"
        os.environ["CHIPBENCH_PROCESS_START"] = repr(_PROCESS_START)
        os.execv(sys.executable, [sys.executable, "-m", "chipbench", *sys.argv[1:]])
    _PROCESS_START = float(
        os.environ.get("CHIPBENCH_PROCESS_START", _PROCESS_START)
    )

    from chipbench.harness import main

    sys.exit(main(process_start=_PROCESS_START))
