# PR 41's first look at rolling-upgrade-5000.arrivals-roll-4 on the chip:
# a traced run whose slice is kept, a plain run, and a traced run with no
# interval between remove and join (the membership scatter's control);
# then every device operation of the kept slice by name, for the question
# whether the carry's in-buffer scatter has a device event of its own.
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/proving/first41.sh
CELL=rolling-upgrade-5000.arrivals-roll-4
python3 chipbench/proving/runs.py first41 \
  "--workload $CELL --seed 2147541101 --seconds 51 --trace 1 --keep-trace .scratch/trace41" \
  "--workload $CELL --seed 2147541102 --seconds 51 --trace 0" \
  "--workload $CELL --seed 2147541103 --seconds 51 --trace 1 --override params.roll.rejoin_after_s=0"
grep -h "node line reading" chiprun_out/first41/run*.txt
python3 - <<'PY' > chiprun_out/first41/ops.txt 2>&1
import glob
from chipbench import tracing
path = sorted(glob.glob(".scratch/trace41/*.xplane.pb"))[-1]
ops = tracing.reduce(path)["ops"]
for name, (calls, seconds) in sorted(ops.items(), key=lambda kv: -kv[1][1]):
    print(f"{name}\t{calls}\t{seconds:.6f}")
PY
head -n 40 chiprun_out/first41/ops.txt
