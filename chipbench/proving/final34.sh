# PR 34's measurements, from checkouts that hold only committed files:
# .checkout/parent (git archive of the parent, with this PR's BENCHMARK.json
# and the directories of its paths laid over it, as the driver does for a
# new cell) and .checkout/change (git archive $(git write-tree)), both
# unpacked before the call.
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/proving/final34.sh first
#     the parent on the new cell, once (it has to end by itself), then the
#     change: one run, one traced run
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/proving/final34.sh cell
#     the new cell: six runs over PERF.md's seeds, one with the control
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final34.sh pairs
#     a pair of each older one-chip cell, parent against change
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/proving/final34.sh all
#     cell, then pairs, in one call (no chip was free for most calls)
#   chiprun --chips 4 --timeout 1500 -- sh chipbench/proving/final34.sh mesh
#     the same for the mesh cell, on four chips
CELL=gang-train-5000.gang-half-8k
case "$1" in
first)
  (cd .checkout/parent && python3 chipbench/proving/runs.py parent34 \
    "--workload $CELL --seed 2147534001 --seconds 51 --trace 0")
  (cd .checkout/change && python3 chipbench/proving/runs.py first34 \
    "--workload $CELL --seed 2147534001 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534002 --seconds 51 --trace 1")
  mkdir -p chiprun_out && cp -r .checkout/parent/chiprun_out/parent34 \
    .checkout/change/chiprun_out/first34 chiprun_out/
  ;;
cell)
  (cd .checkout/change && python3 chipbench/proving/runs.py final34 \
    "--workload $CELL --seed 2147534301 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534302 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534303 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534304 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534305 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534306 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147534307 --seconds 51 --trace 1" \
    "--workload $CELL --seed 2147534308 --seconds 20 --trace 0 --control")
  mkdir -p chiprun_out && cp -r .checkout/change/chiprun_out/final34 chiprun_out/
  ;;
pairs)
  python3 chipbench/proving/pairs.py pairsfinal34 51 \
    "basic-5000.burst-10k 1 2147534311" \
    "priority-tiers-5000.preempt-1k 1 2147534341" \
    "basic-5000.arrivals-steady 1 2147534321" \
    "spread-anti-5000.burst-5k 1 2147534331"
  ;;
all)
  sh chipbench/proving/final34.sh cell
  sh chipbench/proving/final34.sh pairs
  ;;
mesh)
  python3 chipbench/proving/pairs.py meshfinal34 51 \
    "basic-50000.mesh-burst-20k 1 2147534401"
  ;;
esac
