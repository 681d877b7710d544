# PR 32's measurements, from checkouts that hold only committed files:
# .checkout/parent (git archive of the parent) and .checkout/change
# (git archive $(git write-tree)), both unpacked before the call.
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/proving/final32.sh cell
#     the new cell on PERF.md's six seeds, one traced run, one with the control
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final32.sh pairs
#     two pairs of each one-chip cell, parent against change, sides alternating
#   chiprun --chips 4 --timeout 1500 -- sh chipbench/proving/final32.sh mesh
#     the same for the mesh cell, on four chips
CELL=priority-tiers-5000.preempt-1k
case "$1" in
cell)
  (cd .checkout/change && python3 chipbench/proving/runs.py six32 \
    "--workload $CELL --seed 7 --seconds 51 --trace 0" \
    "--workload $CELL --seed 4242 --seconds 51 --trace 0" \
    "--workload $CELL --seed 1000003 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147483659 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147483777 --seconds 51 --trace 0" \
    "--workload $CELL --seed 998244353 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147532101 --seconds 51 --trace 1" \
    "--workload $CELL --seed 2147532102 --seconds 12 --trace 0 --control")
  mkdir -p chiprun_out && cp -r .checkout/change/chiprun_out/six32 chiprun_out/
  ;;
pairs)
  python3 chipbench/proving/pairs.py pairs32 51 \
    "basic-5000.burst-10k 2 2147532111" \
    "basic-5000.arrivals-steady 2 2147532121" \
    "spread-anti-5000.burst-5k 2 2147532131"
  ;;
mesh)
  python3 chipbench/proving/pairs.py mesh32 51 \
    "basic-50000.mesh-burst-20k 2 2147532201"
  ;;
esac
