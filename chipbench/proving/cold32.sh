# PR 32, after the driver's first check: the new cell with an EMPTY
# compile cache (JAX_COMPILATION_CACHE_DIR names a new directory), as
# the driver's first run of a checkout finds it, then warm. Both sides
# hold only committed files: .checkout/laid is the parent's archive
# with this PR's BENCHMARK.json and chipbench/ laid over it (what the
# driver runs a new cell's parent side on), .checkout/change is
# git archive $(git write-tree). Unpack both before the call.
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/proving/cold32.sh
CELL=priority-tiers-5000.preempt-1k
for side in laid change; do
  (cd .checkout/$side && JAX_COMPILATION_CACHE_DIR=$PWD/.cold_cache \
    python3 chipbench/proving/runs.py cold32_$side \
    "--workload $CELL --seed 2147532401 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147532402 --seconds 51 --trace 0" \
    "--workload $CELL --seed 2147532403 --seconds 51 --trace 1")
done
(cd .checkout/change && JAX_COMPILATION_CACHE_DIR=$PWD/.cold_cache \
  python3 chipbench/proving/runs.py cold32_more \
  "--workload $CELL --seed 2147532404 --seconds 51 --trace 0" \
  "--workload $CELL --seed 2147532405 --seconds 51 --trace 0" \
  "--workload $CELL --seed 2147532406 --seconds 51 --trace 0")
mkdir -p chiprun_out
for d in laid/chiprun_out/cold32_laid change/chiprun_out/cold32_change \
    change/chiprun_out/cold32_more; do
  cp -r .checkout/$d chiprun_out/
done
