# PR 32, the committed files after the generator's wait became general
# (wave_timeout_s): one cold run and two warm of .checkout/change, one
# warm of .checkout/laid on a shared seed (see cold32.sh for the two).
#   chiprun --chips 1 --timeout 1200 -- sh chipbench/proving/cold32b.sh
CELL=priority-tiers-5000.preempt-1k
(cd .checkout/change && JAX_COMPILATION_CACHE_DIR=$PWD/.cold_cache \
  python3 chipbench/proving/runs.py cold32b_change \
  "--workload $CELL --seed 2147532411 --seconds 51 --trace 0" \
  "--workload $CELL --seed 2147532412 --seconds 51 --trace 0" \
  "--workload $CELL --seed 2147532413 --seconds 51 --trace 1")
(cd .checkout/laid && JAX_COMPILATION_CACHE_DIR=$PWD/../change/.cold_cache \
  python3 chipbench/proving/runs.py cold32b_laid \
  "--workload $CELL --seed 2147532412 --seconds 51 --trace 0")
mkdir -p chiprun_out
cp -r .checkout/change/chiprun_out/cold32b_change chiprun_out/
cp -r .checkout/laid/chiprun_out/cold32b_laid chiprun_out/
