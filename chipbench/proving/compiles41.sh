# Which programs the new cell compiles, and when: one run on an EMPTY
# compile cache (a directory of this checkout, made anew) and one on the
# machine's, both logging every compile.
#   chiprun --chips 1 --timeout 1200 -- sh chipbench/proving/compiles41.sh
CELL=rolling-upgrade-5000.arrivals-roll-4
mkdir -p chiprun_out/compiles41
kept=$JAX_COMPILATION_CACHE_DIR
for side in cold warm; do
  out=chiprun_out/compiles41/$side.txt
  if [ $side = cold ]; then
    rm -rf "$PWD/.cold41"
    export JAX_COMPILATION_CACHE_DIR=$PWD/.cold41
  elif [ -n "$kept" ]; then
    export JAX_COMPILATION_CACHE_DIR=$kept
  else
    unset JAX_COMPILATION_CACHE_DIR
  fi
  JAX_LOG_COMPILES=1 PYTHONHASHSEED=0 python3 -m chipbench --workload $CELL \
    --seed 2147541101 --seconds 25 --trace 0 > $out 2>&1
  echo "== $side rc=$?"
  grep -E 'Compiling jit\(_solve_packed|^nodes rolled|^set-up|^programs compiled|^window:|^counters|^compare watch history|^arrivals' $out \
    | sed 's/WARNING:2026-[0-9-]* //; s/jax._src.interpreters.pxla:[0-9]*: //; s/Argument mapping.*//' | cut -c1-300
  tail -n 1 $out | cut -c1-400
done
rm -rf "$PWD/.cold41"
