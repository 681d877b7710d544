# PR 43's measurements, from checkouts unpacked before the call (the
# chip's machine has no git):
#   .checkout/change      git archive $(git write-tree)
#   .checkout/parent      git archive of the parent commit
#   .checkout/parentprog  the parent's archive with the change's
#                         BENCHMARK.json, chipbench/ and tests/chipbench/
#                         laid over it: the parent's program under the
#                         change's benchmark, as the driver runs a new cell
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final43.sh first
#     the parent's program given the new cell (it has to end at once, exit
#     code 2) and an old cell traced under the change's benchmark files
#     (the new stats are absent, the line leaves their metrics out); then
#     the new cell on the change: one plain run, one traced, one plain
#     with --control
#   ... final43.sh cell    six plain runs on six seeds and one traced run
#   ... final43.sh pairs   plain pairs, parent / change / change / parent,
#     of basic-5000.burst-10k and spread-anti-5000.burst-5k (the cells
#     that share pack.pods, the greedy kernel and the dispatch the change
#     touches)
CELL=gpu-binpack-5000.binpack-burst-6k
one() {  # side tag cell seed trace [proving flags]
  mkdir -p chiprun_out/$2
  out=$PWD/chiprun_out/$2/$1-$3-s$4-t$5.txt
  start=$(date +%s)
  side=$1; cell=$3; seed=$4; trace=$5; shift 5
  module=chipbench
  [ $# -gt 0 ] && module=chipbench.proving.run
  (cd .checkout/$side && PYTHONHASHSEED=0 python3 -m $module --workload $cell \
    --seed $seed --seconds 51 --trace $trace "$@") > $out 2>&1
  echo "== $side $cell seed $seed trace $trace $*: rc=$? wall=$(( $(date +%s) - start ))s"
  grep -E '^(set-up|window:|slow wave|chipbench:|compare |control |binpack waves|programs compiled|counters over|waves \()' $out | cut -c1-420
  tail -n 1 $out | cut -c1-9000
}
case "$1" in
first)
  one parentprog first43 $CELL 2147543001 0
  one parentprog first43 basic-5000.burst-10k 2147543002 1
  one change first43 $CELL 2147543003 0
  one change first43 $CELL 2147543004 1
  one change first43 $CELL 2147543005 0 --control
  ;;
cell)
  one change cell43 $CELL 2147543101 0
  one change cell43 $CELL 2147543102 0
  one change cell43 $CELL 998244353 0
  one change cell43 $CELL 7 0
  one change cell43 $CELL 2147543105 0
  one change cell43 $CELL 3000000043 0
  one change cell43 $CELL 2147543107 1
  ;;
pairs)
  one parent pairs43 basic-5000.burst-10k 2147543201 0
  one change pairs43 basic-5000.burst-10k 2147543201 0
  one change pairs43 basic-5000.burst-10k 2147543202 0
  one parent pairs43 basic-5000.burst-10k 2147543202 0
  one parent pairs43 spread-anti-5000.burst-5k 2147543203 0
  one change pairs43 spread-anti-5000.burst-5k 2147543203 0
  one change pairs43 basic-5000.arrivals-steady 2147543204 0
  one parent pairs43 basic-5000.arrivals-steady 2147543204 0
  ;;
esac
