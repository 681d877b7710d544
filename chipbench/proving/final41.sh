# PR 41's measurements, from checkouts that hold only committed files,
# all unpacked before the call (the chip's machine has no git):
#   .checkout/change      git archive $(git write-tree)
#   .checkout/parent      git archive of the parent commit
#   .checkout/parentprog  the parent's archive with the change's
#                         BENCHMARK.json, chipbench/ and tests/chipbench/
#                         laid over it: the parent's program under the
#                         change's benchmark, as the driver runs a new cell
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final41.sh cell
#     the compile log (compiles41.sh), then the new cell on the final
#     tree: six plain runs on six seeds, two traced runs, one plain run
#     with --control, and the parent's program under the change's
#     benchmark (one traced run: a result line, the new span metrics
#     absent)
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final41.sh pairs
#   (or `all`: both in one call, where chips are scarce; `burst`: four
#   more pairs of basic-5000.burst-10k; `steady`: three more of
#   basic-5000.arrivals-steady)
#     plain pairs, parent / change: one of basic-5000.arrivals-steady
#     (the new spans sit on handlers that run only in set-up there) and
#     three each of basic-5000.burst-10k and priority-tiers-5000.preempt-1k
#     (whose batches upload the carry whole: the path the stale-pack mend
#     guards)
CELL=rolling-upgrade-5000.arrivals-roll-4
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
  grep -E '^(set-up|window:|slow wave|chipbench:|compare |control |nodes rolled|node line reading|arrivals:|counters over)' $out | cut -c1-420
  tail -n 1 $out | cut -c1-9000
}
case "$1" in
all)
  sh chipbench/proving/final41.sh cell
  sh chipbench/proving/final41.sh pairs
  ;;
cell)
  (cd .checkout/change && sh chipbench/proving/compiles41.sh)
  mkdir -p chiprun_out/compiles41
  cp .checkout/change/chiprun_out/compiles41/*.txt chiprun_out/compiles41/
  one change cell41 $CELL 2147541301 0
  one change cell41 $CELL 2147541302 0
  one change cell41 $CELL 2147541303 1
  one change cell41 $CELL 998244353 0
  one change cell41 $CELL 7 0
  one change cell41 $CELL 2147541306 0
  one change cell41 $CELL 2147541307 0
  one change cell41 $CELL 2147541308 1
  one change cell41 $CELL 2147541309 0 --control
  one parentprog cell41 $CELL 2147541310 1
  ;;
pairs)
  one parent pairs41 priority-tiers-5000.preempt-1k 2147541323 0
  one change pairs41 priority-tiers-5000.preempt-1k 2147541323 0
  one change pairs41 basic-5000.burst-10k 2147541322 0
  one parent pairs41 basic-5000.burst-10k 2147541322 0
  one parent pairs41 basic-5000.arrivals-steady 2147541321 0
  one change pairs41 basic-5000.arrivals-steady 2147541321 0
  one change pairs41 priority-tiers-5000.preempt-1k 2147541324 0
  one parent pairs41 priority-tiers-5000.preempt-1k 2147541324 0
  one parent pairs41 basic-5000.burst-10k 2147541325 0
  one change pairs41 basic-5000.burst-10k 2147541325 0
  one change pairs41 priority-tiers-5000.preempt-1k 2147541326 0
  one parent pairs41 priority-tiers-5000.preempt-1k 2147541326 0
  one parent pairs41 basic-5000.burst-10k 2147541327 0
  one change pairs41 basic-5000.burst-10k 2147541327 0
  ;;
burst)
  # four more pairs of the cell whose three read the change behind
  one change pairs41 basic-5000.burst-10k 2147541331 0
  one parent pairs41 basic-5000.burst-10k 2147541331 0
  one parent pairs41 basic-5000.burst-10k 2147541332 0
  one change pairs41 basic-5000.burst-10k 2147541332 0
  one change pairs41 basic-5000.burst-10k 2147541333 0
  one parent pairs41 basic-5000.burst-10k 2147541333 0
  one parent pairs41 basic-5000.burst-10k 2147541334 0
  one change pairs41 basic-5000.burst-10k 2147541334 0
  ;;
steady)
  # three more pairs of the open loop, whose one pair read the change behind
  one change pairs41 basic-5000.arrivals-steady 2147541335 0
  one parent pairs41 basic-5000.arrivals-steady 2147541335 0
  one parent pairs41 basic-5000.arrivals-steady 2147541336 0
  one change pairs41 basic-5000.arrivals-steady 2147541336 0
  one change pairs41 basic-5000.arrivals-steady 2147541337 0
  one parent pairs41 basic-5000.arrivals-steady 2147541337 0
  ;;
esac
