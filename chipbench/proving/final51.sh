# PR 51's measurements, from checkouts unpacked before the call (the
# chip's machine has no git):
#   .checkout/change      git archive $(git write-tree)
#   .checkout/parent      git archive of the parent commit
#   .checkout/parentprog  the parent's archive with the change's
#                         BENCHMARK.json, chipbench/ and tests/chipbench/
#                         laid over it: the parent's program under the
#                         change's benchmark, as the driver runs a new cell
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final51.sh first
#     the parent's program given the new cell (it has to end by the
#     precondition, exit code 2, soon after set-up) and an old cell
#     traced under the change's benchmark files; then the new cell on the
#     change: one plain run, one traced (its trace kept and the kernel's
#     events dumped: the operand shapes kernel_shape counts), one plain
#     with --control
#   ... final51.sh cell    six plain runs on six seeds and one traced run
#   ... final51.sh pairs   plain pairs, parent / change / change / parent,
#     of image-locality-5000.arrivals-apps-48, spread-anti-5000.burst-5k
#     and basic-5000.burst-10k (the cells that share the most code with
#     the new one)
#   ... final51.sh final   cell, then pairs, in one call
#   ... final51.sh cell2   the final tree: six plain runs on six other
#     seeds, one traced, and the parent's program given the cell again
#   ... final51.sh confirm the cell once plain and once traced
#   ... final51.sh review  after the review (the wire as basic-5000's, the
#     term owners counted from the first batch that asks): the parent's
#     program given the cell, six plain runs on six new seeds, one
#     traced, one plain with --control; then parent / change / change /
#     parent of spread-anti-5000.burst-5k,
#     image-locality-5000.arrivals-apps-48 and basic-5000.burst-10k; then
#     an old cell traced on the parent's program under the change's
#     benchmark files
#   ... final51.sh more    six more plain runs of the cell, six new seeds
#     (asked for 40 times, no chip was free: not run in PR 51)
CELL=services-5000.rollout-5k
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
  grep -E '^(set-up|window:|slow wave|chipbench:|compare |control |rollout|arrivals|check wave|programs compiled|counters over|waves)' $out | cut -c1-460
  tail -n 1 $out | cut -c1-9000
}
case "$1" in
first)
  one parentprog first51 $CELL 2147551001 0
  one change first51 $CELL 2147551003 0
  one change first51 $CELL 2147551004 1 --keep-trace $PWD/chiprun_out/first51/trace
  python3 chipbench/proving/trace_probe.py chiprun_out/first51/trace/*.xplane.pb \
    | grep -E 'PLANE|pallas_constrained|pallas_greedy' | cut -c1-900
  rm -rf chiprun_out/first51/trace
  one change first51 $CELL 2147551005 0 --control
  one parentprog first51 spread-anti-5000.burst-5k 2147551002 1
  ;;
cell)
  one change cell51 $CELL 2147551101 0
  one change cell51 $CELL 2147551102 0
  one change cell51 $CELL 998244353 0
  one change cell51 $CELL 7 0
  one change cell51 $CELL 2147551105 0
  one change cell51 $CELL 3000000051 0
  one change cell51 $CELL 2147551107 1
  ;;
pairs)
  one parent pairs51 image-locality-5000.arrivals-apps-48 2147551201 0
  one change pairs51 image-locality-5000.arrivals-apps-48 2147551201 0
  one change pairs51 spread-anti-5000.burst-5k 2147551202 0
  one parent pairs51 spread-anti-5000.burst-5k 2147551202 0
  one parent pairs51 basic-5000.burst-10k 2147551203 0
  one change pairs51 basic-5000.burst-10k 2147551203 0
  ;;
final)
  sh chipbench/proving/final51.sh cell
  sh chipbench/proving/final51.sh pairs
  ;;
cell2)  # batchWindow 200 ms, which the review took out again
  one change cell51b $CELL 2147551401 0
  one change cell51b $CELL 2147551402 0
  one change cell51b $CELL 1000000007 0
  one change cell51b $CELL 11 0
  one change cell51b $CELL 2147551405 0
  one change cell51b $CELL 3000000151 0
  one change cell51b $CELL 2147551407 1
  one parentprog cell51b $CELL 2147551408 0
  ;;
confirm)  # after a clean-up of the code: the cell once plain, once traced
  one change confirm51 $CELL 2147551301 0
  one change confirm51 $CELL 2147551302 1
  ;;
review)
  one parentprog review51 $CELL 2147551501 0
  one change review51 $CELL 2147551502 0
  one change review51 $CELL 2147551503 0
  one change review51 $CELL 1000000009 0
  one change review51 $CELL 13 0
  one change review51 $CELL 2147551506 0
  one change review51 $CELL 3000000251 0
  one change review51 $CELL 2147551508 1
  one change review51 $CELL 2147551509 0 --control
  for pair in "spread-anti-5000.burst-5k 2147551511 2147551512" \
      "image-locality-5000.arrivals-apps-48 2147551513 2147551514" \
      "basic-5000.burst-10k 2147551515 2147551516"; do
    set -- $pair
    one parent review51 $1 $2 0
    one change review51 $1 $2 0
    one change review51 $1 $3 0
    one parent review51 $1 $3 0
  done
  one parentprog review51 spread-anti-5000.burst-5k 2147551517 1
  ;;
more)
  one change more51 $CELL 2147551601 0
  one change more51 $CELL 2147551602 0
  one change more51 $CELL 1000000021 0
  one change more51 $CELL 17 0
  one change more51 $CELL 2147551605 0
  one change more51 $CELL 3000000351 0
  ;;
esac
