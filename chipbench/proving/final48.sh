# PR 48's measurements, from checkouts unpacked before the call (the
# chip's machine has no git):
#   .checkout/change      git archive $(git write-tree)
#   .checkout/parent      git archive of the parent commit
#   .checkout/parentprog  the parent's archive with the change's
#                         BENCHMARK.json, chipbench/ and tests/chipbench/
#                         laid over it: the parent's program under the
#                         change's benchmark, as the driver runs a new cell
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/proving/final48.sh first
#     the parent's program given the new cell (it has to end by the
#     precondition, exit code 2, a minute or two into set-up) and an old
#     cell traced under the change's benchmark files; then the new cell on
#     the change: one plain run, one traced (its trace kept and the
#     kernel's events dumped), one plain with --control; then the Pallas
#     kernel against the XLA scan at the live family's shape
#     (tools/score_rows_parity.py, which prints the operand shapes
#     kernel_shape counts)
#   ... final48.sh cell    six plain runs on six seeds and one traced run
#   ... final48.sh pairs   plain pairs, parent / change / change / parent,
#     of basic-5000.arrivals-steady, rolling-upgrade-5000.arrivals-roll-4
#     and spread-anti-5000.burst-5k (the cells that share the most code
#     with the new one)
#   ... final48.sh final   cell, then pairs, in one call
#   ... final48.sh rate R  the new cell on the change at R pods/s (the
#     sweep, where 2,600 leaves a backlog)
#   ... final48.sh review  the second session's, after the review: the
#     parent's program given the cell again, the cell on the final tree
#     (two plain runs, one traced), and the kernel against the XLA scan at
#     the cell's size, at the VMEM gate's last size for a live score
#     family alone (7,700 nodes) and inside the parent's ceiling (13,000)
#   chiprun --chips 4 -- python3 tools/mesh_score_shapes.py   (the mesh's
#     two constrained signatures; from the tree itself, not a checkout)
CELL=image-locality-5000.arrivals-apps-48
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
  grep -E '^(set-up|window:|slow wave|chipbench:|compare |control |arrivals|check wave|programs compiled|counters over)' $out | cut -c1-460
  tail -n 1 $out | cut -c1-9000
}
case "$1" in
first)
  one parentprog first48 $CELL 2147548001 0
  one parentprog first48 basic-5000.arrivals-steady 2147548002 1
  one change first48 $CELL 2147548003 0
  one change first48 $CELL 2147548004 1 --keep-trace $PWD/chiprun_out/first48/trace
  python3 chipbench/proving/trace_probe.py chiprun_out/first48/trace/*.xplane.pb \
    | grep -E 'PLANE|pallas_constrained|pallas_greedy' | cut -c1-700
  rm -rf chiprun_out/first48/trace
  one change first48 $CELL 2147548005 0 --control
  (cd .checkout/change && python3 tools/score_rows_parity.py) 2>&1 | grep -E '^\{' | cut -c1-600
  ;;
cell)
  one change cell48 $CELL 2147548101 0
  one change cell48 $CELL 2147548102 0
  one change cell48 $CELL 998244353 0
  one change cell48 $CELL 7 0
  one change cell48 $CELL 2147548105 0
  one change cell48 $CELL 3000000048 0
  one change cell48 $CELL 2147548107 1
  ;;
pairs)
  one parent pairs48 basic-5000.arrivals-steady 2147548201 0
  one change pairs48 basic-5000.arrivals-steady 2147548201 0
  one change pairs48 rolling-upgrade-5000.arrivals-roll-4 2147548202 0
  one parent pairs48 rolling-upgrade-5000.arrivals-roll-4 2147548202 0
  one parent pairs48 spread-anti-5000.burst-5k 2147548203 0
  one change pairs48 spread-anti-5000.burst-5k 2147548203 0
  ;;
final)
  sh chipbench/proving/final48.sh cell
  sh chipbench/proving/final48.sh pairs
  ;;
review)
  one parentprog review48 $CELL 2147548301 0
  one change review48 $CELL 2147548302 0
  one change review48 $CELL 2147548303 1
  one change review48 $CELL 3000000148 0
  for nodes in 5000 7700 13000; do
    (cd .checkout/change && python3 tools/score_rows_parity.py --nodes $nodes) 2>&1 \
      | grep -E '^\{|score_rows_parity' | cut -c1-700
  done
  ;;
rate)
  one change rate48 $CELL 21475483$2 0 --override params.rate=$2
  ;;
esac
