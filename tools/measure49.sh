# PR 49's measurements, from checkouts unpacked before the call (the
# chip's machine has no git):
#   .checkout/change   git archive $(git write-tree)
#   .checkout/parent   git archive f71baa3
#   chiprun --chips 1 --timeout 3400 -- sh tools/measure49.sh first
#     the claimed cell: a traced pair (each trace kept and its
#     sched/pack.families spans read, tools/score_rows_spans.py) and one
#     plain pair
#   ... measure49.sh final   the traced pair and three plain pairs of the
#     claimed cell, then one plain pair each of basic-5000.arrivals-steady,
#     rolling-upgrade-5000.arrivals-roll-4 and spread-anti-5000.burst-5k
#     (chipbench/proving/pairs.py: parent and change in turn, the side
#     that runs first alternating)
#   ... measure49.sh stages  two more traced runs of the change, each
#     stage's spans of the slice printed (where the dispatcher's period
#     goes once the pack is short)
CELL=image-locality-5000.arrivals-apps-48
traced() {  # tag seed [sides]
  for side in ${3:-parent change}; do
    keep=$PWD/chiprun_out/$1/trace-$side
    out=$PWD/chiprun_out/$1/$CELL.traced.$side.$2.txt
    mkdir -p chiprun_out/$1
    (cd .checkout/$side && PYTHONHASHSEED=0 python3 -m chipbench.proving.run \
      --workload $CELL --seed $2 --seconds 51 --trace 1 --keep-trace $keep) \
      > $out 2>&1
    echo "== $CELL traced $side seed $2: rc=$?"
    grep -E '^(compare |chipbench:)' $out | cut -c1-300
    tail -n 1 $out | cut -c1-9000
    python3 tools/score_rows_spans.py $keep
    rm -rf $keep
  done
}
case "$1" in
first)
  traced first49 2147549001
  python3 chipbench/proving/pairs.py first49 51 "$CELL 1 2147549011"
  ;;
final)
  traced final49 2147549101
  python3 chipbench/proving/pairs.py final49 51 "$CELL 3 2147549111" \
    "basic-5000.arrivals-steady 1 2147549121" \
    "rolling-upgrade-5000.arrivals-roll-4 1 2147549131" \
    "spread-anti-5000.burst-5k 1 2147549141"
  ;;
stages)
  traced stages49 2147549201 change
  traced stages49 2147549202 change
  ;;
esac
