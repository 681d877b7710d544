# PR 50's measurements, from checkouts unpacked before the call (the
# chip's machine has no git):
#   .checkout/change   git archive $(git write-tree)
#   .checkout/parent   git archive fc01014
#   chiprun --chips 1 --timeout 3400 -- sh tools/measure50.sh first
#     ISSUE 50's step 1 and the change beside it: a probed, traced pair of
#     gpu-binpack-5000.binpack-burst-6k and of basic-5000.burst-10k
#     (tools/gc_probe.py: every collection of the process by thread,
#     generation, length and harness phase, the sched/gc spans of the kept
#     trace, whole walks, peak RSS), then two plain pairs of the claimed
#     cell (chipbench/proving/pairs.py: parent and change in turn, the
#     side that runs first alternating)
#     (that tree walked the whole heap at the idle point itself)
#   ... measure50.sh final   the final tree: four plain pairs of the
#     claimed cell, two of burst-10k, one of arrivals-steady, and probed
#     traced pairs of the five one-chip closed-wave cells
#   chiprun --chips 4 ... measure50.sh mesh   a probed traced pair of the
#     mesh cell: NOT RUN by PR 50 (no chip could be had in time)
ROOT=$PWD
probed() {  # tag cell seed trace [sides]
  for side in ${5:-parent change}; do
    dir=$PWD/chiprun_out/$1
    keep=$dir/trace-$side
    mkdir -p $dir
    extra=""
    [ "$4" = 1 ] && extra="--keep-trace $keep"
    (cd .checkout/$side && PYTHONHASHSEED=0 python3 $ROOT/tools/gc_probe.py \
      $dir/$2.probe.$side.$3.json --workload $2 --seed $3 --seconds 51 \
      --trace $4 $extra) > $dir/$2.probed.$side.$3.txt 2> $dir/$2.probed.$side.$3.err
    echo "== $2 probed trace=$4 $side seed $3: rc=$?"
    grep -E '^(compare |chipbench:|slow wave|window:)' $dir/$2.probed.$side.$3.txt | cut -c1-300
    tail -n 1 $dir/$2.probed.$side.$3.txt | cut -c1-9000
    grep '^gc_probe' $dir/$2.probed.$side.$3.err | cut -c1-3000
    rm -rf $keep
  done
}
BINPACK=gpu-binpack-5000.binpack-burst-6k
case "$1" in
first)
  probed first50 $BINPACK 2147550001 1
  probed first50 basic-5000.burst-10k 2147550002 1 "change parent"
  python3 chipbench/proving/pairs.py first50 51 "$BINPACK 2 2147550011"
  ;;
final)  # the tree whose whole walks wait for a second empty poll
  python3 chipbench/proving/pairs.py final50 51 "$BINPACK 4 2147550111"
  probed final50 basic-5000.burst-10k 2147550105 1 "change parent"
  python3 chipbench/proving/pairs.py final50b 51 \
    "basic-5000.burst-10k 2 2147550121"
  probed final50 $BINPACK 2147550101 1
  probed final50 spread-anti-5000.burst-5k 2147550102 1 "change parent"
  probed final50 priority-tiers-5000.preempt-1k 2147550103 1
  probed final50 gang-train-5000.gang-half-8k 2147550104 1 "change parent"
  python3 chipbench/proving/pairs.py final50c 51 \
    "basic-5000.arrivals-steady 1 2147550141"
  ;;
mesh)
  probed mesh50 basic-50000.mesh-burst-20k 2147550201 1
  ;;
esac
