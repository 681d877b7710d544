# PR 37's measurements, from checkouts that hold only committed files:
# .checkout/parent (git archive of the parent, with this PR's benchmark
# files laid over it, as the driver does for a traced run) and
# .checkout/change (git archive $(git write-tree)), both unpacked before
# the call, and in both the thirteen work / wait metrics declared
# (python3 -m chipbench.proving.declare chipbench/proving/entries37.json),
# so a traced run of the change reads them and a traced run of the
# parent shows that their readers find nothing there and do not raise.
#   chiprun --chips 1 --timeout 3600 -- sh chipbench/proving/final37.sh first
#     what the instrumentation costs when on: burst-10k and
#     arrivals-steady, each traced and plain, parent / change / change /
#     parent; then one traced run of the change in each of the three
#     other one-chip cells, and a plain pair of the gang cell (the one
#     whose largest stage is ingest). (As first run, with the CPU clock
#     always on, the change's plain runs of first, rest and mesh went
#     through a module that also printed the whole window's CPU totals;
#     it went with those totals.)
#   chiprun --chips 1 --timeout 1200 -- sh chipbench/proving/final37.sh rest
#     what a stage and its two clocks cost on the chip's host, both sides
#     (clock: proving/stage_cost.py; touches no device), then a plain
#     pair of burst-5k and of preempt-1k
#   chiprun --chips 1 --timeout 1200 -- sh chipbench/proving/final37.sh arrivals
#     four more plain pairs of arrivals-steady, the side that runs first
#     alternating (the first two pairs read the change 5-10 % slower)
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/proving/final37.sh again
#     after the CPU clock was put behind the profiler session (the
#     arrivals pairs read the always-on clock 4 % slower, 6 of 6): the
#     stage's cost again, four plain pairs of arrivals-steady, and a
#     traced run of arrivals-steady and of burst-10k on the final tree
#   chiprun --chips 4 --timeout 1800 -- sh chipbench/proving/final37.sh mesh
#     the mesh cell on four chips: one traced run of the change and a
#     plain pair
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/proving/final37.sh last
#     the final tree (CPU time on the span alone, the hand-off stats and
#     the frame's counts only under a session): one traced run in each
#     of the three one-chip cells `again` left out, and a plain pair of
#     arrivals-steady
#   chiprun --chips 4 --timeout 900 -- sh chipbench/proving/final37.sh lastmesh
#     the final tree's traced run of the mesh cell
#   chiprun --chips 1 --timeout 900 -- sh chipbench/proving/final37.sh stall
#     two more plain pairs of arrivals-steady: `last`'s pair read the
#     change's p99 2,110 ms for 176 (one standstill of two seconds)
one() {  # side tag module cell seed trace
  mkdir -p chiprun_out/$2
  out=$PWD/chiprun_out/$2/$1-$4-t$6.txt
  start=$(date +%s)
  (cd .checkout/$1 && PYTHONHASHSEED=0 python3 -m $3 --workload $4 \
    --seed $5 --seconds 51 --trace $6) > $out 2>&1
  echo "== $1 $4 seed $5 trace $6: rc=$? wall=$(( $(date +%s) - start ))s"
  grep -E '^(set-up|window:|stage cpu|slow wave|chipbench:)' $out | cut -c1-1500
  tail -n 1 $out | cut -c1-6000
}
case "$1" in
first)
  one change first37 chipbench basic-5000.burst-10k 2147537101 1
  one parent first37 chipbench basic-5000.burst-10k 2147537101 1
  one parent first37 chipbench basic-5000.burst-10k 2147537102 0
  one change first37 chipbench basic-5000.burst-10k 2147537102 0
  one parent first37 chipbench basic-5000.arrivals-steady 2147537111 1
  one change first37 chipbench basic-5000.arrivals-steady 2147537111 1
  one change first37 chipbench basic-5000.arrivals-steady 2147537112 0
  one parent first37 chipbench basic-5000.arrivals-steady 2147537112 0
  one change first37 chipbench gang-train-5000.gang-half-8k 2147537141 1
  one change first37 chipbench priority-tiers-5000.preempt-1k 2147537131 1
  one change first37 chipbench spread-anti-5000.burst-5k 2147537121 1
  one parent first37 chipbench gang-train-5000.gang-half-8k 2147537142 0
  one change first37 chipbench gang-train-5000.gang-half-8k 2147537142 0
  ;;
rest)
  sh chipbench/proving/final37.sh clock
  one change rest37 chipbench spread-anti-5000.burst-5k 2147537122 0
  one parent rest37 chipbench spread-anti-5000.burst-5k 2147537122 0
  one parent rest37 chipbench priority-tiers-5000.preempt-1k 2147537132 0
  one change rest37 chipbench priority-tiers-5000.preempt-1k 2147537132 0
  ;;
clock)
  for side in parent change; do
    echo "== $side"; (cd .checkout/$side && python3 -m chipbench.proving.stage_cost)
  done
  ;;
arrivals)
  one change arrivals37 chipbench basic-5000.arrivals-steady 2147537113 0
  one parent arrivals37 chipbench basic-5000.arrivals-steady 2147537113 0
  one parent arrivals37b chipbench basic-5000.arrivals-steady 2147537114 0
  one change arrivals37b chipbench basic-5000.arrivals-steady 2147537114 0
  one change arrivals37c chipbench basic-5000.arrivals-steady 2147537115 0
  one parent arrivals37c chipbench basic-5000.arrivals-steady 2147537115 0
  one parent arrivals37d chipbench basic-5000.arrivals-steady 2147537116 0
  one change arrivals37d chipbench basic-5000.arrivals-steady 2147537116 0
  ;;
again)
  sh chipbench/proving/final37.sh clock
  one parent again37 chipbench basic-5000.arrivals-steady 2147537117 0
  one change again37 chipbench basic-5000.arrivals-steady 2147537117 0
  one change again37b chipbench basic-5000.arrivals-steady 2147537118 0
  one parent again37b chipbench basic-5000.arrivals-steady 2147537118 0
  one parent again37c chipbench basic-5000.arrivals-steady 2147537119 0
  one change again37c chipbench basic-5000.arrivals-steady 2147537119 0
  one change again37d chipbench basic-5000.arrivals-steady 2147537120 0
  one parent again37d chipbench basic-5000.arrivals-steady 2147537120 0
  one change again37 chipbench basic-5000.arrivals-steady 2147537123 1
  one change again37 chipbench basic-5000.burst-10k 2147537124 1
  ;;
mesh)
  one change mesh37 chipbench basic-50000.mesh-burst-20k 2147537151 1
  one change mesh37 chipbench basic-50000.mesh-burst-20k 2147537152 0
  one parent mesh37 chipbench basic-50000.mesh-burst-20k 2147537152 0
  ;;
last)
  one change last37 chipbench spread-anti-5000.burst-5k 2147537161 1
  one change last37 chipbench priority-tiers-5000.preempt-1k 2147537162 1
  one change last37 chipbench gang-train-5000.gang-half-8k 2147537163 1
  one parent last37 chipbench basic-5000.arrivals-steady 2147537165 0
  one change last37 chipbench basic-5000.arrivals-steady 2147537165 0
  ;;
stall)
  one change stall37 chipbench basic-5000.arrivals-steady 2147537167 0
  one parent stall37 chipbench basic-5000.arrivals-steady 2147537167 0
  one parent stall37b chipbench basic-5000.arrivals-steady 2147537168 0
  one change stall37b chipbench basic-5000.arrivals-steady 2147537168 0
  ;;
lastmesh)
  one change lastmesh37 chipbench basic-50000.mesh-burst-20k 2147537166 1
  ;;
esac
