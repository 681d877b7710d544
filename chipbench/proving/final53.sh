# PR 53's measurements, from checkouts unpacked before the call (the
# chip's machine has no git):
#   .checkout/change      git archive $(git write-tree)
#   .checkout/parent      git archive of the parent commit
#   .checkout/parentprog  the parent's archive with the change's
#                         BENCHMARK.json, chipbench/ and tests/chipbench/
#                         laid over it: the parent's program under the
#                         change's benchmark, as the driver traces it
#   chiprun --chips 1 --timeout 3400 -- sh chipbench/proving/final53.sh cells
#     every one-chip cell traced once on the change (the nine new
#     metrics in every cell), the traces of the burst cells kept for
#     proving/self_gaps.py (what each parent's own time lies between)
#   ... final53.sh pairs   traced, parent / change / change / parent, of
#     basic-5000.burst-10k and basic-5000.arrivals-steady: what the new
#     spans cost with a session on (pack_ms_per_batch and its six older
#     children, parent against change); the stage primitive's cost on
#     this host before them
#   ... final53.sh plain   plain pairs, parent / change / change /
#     parent, of basic-5000.arrivals-steady (the cell a few us a batch
#     could show in) and gang-train-5000.gang-half-8k (the gang's new
#     spans), and an old cell traced on the parent's program under the
#     change's benchmark files: the nine are left out, nothing raises
#   chiprun --chips 4 --timeout 1500 -- sh chipbench/proving/final53.sh mesh
#     the mesh cell traced once on the change
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
  tail -n 1 $out | cut -c1-12000
}
gaps() {  # tag name: what the kept trace's parents spend between which children
  python3 chipbench/proving/self_gaps.py chiprun_out/$1/trace-$2 \
    > chiprun_out/$1/gaps-$2.txt 2>&1
  rm -rf chiprun_out/$1/trace-$2
  head -n 60 chiprun_out/$1/gaps-$2.txt | cut -c1-200
}
case "$1" in
cells)
  T=$PWD/chiprun_out/cells53
  one change cells53 basic-5000.burst-10k 2147553101 1 --keep-trace $T/trace-burst10k
  gaps cells53 burst10k
  one change cells53 gang-train-5000.gang-half-8k 2147553102 1 --keep-trace $T/trace-gang
  gaps cells53 gang
  one change cells53 services-5000.rollout-5k 2147553103 1 --keep-trace $T/trace-services
  gaps cells53 services
  one change cells53 gpu-binpack-5000.binpack-burst-6k 2147553104 1 --keep-trace $T/trace-binpack
  gaps cells53 binpack
  one change cells53 spread-anti-5000.burst-5k 2147553105 1
  one change cells53 priority-tiers-5000.preempt-1k 2147553106 1
  one change cells53 basic-5000.arrivals-steady 2147553107 1
  one change cells53 rolling-upgrade-5000.arrivals-roll-4 2147553108 1
  one change cells53 image-locality-5000.arrivals-apps-48 2147553109 1
  ;;
pairs)
  (cd .checkout/parent && python3 -m chipbench.proving.stage_cost)
  (cd .checkout/change && python3 -m chipbench.proving.stage_cost)
  one parent pairs53 basic-5000.burst-10k 2147553201 1
  one change pairs53 basic-5000.burst-10k 2147553201 1
  one change pairs53 basic-5000.burst-10k 2147553202 1
  one parent pairs53 basic-5000.burst-10k 2147553202 1
  one parent pairs53 basic-5000.arrivals-steady 2147553203 1
  one change pairs53 basic-5000.arrivals-steady 2147553203 1
  one change pairs53 basic-5000.arrivals-steady 2147553204 1
  one parent pairs53 basic-5000.arrivals-steady 2147553204 1
  ;;
plain)
  one parent plain53 basic-5000.arrivals-steady 2147553301 0
  one change plain53 basic-5000.arrivals-steady 2147553301 0
  one change plain53 basic-5000.arrivals-steady 2147553302 0
  one parent plain53 basic-5000.arrivals-steady 2147553302 0
  one parent plain53 gang-train-5000.gang-half-8k 2147553303 0
  one change plain53 gang-train-5000.gang-half-8k 2147553303 0
  one change plain53 gang-train-5000.gang-half-8k 2147553304 0
  one parent plain53 gang-train-5000.gang-half-8k 2147553304 0
  one parentprog plain53 basic-5000.burst-10k 2147553305 1
  ;;
mesh)
  T=$PWD/chiprun_out/mesh53
  one change mesh53 basic-50000.mesh-burst-20k 2147553401 1 --keep-trace $T/trace-mesh
  gaps mesh53 mesh
  ;;
esac
