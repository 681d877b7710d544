# PR 27's first four-chip call (chiprun --chips 4 -- sh chipbench/proving/mesh.sh):
# the parent under the new benchmark files (.scratch/parent27: git archive of
# the parent commit, BENCHMARK.json and the paths laid over it) untraced,
# traced and at one-batch waves; the change traced, at one-batch waves, with
# the control and its placements by shard; and arrivals-steady's mix on
# basic-50000 (.scratch/arr27: the change beside a BENCHMARK.json that holds
# that pair as a cell; a proving run, not a cell)
C=basic-50000.mesh-burst-20k
SMALL='params.wave=[{"class":"plain","apps":1,"pods_per_app":2000}]'
(cd .scratch/parent27 && python3 chipbench/proving/runs.py parent \
  "--workload $C --seed 2147487101 --seconds 51 --trace 0" \
  "--workload $C --seed 27102 --seconds 51 --trace 1" \
  "--workload $C --seed 27103 --seconds 10 --trace 0 --override '$SMALL'")
mkdir -p chiprun_out/pr27_parent && cp -r .scratch/parent27/chiprun_out/parent/. chiprun_out/pr27_parent/
python3 chipbench/proving/runs.py pr27_change \
  "--workload $C --seed 27102 --seconds 51 --trace 1" \
  "--workload $C --seed 27103 --seconds 10 --trace 0 --override '$SMALL'"
PYTHONHASHSEED=0 python3 -m chipbench.proving.shards --workload $C --seed 2147487104 --seconds 51 --trace 0 --control > chiprun_out/pr27_change/shards.txt 2>&1
echo "== shards rc=$?"; grep -E "^(compare|control|set-up|window|waves|counters|rows by|placements|check wave|\{)" chiprun_out/pr27_change/shards.txt | cut -c1-1200
(cd .scratch/arr27 && python3 chipbench/proving/runs.py arrivals \
  "--workload basic-50000.arrivals-steady --seed 27105 --seconds 20 --trace 0")
mkdir -p chiprun_out/pr27_arrivals && cp -r .scratch/arr27/chiprun_out/arrivals/. chiprun_out/pr27_arrivals/
