# the last proving call of PR 23 (after the review): a cell from a checkout
# that holds only the committed files, the refusal in a directory that
# holds only the benchmark, one traced run of each cell at the full
# length, six more seeds of the arrivals cell at the full length, and
# short runs that bring every cell to a dozen seeds
A=basic-5000.burst-10k
B=spread-anti-5000.burst-5k
C=basic-5000.arrivals-steady
mkdir -p chiprun_out/final3
(cd .checkout && python3 -m chipbench --workload $A --seed 31338 --seconds 51 --trace 0) > chiprun_out/final3/archive.txt 2>&1
echo "archive rc=$?" >> chiprun_out/final3/archive.txt
rm -rf .scratch/only && mkdir -p .scratch/only/tests && cp -r BENCHMARK.json chipbench .scratch/only/ && cp -r tests/chipbench .scratch/only/tests/
(cd .scratch/only && python3 -m chipbench --workload $A --seed 1 --seconds 5 --trace 0) > chiprun_out/final3/only.txt 2>&1
echo "only rc=$?" >> chiprun_out/final3/only.txt
tail -n 3 chiprun_out/final3/archive.txt | cut -c1-600; tail -n 4 chiprun_out/final3/only.txt | cut -c1-300
python3 chipbench/proving/runs.py traces3 "--workload $A --seed 5101 --seconds 51 --trace 1" "--workload $B --seed 5102 --seconds 51 --trace 1" "--workload $C --seed 5103 --seconds 51 --trace 1"
python3 chipbench/proving/runs.py fixC \
 "--workload $C --seed 8101 --seconds 51 --trace 0" "--workload $C --seed 2147486101 --seconds 51 --trace 0" "--workload $C --seed 8103 --seconds 51 --trace 0" \
 "--workload $C --seed 8104 --seconds 51 --trace 0" "--workload $C --seed 2147486105 --seconds 51 --trace 0" "--workload $C --seed 8106 --seconds 51 --trace 0"
sh chipbench/proving/short.sh short5k $B 8201 2147486202
sh chipbench/proving/short.sh shortarr $C 8301 2147486302
