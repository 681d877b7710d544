# PR 31's last call: a cell from a checkout that holds only the committed
# files (git archive $(git write-tree), unpacked in .checkout/final), one
# traced run of the cell with the new metric from there, and the refusal
# in a directory that holds only the benchmark
mkdir -p chiprun_out/final31
(cd .checkout/final && python3 -m chipbench --workload basic-5000.burst-10k --seed 2147496001 --seconds 51 --trace 0) > chiprun_out/final31/archive.txt 2>&1
echo "archive rc=$?" >> chiprun_out/final31/archive.txt
(cd .checkout/final && python3 -m chipbench --workload spread-anti-5000.burst-5k --seed 2147496002 --seconds 51 --trace 1) > chiprun_out/final31/archive5k.txt 2>&1
echo "archive5k rc=$?" >> chiprun_out/final31/archive5k.txt
rm -rf .scratch/only && mkdir -p .scratch/only/tests && cp -r .checkout/final/BENCHMARK.json .checkout/final/chipbench .scratch/only/ && cp -r .checkout/final/tests/chipbench .scratch/only/tests/
(cd .scratch/only && python3 -m chipbench --workload basic-5000.burst-10k --seed 1 --seconds 5 --trace 0) > chiprun_out/final31/only.txt 2>&1
echo "only rc=$?" >> chiprun_out/final31/only.txt
grep "^compare\|^window\|^set-up\|rc=" chiprun_out/final31/archive.txt | cut -c1-300; tail -n 2 chiprun_out/final31/archive.txt | cut -c1-700
grep "^compare\|^window\|^set-up\|rc=" chiprun_out/final31/archive5k.txt | cut -c1-300; tail -n 2 chiprun_out/final31/archive5k.txt | cut -c1-3000
tail -n 4 chiprun_out/final31/only.txt | cut -c1-300
