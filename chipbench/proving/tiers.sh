# PR 32's first call on the chip (one chip): the parent's archive asked
# for the new cell (.checkout/parent: git archive of the parent); the
# parent with this PR's benchmark files laid over it (.checkout/laid),
# the new cell and one old cell traced; then the working tree: the new
# cell untraced, traced (trace kept) and with the control.
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/proving/tiers.sh
CELL=priority-tiers-5000.preempt-1k
OUT=chiprun_out/tiers32
mkdir -p $OUT
t0=$(date +%s)
(cd .checkout/parent && python3 -m chipbench --workload $CELL --seed 2147532001 --seconds 51 --trace 0) > $OUT/parent_bare.txt 2>&1
echo "parent_bare rc=$? after $(( $(date +%s) - t0 ))s" | tee -a $OUT/parent_bare.txt
tail -n 3 $OUT/parent_bare.txt | cut -c1-300
(cd .checkout/laid && python3 -m chipbench --workload $CELL --seed 2147532002 --seconds 51 --trace 1) > $OUT/laid_new.txt 2>&1
echo "laid_new rc=$?" | tee -a $OUT/laid_new.txt
(cd .checkout/laid && python3 -m chipbench --workload basic-5000.burst-10k --seed 2147532003 --seconds 51 --trace 1) > $OUT/laid_old.txt 2>&1
echo "laid_old rc=$?" | tee -a $OUT/laid_old.txt
for f in laid_new laid_old; do grep "^compare\|^window\|^set-up\|^preempt" $OUT/$f.txt | cut -c1-330; tail -n 2 $OUT/$f.txt | cut -c1-4500; done
python3 chipbench/proving/runs.py tiers32 \
  "--workload $CELL --seed 2147532004 --seconds 51 --trace 0" \
  "--workload $CELL --seed 2147532005 --seconds 51 --trace 1 --keep-trace chiprun_out/tiers32/trace" \
  "--workload $CELL --seed 2147532006 --seconds 12 --trace 0 --control"
python3 chipbench/proving/span_dump.py chiprun_out/tiers32/trace > $OUT/spans.txt 2>&1
tail -n 60 $OUT/spans.txt | cut -c1-250
