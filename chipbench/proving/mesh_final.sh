# PR 27's last four-chip call (chiprun --chips 4 -- sh chipbench/proving/mesh_final.sh):
# the committed files alone (.checkout: git archive $(git write-tree), made
# before the call), the new cell six times untraced on PR 23's six seeds and
# once traced, then the spreads as the contract defines them
C=basic-50000.mesh-burst-20k
ARGS=""
for s in 7 4242 1000003 2147483659 2147483777 998244353; do ARGS="$ARGS \"--workload $C --seed $s --seconds 51 --trace 0\""; done
(cd .checkout && eval python3 chipbench/proving/runs.py final $ARGS "\"--workload $C --seed 2147488207 --seconds 51 --trace 1\"")
mkdir -p chiprun_out/pr27_final && cp -r .checkout/chiprun_out/final/. chiprun_out/pr27_final/
python3 chipbench/proving/spreads.py pr27_final
