# two sets of 6 runs of one cell, the same seeds in both: sh sets.sh <workload> <tag>
W=$1
S="7 4242 1000003 2147483659 2147483777 998244353"
ARGS=""
for rep in 1 2; do for s in $S; do ARGS="$ARGS \"--workload $W --seed $s --seconds 51 --trace 0\""; done; done
eval python3 chipbench/proving/runs.py $2 $ARGS
