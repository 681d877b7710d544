# short runs of one cell on seeds it has not had, the control read beside
# each comparison: sh short.sh <tag> <workload> <seed> [<seed> ...]
TAG=$1; W=$2; shift 2
ARGS=""
for s in "$@"; do ARGS="$ARGS \"--workload $W --seed $s --seconds 6 --trace 0 --control\""; done
eval python3 chipbench/proving/runs.py $TAG $ARGS
