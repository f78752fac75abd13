#!/bin/sh
# The two full sets of one cell (same six seeds in both) and three traced
# runs, in one call on the chip: sh benchmarks/tools/full_sets.sh <cell> <first seed> [seconds]
cell=$1; first=$2; seconds=${3:-}
mkdir -p chiprun_out
out=chiprun_out/sets_$cell.jsonl
run() { # set, seed, trace
  python3 benchmarks/run.py --workload "$cell" --seed "$2" ${seconds:+--seconds $seconds} --trace "$3" \
    > chiprun_out/last_run.out 2> chiprun_out/last_run.err
  rc=$?
  grep -v arn chiprun_out/last_run.out | grep -v '^{' | cut -c1-900 >> chiprun_out/log_$cell.txt
  printf '{"set": "%s", "seed": %s, "trace": %s, "rc": %s, "line": %s}\n' "$1" "$2" "$3" "$rc" \
    "$(tail -n 1 chiprun_out/last_run.out | grep '^{' || echo null)" >> "$out"
  [ "$rc" = 0 ] || tail -n 15 chiprun_out/last_run.err >> chiprun_out/log_$cell.txt
}
for set in A B; do
  for i in 0 1 2 3 4 5; do run $set $((first + 1000003 * i)) 0; done
done
for i in 6 7 8; do run T $((first + 1000003 * i)) 1; done
python3 - "$out" <<'PY'
import json, statistics, sys
rows = [json.loads(l) for l in open(sys.argv[1])]
print("rc", [r["rc"] for r in rows], "correct", [r["line"] and r["line"]["correct"] for r in rows])
for s in "AB":
    vals = {}
    for r in rows:
        if r["set"] == s and r["line"]:
            for k, m in r["line"]["metrics"].items():
                vals.setdefault(k, []).append(m["value"])
    for k, v in vals.items():
        q = statistics.quantiles(v, n=4)
        print(s, k, "median %.5g spread %.4f" % (statistics.median(v), (q[2] - q[0]) / statistics.median(v)), [round(x, 3) for x in v])
PY
