# Two sets of six runs of one cell, the same six seeds in both sets (each
# run of a set another seed), then one traced run, all in one call (one
# compile cache), as the bounds are set from:
#   chiprun --chips <n> --timeout 2400 -- bash benchmark/tools/sets.sh <cell> <seconds> <seed base> <outdir>
# then: python3 benchmark/tools/spread.py --sets 2 chiprun_out/<outdir>/run{1..12}.out
cell=$1; secs=$2; base=$3; out=chiprun_out/$4; mkdir -p $out
for i in 1 2 3 4 5 6 7 8 9 10 11 12; do
  python3 benchmark/run.py --workload $cell --seed $((base+1+(i-1)%6)) --seconds $secs --trace 0 > $out/run$i.out 2> $out/run$i.err; echo "i=$i rc=$? $(tail -n 1 $out/run$i.out | cut -c1-330)"
done
python3 benchmark/run.py --workload $cell --seed $((base+13)) --seconds $secs --trace 1 > $out/trace.out 2> $out/trace.err; echo "trace rc=$? $(tail -n 1 $out/trace.out | cut -c1-2500)"
mkdir -p $out/json; cp .bench_out/$cell.*.json $out/json/
cat $out/*.err | grep -v "UserWarning\|warnings.warn" | tail -5
