# one cell's proof in one call: readings, limits, a traced run, two sets of six timed runs
cell=$1; seconds=$2; O=chiprun_out/proof/$cell; mkdir -p $O
python benchmark/calibrate.py --workload $cell --seeds 12 --control-seeds 4 > $O/cal.out 2> $O/cal.err; echo $cell calibrate rc=$?
tail -2 $O/cal.err
python benchmark/tools/set_limits.py $O/cal.out; cp benchmark/workloads/$cell.json $O/workload.json
python benchmark/run.py --workload $cell --seed 1000033 --seconds $seconds --trace 1 --keep-trace > $O/t1.out 2> $O/t1.err; echo $cell trace rc=$?
tail -1 $O/t1.out | cut -c1-2500; tail -2 $O/t1.err
for set in A B; do for seed in 3000000019 3000000037 3000000059 3000000061 3000000077 3000000083; do
python benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 > $O/$set.$seed.out 2> $O/$set.$seed.err; echo $cell $set $seed rc=$? $(tail -1 $O/$set.$seed.out | cut -c1-330)
done; done
# the capture comes back small: the profiler's own json copy goes, the xplane is compressed
find chiprun_out/trace -name "*.trace.json.gz" -delete; gzip -f chiprun_out/trace/$cell-seed*/plugins/profile/*/*.xplane.pb
