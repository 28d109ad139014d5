#!/bin/bash
# First look at the one-chip cells, one chip call (PR 25):
#   chiprun --timeout 3300 -- bash benchmarks/chip/first_look.sh
# train_dense cold, traced, warm; with SWEEP=1 the rate sweep;
# then serve_chat and serve_sat, each plain and traced.  Traces and full
# outputs go to chiprun_out/.
mkdir -p chiprun_out
run() {  # name, then arguments of benchmarks.run
  name=$1; shift
  python3 -m benchmarks.run "$@" \
    > chiprun_out/$name.out 2> chiprun_out/$name.err
  echo "== $name rc=$?"; tail -n 7 chiprun_out/$name.out | cut -c1-1800
  tail -n 4 chiprun_out/$name.err | cut -c1-600
}
S1=2147483659; S2=2147483693
run td_cold  --workload train_dense --seed $S1 --seconds 10 --trace 0
run td_trace --workload train_dense --seed $S2 --seconds 10 --trace 1 --keep-trace chiprun_out/td_trace
run td_warm  --workload train_dense --seed $S1 --seconds 10 --trace 0
if [ -n "$SWEEP" ]; then
python3 -m benchmarks.sweep --workload serve_chat --rates ${RATES:-4,6,8,10,12,14,18} --seconds 16 \
  > chiprun_out/sweep.out 2> chiprun_out/sweep.err
echo "== sweep rc=$?"; tail -n 10 chiprun_out/sweep.out; tail -n 4 chiprun_out/sweep.err | cut -c1-600
fi
run sc_plain --workload serve_chat --seed $S1 --seconds 32 --trace 0
run sc_trace --workload serve_chat --seed $S2 --seconds 32 --trace 1 --keep-trace chiprun_out/sc_trace
run ss_plain --workload serve_sat --seed $S1 --seconds 32 --trace 0
run ss_trace --workload serve_sat --seed $S2 --seconds 32 --trace 1 --keep-trace chiprun_out/ss_trace
gzip -r chiprun_out/td_trace chiprun_out/sc_trace chiprun_out/ss_trace; du -sh chiprun_out/* | tail -n 20
