#!/bin/bash
# The four-chip cell, one call (PR 25):
#   chiprun --chips 4 --timeout 3300 -- bash benchmarks/chip/four_chips.sh
# One cold run first.  If it fails at 4 x 4096 (memory), the copy on the
# machine is switched to the fallback traffic batch_4x2048 and tried again;
# the committed file is then changed by hand.  Then a traced run and the
# sets of plain runs.
mkdir -p chiprun_out
cold() {
  python3 -m benchmarks.run --workload train_dp2mp2 --seed 2147483659 --seconds 10 --trace 0 \
    > chiprun_out/mp_cold$1.out 2> chiprun_out/mp_cold$1.err
  rc=$?; echo "== mp_cold$1 rc=$rc"; tail -n 6 chiprun_out/mp_cold$1.out | cut -c1-1500
  tail -n 3 chiprun_out/mp_cold$1.err | cut -c1-800
  return $rc
}
if ! cold ""; then
  grep -m 3 -n "RESOURCE_EXHAUSTED\|Ran out of memory" chiprun_out/mp_cold.err | cut -c1-600
  echo "== falling back to batch_4x2048"
  sed -i 's/"batch_4x4096"/"batch_4x2048"/' benchmarks/workloads/train_dp2mp2.json BENCHMARK.json
  cold "_2048" || exit 1
fi
python3 -m benchmarks.run --workload train_dp2mp2 --seed 2147483693 --seconds 10 --trace 1 \
  --keep-trace chiprun_out/mp_trace > chiprun_out/mp_trace.out 2> chiprun_out/mp_trace.err
echo "== mp_trace rc=$?"; tail -n 3 chiprun_out/mp_trace.out | cut -c1-3000
tail -n 3 chiprun_out/mp_trace.err | cut -c1-600
python3 -m benchmarks.measure --workload train_dp2mp2 --seconds ${SECONDS_RUN:-32} --sets 2 \
  --seeds ${SEEDS:-2147483659,2147483693,2147483713,2147483743,2147483777}
gzip -r chiprun_out/mp_trace; du -sh chiprun_out/mp_trace
