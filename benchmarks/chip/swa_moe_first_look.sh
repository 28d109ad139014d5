#!/bin/bash
# First look at serve_swa_moe_sat, one chip call (PR 37):
#   chiprun --timeout 3300 -- bash benchmarks/chip/swa_moe_first_look.sh
# The kernels against their references at the cell's shapes; the parent commit
# (unpacked into _parent/ with this benchmark laid over it) must fail at once
# on the new cell; then the cell plain (cold), traced, the rate sweep
# (SWEEP=0 skips it) and the two precision readings.  Full outputs go to
# chiprun_out/.
mkdir -p chiprun_out
CELL=serve_swa_moe_sat
S1=2147483659; S2=2147483693
python3 -m benchmarks.chip.swa_moe_kernels > chiprun_out/swa_kernels.out 2> chiprun_out/swa_kernels.err
echo "== kernels rc=$?"; tail -n 12 chiprun_out/swa_kernels.out; tail -n 3 chiprun_out/swa_kernels.err | cut -c1-600
if [ -d _parent ]; then
  t0=$(date +%s)
  (cd _parent && python3 -m benchmarks.run --workload $CELL --seed $S1 --seconds 32 --trace 0 \
     > ../chiprun_out/parent_new_cell.out 2> ../chiprun_out/parent_new_cell.err)
  echo "== parent on $CELL rc=$? after $(( $(date +%s) - t0 )) s"; tail -n 3 chiprun_out/parent_new_cell.err | cut -c1-400
fi
run() {  # name, then arguments of benchmarks.run
  name=$1; shift
  python3 -m benchmarks.run "$@" > chiprun_out/$name.out 2> chiprun_out/$name.err
  echo "== $name rc=$?"; grep -v '"phase": "routing"' chiprun_out/$name.out | tail -n 9 | cut -c1-3000
  grep '"phase": "routing"' chiprun_out/$name.out | cut -c1-400
  tail -n 4 chiprun_out/$name.err | cut -c1-600
}
run swa_plain --workload $CELL --seed $S1 --seconds 32 --trace 0
run swa_trace --workload $CELL --seed $S2 --seconds 32 --trace 1 --keep-trace chiprun_out/swa_trace
if [ "${SWEEP:-1}" != 0 ]; then
python3 -m benchmarks.sweep --workload $CELL --rates ${RATES:-2,2.5,3,3.5,4,4.5,5,6} --seconds 24 \
  > chiprun_out/swa_sweep.out 2> chiprun_out/swa_sweep.err
echo "== sweep rc=$?"; tail -n 12 chiprun_out/swa_sweep.out; tail -n 4 chiprun_out/swa_sweep.err | cut -c1-600
fi
if [ "${PRECISION:-1}" != 0 ]; then
python3 -m benchmarks.chip.precision_readings --workload $CELL --seeds $S1 \
  > chiprun_out/swa_precision.out 2> chiprun_out/swa_precision.err
echo "== precision rc=$?"; grep -v '"phase": "routing"' chiprun_out/swa_precision.out | tail -n 10
grep '"phase": "routing"' chiprun_out/swa_precision.out | cut -c1-400; tail -n 4 chiprun_out/swa_precision.err | cut -c1-600
fi
gzip -r chiprun_out/swa_trace 2>/dev/null; du -sh chiprun_out/* | tail -n 12
