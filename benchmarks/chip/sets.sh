#!/bin/bash
# The sets of runs the bounds are set from, one chip call for a few cells (PR 25):
#   chiprun --timeout 3500 -- bash benchmarks/chip/sets.sh train_dense serve_sat:4
# Two sets of runs a cell, the same seeds in both (six, or the number after
# the colon), each run a new process; every last line goes to
# chiprun_out/<cell>.jsonl.
ALL=(2147483659 2147483693 2147483713 2147483743 2147483777 2147483783)
for arg in "$@"; do
  cell=${arg%%:*}; n=6; [[ $arg == *:* ]] && n=${arg##*:}
  seeds=$(IFS=,; echo "${ALL[*]:0:$n}")
  echo "== $cell ($n seeds a set)"
  python3 -m benchmarks.measure --workload $cell --seconds ${SECONDS_RUN:-32} \
    --sets ${SETS:-2} --seeds $seeds
done
# the proof that the committed files are enough: one cell from a directory
# that holds only what `git archive $(git write-tree)` unpacks (made before
# the call: rm -rf _archive; mkdir _archive; git archive $(git write-tree) | tar -x -C _archive)
if [ -d _archive ]; then
  echo "== from _archive: $(ls _archive | tr '\n' ' ')"
  (cd _archive && python3 -m benchmarks.run --workload ${ARCHIVE_CELL:-train_dense} \
     --seed 2147483659 --seconds 10 --trace 0 2>&1 | tail -n 2 | cut -c1-1200)
fi
