#!/bin/bash
# Several runs in one chip call (a call's overhead is a minute; a session waits on each):
#   chiprun --chips 1 -- bash benchmark/tests/chip_runs.sh <label> "<name>|<program> <arguments>" ...
# <program> is run.py or tests/control.py. Each run's output goes to
# chiprun_out/<label>/<name>.{out,err}; the lines after the build line are shown.
set -u
label=$1; shift
out=chiprun_out/$label
mkdir -p "$out"
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset} cores=$(nproc)"
for spec in "$@"; do
  name=${spec%%|*}; cmd=${spec#*|}
  echo "=== $name: $cmd"
  start=$(date +%s)
  # shellcheck disable=SC2086
  python3 benchmark/$cmd > "$out/$name.out" 2> "$out/$name.err"
  echo "rc=$? wall=$(( $(date +%s) - start ))s"
  log=$(ls -t .bench_cache/benchmark/*/server.log 2>/dev/null | head -1)
  [ -n "$log" ] && tail -c 20000 "$log" > "$out/$name.server.log"
  grep -v '"phase": "build"' "$out/$name.out" | cut -c1-2500
  tail -6 "$out/$name.err" | cut -c1-1200
done
