#!/bin/bash
# chip_guard.sh <tag> <command...> — run one command on the chip machine
# under a host-memory watchdog.
#
# A command that exhausts the chip machine's host memory loses the machine:
# chiprun returns 3 ("no machine was held"), nothing comes back, and the
# minutes are charged.  This aborts the command first — SIGABRT under
# PYTHONFAULTHANDLER prints the Python traceback of what was allocating —
# and logs the process's RSS as it grows.  bash never touches JAX, so the
# command still owns the chip.
#
#   chiprun -- bash scripts/chip_guard.sh bench python bench.py
tag=$1; shift
PYTHONFAULTHANDLER=1 "$@" &
pid=$!
peak=0
while kill -0 $pid 2>/dev/null; do
  rss=$(awk '/VmRSS/{print $2}' /proc/$pid/status 2>/dev/null || echo 0)
  avail=$(awk '/MemAvailable/{print $2}' /proc/meminfo)
  if [ "${rss:-0}" -gt $((peak + 1000000)) ]; then
    peak=$rss; echo "[guard:$tag] t=$SECONDS rss_kb=$rss avail_kb=$avail"
  fi
  if [ "$avail" -lt 12000000 ]; then
    echo "[guard:$tag] t=$SECONDS LOW MEMORY rss_kb=$rss avail_kb=$avail: aborting"
    kill -ABRT $pid; sleep 8; kill -9 $pid 2>/dev/null
  fi
  sleep 0.5
done
wait $pid
rc=$?
echo "[guard:$tag] done rc=$rc peak_rss_kb=$peak t=$SECONDS"
exit $rc
