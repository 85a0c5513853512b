#!/usr/bin/env bash
# Rehearse the whole benchmark command without a chip: the tiny configuration
# benchmark/configs/rehearsal-tiny.json (marked "cell": false) on the CPU
# backend, through the same run.py, server child, load generator, scrapes,
# tracer and reduction as a cell. Nothing it prints is a measurement: the
# result lines say platform "cpu".
#
#   bash benchmark/rehearse.sh
#
# Four runs: closed loop; open loop, traced; a deliberate over-budget run,
# which must END with a failing result line and a non-zero exit, not hang;
# and the command as the driver gives it, which must refuse to run without an
# accelerator and print no result line. Then one run for every file of
# benchmark/rehearsals/: a later PR that brings a family or a traffic kind
# adds its rehearsal there, as data (its arguments, the metrics its line must
# and must not hold, the counters that must have moved in its window).
set -u
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
fail=0

run() {  # name, expected exit code, arguments...
  local name=$1 want=$2; shift 2
  local out; out=$(mktemp)
  timeout 300 python3 benchmark/run.py "$@" >"$out" 2>/dev/null
  local rc=$?
  grep '^\[benchmark' "$out" | tail -n 6
  if [ "$rc" -ne "$want" ]; then echo "REHEARSAL FAILED: $name exited $rc, expected $want"; fail=1; fi
  LAST=$(tail -n 1 "$out"); MOVED=$(grep 'counters that moved in the window' "$out" | tail -n 1)
  rm -f "$out"
}

common=(--workload rehearsal --config rehearsal-tiny --rehearse --seconds 3)

echo "== closed loop"
run closed 0 "${common[@]}" --traffic rehearsal-closed --seed 3000000007 --trace 0
python3 - "$LAST" <<'PY' || fail=1
import json, sys
r = json.loads(sys.argv[1])
assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0, r
assert set(r["metrics"]) == {"items_per_s", "latency_p50_ms", "setup_s"}, r
assert r["device"]["platform"] == "cpu"
print("ok: closed loop result line")
PY

echo "== open loop, traced"
run open-traced 0 "${common[@]}" --traffic rehearsal-open --seed 11 --trace 1
python3 - "$LAST" <<'PY' || fail=1
import json, sys
r = json.loads(sys.argv[1])
assert r["correct"] is True and r["attempted"] > 0, r
assert {"ingest_parse_ms_p50", "queue_ms_p50", "batch_fill_ratio", "compiles_in_window",
        "server_cpu_ms_per_item", "loadgen_late_ms_p95", "request_p95_ms"} <= set(r["metrics"]), r
# no device plane on the CPU backend: no device metric may appear
assert not {"exec_ms_per_batch", "exec_roofline_share", "device_idle_share"} & set(r["metrics"]), r
assert "busy_s" not in r["device"]
print("ok: traced result line, and no device metric from a CPU run")
PY

echo "== over budget on purpose (33 s: enough to start the server, not to finish; the child must be killed)"
start=$(date +%s)
run over-budget 1 "${common[@]}" --traffic rehearsal-closed --seed 5 --trace 0 --budget-s 33
took=$(( $(date +%s) - start ))
python3 - "$LAST" "$took" <<'PY' || fail=1
import json, sys
r = json.loads(sys.argv[1])
assert r["correct"] is False and r["metrics"] == {}, r
assert int(sys.argv[2]) < 60, f"took {sys.argv[2]} s: it has to stop at its budget"
print(f"ok: over-budget run ended with a failing result line after {sys.argv[2]} s")
PY
if pgrep -f benchmark/serve_child.py >/dev/null; then echo "REHEARSAL FAILED: a server child was left behind"; fail=1; fi

echo "== the driver's command on a machine with no accelerator"
out=$(mktemp)
env -u JAX_PLATFORMS timeout 300 python3 benchmark/run.py --workload bert-base-s512.docs-closed-64 \
  --seed 1 --seconds 3 --trace 0 >"$out" 2>/dev/null
rc=$?
if [ "$rc" -eq 0 ] || tail -n 1 "$out" | grep -q '^{'; then
  echo "REHEARSAL FAILED: a run without an accelerator exited $rc or printed a result"; fail=1
else
  echo "ok: refused without an accelerator (exit $rc, no result line)"
fi
rm -f "$out"

for f in benchmark/rehearsals/*.json; do
  [ -e "$f" ] || continue
  name=$(basename "$f" .json)
  echo "== rehearsals/$name: $(python3 -c 'import json, sys; print(json.load(open(sys.argv[1]))["why"])' "$f")"
  mapfile -t extra < <(python3 -c 'import json, sys; print("\n".join(json.load(open(sys.argv[1]))["args"]))' "$f")
  run "$name" 0 --workload "rehearsal-$name" --rehearse --seconds 3 "${extra[@]}"
  python3 - "$f" "$LAST" "$MOVED" <<'PY' || fail=1
import json, sys
want, r, moved = json.load(open(sys.argv[1])), json.loads(sys.argv[2]), sys.argv[3]
assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0, r
assert r["device"]["platform"] == "cpu"
assert set(want.get("metrics", [])) <= set(r["metrics"]), (want["metrics"], sorted(r["metrics"]))
assert not set(want.get("not_metrics", [])) & set(r["metrics"]), sorted(r["metrics"])
for c in want.get("counters", []):
    assert f"{c}=" in moved, f"{c} did not move in the window: {moved}"
print("ok: result line, metrics and counters as the rehearsal's file says")
PY
done
[ "$fail" -eq 0 ] && echo "REHEARSAL PASSED" || echo "REHEARSAL FAILED"
exit $fail
