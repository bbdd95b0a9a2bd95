#!/usr/bin/env bash
# Pre-merge smoke gate: tier-1 tests + the table2 quick benchmark, so policy
# regressions surface before merge (DESIGN.md §7).
#
#   bash tools/smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 pytest =="
python -m pytest -x -q

echo "== table2 quick benchmark =="
python -m benchmarks.run --quick --only table2

echo "== capacity-planning quick benchmark =="
python -m benchmarks.run --quick --only capacity

echo "== fleet-routing quick benchmark =="
python -m benchmarks.run --quick --only fleet_routing

echo "== fleet-rebalance quick benchmark =="
python -m benchmarks.run --quick --only fleet_rebalance

echo "== site-hierarchy quick benchmark =="
python -m benchmarks.run --quick --only site_hierarchy

echo "== chaos-resilience quick benchmark =="
python -m benchmarks.run --quick --only chaos_resilience

echo "== observability quick benchmark =="
python -m benchmarks.run --quick --only observability

echo "== alerting quick benchmark =="
python -m benchmarks.run --quick --only alerting

echo "== batched-engine quick benchmark (grid engine + oracle parity + tails) =="
# forced host devices exercise the sharded member axis; the grid rows record
# members/sec trajectory into BENCH_batched_engine.json via --artifacts below
ARTIFACTS_DIR="${ARTIFACTS_DIR:-out/smoke-artifacts}"
rm -rf "$ARTIFACTS_DIR"
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m benchmarks.run --quick --only batched_engine \
    --artifacts "$ARTIFACTS_DIR"

echo "== artifact pipeline (instrumented run -> manifest/metrics/events/incidents/report) =="
python -m benchmarks.run --quick --only table2,alerting --artifacts "$ARTIFACTS_DIR"
python tools/incidents.py "$ARTIFACTS_DIR" > /dev/null
python - "$ARTIFACTS_DIR" <<'EOF'
import json, os, sys
d = sys.argv[1]
from repro.obs.export import read_events, read_manifest, read_prometheus
man = read_manifest(d)
assert man["numpy"] and "git_sha" in man, man
read_prometheus(os.path.join(d, "metrics.prom"))
read_events(os.path.join(d, "events.jsonl"))
bench = [p for p in os.listdir(d) if p.startswith("BENCH_") and p.endswith(".json")]
assert bench, f"no BENCH_*.json under {d}"
for p in bench:
    with open(os.path.join(d, p)) as f:
        assert json.load(f)["rows"] is not None, f"{p}: module raised"
with open(os.path.join(d, "incidents.json")) as f:
    inc = json.load(f)
assert inc["n_incidents"] >= 1, inc  # the alerting module injects real faults
assert inc["n_false_alarms"] == 0, inc
print(f"artifacts OK: {sorted(os.listdir(d))}")
EOF
python tools/report.py "$ARTIFACTS_DIR" > "$ARTIFACTS_DIR/report.md"
echo "report: $ARTIFACTS_DIR/report.md"

echo "== scenario + registry docs sync check =="
python tools/gen_scenario_docs.py --check

echo "smoke OK"
