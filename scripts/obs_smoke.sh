#!/usr/bin/env sh
# Observability smoke (DESIGN.md §14): a short serve_fleet run must land
# a JSONL span trace + Chrome trace-event JSON + metrics registry file,
# the Chrome export must pass schema validation (loadable in Perfetto),
# and scripts/obs_report.py must render the trace with a nonzero
# per-phase sync budget — so the instrumented serving path can't
# silently stop exporting. Called from bench_smoke.sh.
set -e
cd "$(dirname "$0")/.."
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}"
export PYTHONPATH

OBS_DIR=$(mktemp -d)
trap 'rm -rf "$OBS_DIR"' EXIT

python -m repro.launch.serve_fleet \
    --graph grid_64 --stream churn --batch 256 --steps 4 \
    --tenants 3 --slots 2 --tour incremental --tour-every 2 \
    --read-ratio 0.2 \
    --trace-out "$OBS_DIR/trace.jsonl" \
    --metrics-out "$OBS_DIR/metrics.json"

for f in trace.jsonl trace.jsonl.chrome.json metrics.json; do
    if [ ! -s "$OBS_DIR/$f" ]; then
        echo "obs_smoke: $f missing or empty" >&2
        exit 1
    fi
done

python - "$OBS_DIR" <<'EOF'
import json, sys

d = sys.argv[1]

# Chrome trace-event schema: what Perfetto/chrome://tracing needs.
ch = json.load(open(f"{d}/trace.jsonl.chrome.json"))
assert isinstance(ch["traceEvents"], list) and ch["traceEvents"], \
    "no traceEvents"
for ev in ch["traceEvents"]:
    assert ev["ph"] in ("X", "C", "i"), f"bad phase {ev['ph']!r}"
    assert isinstance(ev["name"], str) and isinstance(ev["ts"], int)
    if ev["ph"] == "X":
        assert isinstance(ev["dur"], int)
assert ch["otherData"]["sync_total"] > 0, "zero sync_total in otherData"
print(f"obs_smoke: chrome export ok "
      f"({len(ch['traceEvents'])} events, "
      f"sync_total={ch['otherData']['sync_total']})")

# Round trip: chrome export reconstructs the native records.
from repro.obs import chrome_to_records, read_jsonl
native = [r for r in read_jsonl(f"{d}/trace.jsonl")
          if r["type"] in ("span", "count", "event")]
assert chrome_to_records(ch) == native, "chrome round-trip mismatch"

# Metrics registry: per-tenant labels landed.
m = json.load(open(f"{d}/metrics.json"))
names = {rec["name"] for rec in m["metrics"]}
assert "applied_events" in names and "batch_latency_ms" in names, names
tenants = {dict(rec["labels"]).get("tenant")
           for rec in m["metrics"] if rec["name"] == "applied_events"}
assert len(tenants) == 3, f"expected 3 tenant labels, got {tenants}"
print(f"obs_smoke: metrics ok ({len(m['metrics'])} series, "
      f"{len(tenants)} tenants)")
EOF

REPORT=$(python scripts/obs_report.py "$OBS_DIR/trace.jsonl")
echo "$REPORT"
if ! echo "$REPORT" | grep -q "fleet_apply.*[1-9][0-9]* syncs"; then
    echo "obs_smoke: obs_report shows no nonzero fleet_apply sync budget" >&2
    exit 1
fi

echo "obs_smoke: ok (trace + chrome + metrics land; report renders)"
