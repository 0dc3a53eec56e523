#!/usr/bin/env python
"""Render an obs trace (DESIGN.md §14) as a serving post-mortem report.

    PYTHONPATH=src python scripts/obs_report.py trace.jsonl

Reads the JSONL span trace a serving loop wrote via ``--trace-out`` and
prints the summaries an operator actually reaches for:

  * the sync budget per ledger phase (where the engine's convergence
    checks went — the device-independent cost signal);
  * wall-clock p50/p99 per span name (where the time went), with each
    name's mean self time: a span's duration less its child spans';
  * the counts the engine wrappers reported (link rounds, GConn rounds,
    convergence checks): total, and mean per record in each parent span;
  * the incident log: every ``audit_violation`` and ``recovery`` event,
    i.e. what the self-healing ladder saw and what it decided.

Exits 0 on a well-formed trace (even an empty one); nonzero only on a
missing/corrupt file. ``scripts/obs_smoke.sh`` runs this in CI.
"""
from __future__ import annotations

import argparse
import sys


def _percentile(xs: list[float], q: float) -> float:
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q / 100 * (len(xs) - 1))))
    return xs[i]


def _self_ms(spans: list[dict]) -> dict[int, float]:
    """{span id: duration less its children's, ms}."""
    children: dict[int, int] = {}
    for s in spans:
        if s.get("parent") is not None:
            children[s["parent"]] = children.get(s["parent"], 0) + s["dur"]
    return {s["id"]: (s["dur"] - children.get(s["id"], 0)) / 1e3
            for s in spans if "id" in s}


def report(records: list[dict], out=sys.stdout) -> None:
    spans = [r for r in records if r.get("type") == "span"]
    counts = [r for r in records if r.get("type") == "count"]
    events = [r for r in records if r.get("type") == "event"]
    summaries = [r for r in records if r.get("type") == "summary"]

    print("== sync budget per phase ==", file=out)
    by_phase = summaries[-1]["sync_by_phase"] if summaries else {}
    if not by_phase:
        print("  (no ledger phases recorded)", file=out)
    total = sum(by_phase.values())
    for phase in sorted(by_phase):
        v = by_phase[phase]
        pct = 100.0 * v / total if total else 0.0
        print(f"  {phase:20s} {v:8d} syncs  ({pct:5.1f}%)", file=out)
    if by_phase:
        print(f"  {'total':20s} {total:8d} syncs", file=out)

    print("\n== span latency (p50/p99, mean self time; ms) ==", file=out)
    names: dict[str, list] = {}
    for s in spans:
        names.setdefault(s["name"], []).append(s["dur"] / 1e3)
    if not names:
        print("  (no spans recorded)", file=out)
    self_ms = _self_ms(spans)
    for name in sorted(names):
        ms = names[name]
        syncs = sum(s.get("syncs", 0) for s in spans
                    if s["name"] == name)
        own = [self_ms[s["id"]] for s in spans
               if s["name"] == name and s.get("id") in self_ms]
        own_col = f"{sum(own) / len(own):8.2f}" if own else f"{'-':>8s}"
        print(f"  {name:20s} n={len(ms):5d}  "
              f"p50 {_percentile(ms, 50):8.2f}  "
              f"p99 {_percentile(ms, 99):8.2f}  self {own_col}  "
              f"syncs={syncs}", file=out)

    print("\n== counts (total; mean per record, by parent span) ==",
          file=out)
    if not counts:
        print("  (no counts recorded)", file=out)
    span_name = {s["id"]: s["name"] for s in spans if "id" in s}
    by_name: dict[str, list] = {}
    for c in counts:
        by_name.setdefault(c["name"], []).append(c)
    for name in sorted(by_name):
        cs = by_name[name]
        per_parent: dict[str, list] = {}
        for c in cs:
            per_parent.setdefault(span_name.get(c["parent"], "(none)"),
                                  []).append(c["value"])
        means = "  ".join(f"{p} {sum(v) / len(v):.2f} (n={len(v)})"
                          for p, v in sorted(per_parent.items()))
        print(f"  {name:20s} total {sum(c['value'] for c in cs):8d}  "
              f"{means}", file=out)

    print("\n== incidents ==", file=out)
    incidents = [e for e in events
                 if e["name"] in ("audit_violation", "recovery")]
    if not incidents:
        print("  (none)", file=out)
    for e in incidents:
        args = e.get("args", {})
        if e["name"] == "audit_violation":
            print(f"  audit_violation @{e['ts'] / 1e6:8.2f}s: "
                  f"{','.join(args.get('violations', []))} "
                  f"(n_violating={args.get('n_violating')}, "
                  f"syncs={args.get('syncs')})", file=out)
        else:
            print(f"  recovery        @{e['ts'] / 1e6:8.2f}s: "
                  f"mode={args.get('mode')} "
                  f"reason={args.get('reason')} "
                  f"(n_violating={args.get('n_violating')})", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="JSONL trace from --trace-out")
    args = ap.parse_args(argv)
    try:
        from repro.obs import read_jsonl
        records = read_jsonl(args.trace)
    except (OSError, ValueError) as e:
        print(f"obs_report: cannot read {args.trace}: {e}",
              file=sys.stderr)
        return 1
    report(records)
    return 0


if __name__ == "__main__":
    sys.exit(main())
