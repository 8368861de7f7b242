#!/usr/bin/env python
"""pd_top: pretty-print a paddle_tpu observability snapshot, live or dumped.

The `top(1)` of the telemetry hub (docs/observability.md):

    python tools/pd_top.py snapshot.json   # a saved observability.snapshot()
    python tools/pd_top.py --port 9100                # live /snapshot
    python tools/pd_top.py --port 9100 --watch 2      # refresh every 2s
    python tools/pd_top.py --port 9100 --json         # raw JSON passthrough
    python tools/pd_top.py --port 9100 --fleet        # fleet plane only

The live mode talks to the stdlib endpoint started by
``observability.serve(port)`` / ``PT_METRICS_PORT=<port>``. Rendering is
``observability.render_snapshot`` — the same tables ``report()`` prints —
so a dumped file and a live process look identical.

``--fleet`` filters to the fleet observability plane (the supervisor
process's ``fleet_telemetry`` + ``slo`` providers): per-replica rows
(state, pool, inflight, beat age, p95, KV headroom), the fleet totals
line, and the SLO burn table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable from anywhere: the repo root (one up from tools/) wins over
# sys.path[0] being tools/ itself
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(args) -> dict:
    if args.port is not None:
        import urllib.request

        url = f"http://{args.host}:{args.port}/snapshot"
        with urllib.request.urlopen(url, timeout=5) as r:
            return json.load(r)
    with open(args.path) as f:
        return json.load(f)


def _render(snap: dict) -> str:
    try:
        from paddle_tpu.observability import render_snapshot

        return render_snapshot(snap)
    except ImportError:  # render dumped files even without the package
        return json.dumps(snap, indent=1, default=str)


_FLEET_FAMS = ("fleet_telemetry", "slo", "fleet_trace", "serving_fleet",
               "kv_migration")


def _fleet_filter(snap: dict) -> dict:
    """Keep only the fleet-plane families (+ meta). An empty result
    means the snapshot is not from a fleet supervisor process."""
    out = {k: v for k, v in snap.items()
           if k in _FLEET_FAMS or k == "meta"}
    if not any(k in out for k in ("fleet_telemetry", "slo")):
        out["fleet_telemetry"] = {
            "error": "no fleet_telemetry/slo providers in this snapshot "
                     "(point pd_top at the fleet SUPERVISOR process)"}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="pd_top", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("path", nargs="?", default=None,
                    help="dumped observability.snapshot() JSON file")
    ap.add_argument("--port", type=int, default=None,
                    help="live mode: observability.serve() port")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--watch", type=float, default=None, metavar="SECONDS",
                    help="live mode: refresh every N seconds until ^C")
    ap.add_argument("--json", action="store_true",
                    help="print the raw snapshot JSON instead of tables")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet mode: only the merged fleet telemetry "
                         "(per-replica rows + totals) and SLO tables")
    args = ap.parse_args(argv)
    if (args.path is None) == (args.port is None):
        ap.error("give exactly one of: a snapshot file, or --port")
    try:
        while True:
            snap = _load(args)
            if args.fleet:
                snap = _fleet_filter(snap)
            out = json.dumps(snap, indent=1, default=str) if args.json \
                else _render(snap)
            if args.watch:
                print("\x1b[2J\x1b[H", end="")  # clear screen, home cursor
            print(out)
            if not args.watch:
                return 0
            time.sleep(args.watch)
    except KeyboardInterrupt:
        return 0
    except OSError as e:
        print(f"pd_top: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
