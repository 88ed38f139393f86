"""Tracing CLI: export the cluster timeline or one trace's span tree, or
print a finished run's start-up as a waterfall.

    python -m ray_tpu.observability timeline [--out timeline.json]
                                             [--window 300] [--limit N]
    python -m ray_tpu.observability trace <trace_id> [--out tree.json]
    python -m ray_tpu.observability startup [--session DIR] [--json]

The GCS address comes from --address or the RAY_TPU_GCS_ADDRESS env var
(set for every cluster process; for a driver shell, pass it explicitly).
Load the timeline file in https://ui.perfetto.dev or chrome://tracing.
`startup` needs no cluster: it reads `<session>/lifecycle/`, by default
the newest session under `$RAY_TPU_TMPDIR` (`/tmp/ray_tpu`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _gcs_client(address: str):
    from ray_tpu.core.rpc import RpcClient

    return RpcClient(address, name="trace-cli->gcs")


def _resolve_address(args) -> str:
    addr = args.address or os.environ.get("RAY_TPU_GCS_ADDRESS")
    if not addr:
        sys.exit("no GCS address: pass --address HOST:PORT or set "
                 "RAY_TPU_GCS_ADDRESS")
    return addr


def _write(out_path: str, obj) -> None:
    text = json.dumps(obj)
    if out_path == "-":
        sys.stdout.write(text + "\n")
        return
    with open(out_path, "w", encoding="utf-8") as f:
        f.write(text)
    print(f"wrote {out_path} ({len(text)} bytes)")


def _newest_session() -> str:
    import glob

    base = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    sessions = [d for d in glob.glob(os.path.join(base, "session_*"))
                if os.path.isdir(os.path.join(d, "lifecycle"))]
    if not sessions:
        sys.exit(f"no session with a lifecycle/ directory under {base}: "
                 "pass --session DIR")
    return max(sessions, key=os.path.getmtime)


def _startup(args) -> int:
    from ray_tpu.observability import format_waterfall, startup_report

    report = startup_report(args.session or os.environ.get(
        "RAY_TPU_SESSION_DIR") or _newest_session())
    if args.json:
        print(json.dumps(report, default=str))
    else:
        print(format_waterfall(report))
    return 0 if report["startups"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m ray_tpu.observability")
    ap.add_argument("--address", default=None,
                    help="GCS address (default: $RAY_TPU_GCS_ADDRESS)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    tl = sub.add_parser("timeline",
                        help="export the Chrome trace-event timeline")
    tl.add_argument("--out", default="timeline.json",
                    help="output path, or - for stdout")
    tl.add_argument("--window", type=float, default=None,
                    help="only spans ending within the last WINDOW seconds")
    tl.add_argument("--limit", type=int, default=None,
                    help="cap on exported spans (newest win)")
    tr = sub.add_parser("trace", help="export one trace's span tree")
    tr.add_argument("trace_id")
    tr.add_argument("--out", default="-", help="output path (default stdout)")
    su = sub.add_parser("startup",
                        help="print a session's start-up waterfall")
    su.add_argument("--session", default=None,
                    help="session directory (default: the newest)")
    su.add_argument("--json", action="store_true",
                    help="the merged report as JSON instead of text")
    args = ap.parse_args(argv)
    if args.cmd == "startup":
        return _startup(args)

    from ray_tpu.observability import chrome_trace_events, span_tree

    gcs = _gcs_client(_resolve_address(args))
    try:
        if args.cmd == "timeline":
            resp = gcs.call("trace_timeline",
                            {"window_s": args.window, "limit": args.limit},
                            timeout=30)
            spans = resp.get("spans") or []
            if not spans:
                print("no spans recorded (is tracing_enabled on?)",
                      file=sys.stderr)
            _write(args.out, chrome_trace_events(spans))
            if resp.get("dropped"):
                print(f"note: GCS dropped {resp['dropped']} spans "
                      "(trace_gcs_max_spans)", file=sys.stderr)
        else:
            resp = gcs.call("trace_get", {"trace_id": args.trace_id},
                            timeout=30)
            _write(args.out, span_tree(resp.get("spans") or [],
                                       args.trace_id))
    finally:
        gcs.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
