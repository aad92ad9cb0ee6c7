#!/usr/bin/env python3
"""traceconv — convert flight-recorder trace dumps into a
Perfetto-loadable Chrome trace-event file.

Input: a JSON file holding either

- the ``/v1/agent/trace`` response object (``{"recent": [...],
  "tail": [...], ...}``) — e.g. ``curl $AGENT/v1/agent/trace > dump``;
  an optional ``profile_timeline`` key (the tuple list from
  ``Profiler.timeline.events()``) and ``convoys`` list merge in as
  pipeline/convoy tracks, or
- a bare JSON list of completed trace dicts.

Output: ``{"traceEvents": [...]}`` — load it at chrome://tracing or
https://ui.perfetto.dev.

With ``--xplane`` the input is instead a device profile: a directory
as ``jax.profiler.start_trace`` leaves it, or one ``.xplane.pb``. The
output, one JSON object on standard output, is the busiest device
plane's time by program (``modules``) and by ``jax.named_scope``
(``scopes``), and its idle gaps, longest first, each named by the
``nomad.*`` host annotation that covers most of it or ``no host
annotation`` (``idle_gaps``: ``[[name, seconds]]``; see
``nomad_tpu/profile/xplane.py``).

Usage:
    python tools/traceconv.py dump.json -o trace.chrome.json
    python tools/traceconv.py dump.json --tail-only
    python tools/traceconv.py --validate trace.chrome.json
    curl -s localhost:4646/v1/agent/trace | python tools/traceconv.py -
    python tools/traceconv.py --xplane /tmp/profile-dir

Exit codes: 0 = converted (or validated clean), 1 = validation
failures, 2 = usage/input error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

from nomad_tpu.profile.export import (  # noqa: E402
    chrome_trace,
    validate_chrome_trace,
)


def _load(path: str):
    if path == "-":
        return json.load(sys.stdin)
    with open(path) as f:
        return json.load(f)


def convert(doc, tail_only: bool = False) -> dict:
    """Dump object / bare trace list -> chrome trace document."""
    if isinstance(doc, list):
        traces = doc
        timeline = None
        convoys = None
    elif isinstance(doc, dict):
        tail = doc.get("tail") or []
        recent = [] if tail_only else (doc.get("recent") or [])
        # Tail first: dedup keeps the first occurrence, so the
        # p99-defining outliers win over their recent-ring duplicates.
        traces = tail + recent
        if not traces and "trace" in doc:
            traces = [doc["trace"]]  # ?eval= single-trace response
        timeline = doc.get("profile_timeline")
        convoys = doc.get("convoys")
    else:
        raise ValueError("input is neither a trace list nor a dump object")
    return chrome_trace(traces, timeline=timeline, convoys=convoys)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="traceconv", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("input", help="trace dump JSON file, or - for "
                        "stdin; with --xplane a profile directory or "
                        "an .xplane.pb file")
    parser.add_argument("-o", "--output", default="trace.chrome.json",
                        help="output file (default trace.chrome.json)")
    parser.add_argument("--tail-only", action="store_true",
                        help="convert only the tail-kept slow traces")
    parser.add_argument("--validate", action="store_true",
                        help="treat INPUT as a chrome trace file and "
                             "schema-check it instead of converting")
    parser.add_argument("--xplane", action="store_true",
                        help="treat INPUT as a device profile and print "
                             "device time by program and named scope, "
                             "and the idle gaps named by nomad.* host "
                             "annotations")
    args = parser.parse_args(argv)

    if args.xplane:
        from nomad_tpu.profile.xplane import reduce_xplane

        try:
            print(json.dumps(reduce_xplane(args.input), indent=1))
        except (OSError, ValueError) as e:
            print(f"traceconv: cannot read {args.input!r}: {e}",
                  file=sys.stderr)
            return 2
        return 0

    try:
        doc = _load(args.input)
    except (OSError, ValueError) as e:
        print(f"traceconv: cannot read {args.input!r}: {e}",
              file=sys.stderr)
        return 2

    if args.validate:
        errors = validate_chrome_trace(doc)
        for e in errors:
            print(f"traceconv: {e}", file=sys.stderr)
        if errors:
            print(f"traceconv: {len(errors)} schema violation(s)",
                  file=sys.stderr)
            return 1
        print(f"traceconv: {len(doc.get('traceEvents', []))} events, "
              f"schema clean")
        return 0

    try:
        out = convert(doc, tail_only=args.tail_only)
    except (KeyError, TypeError, ValueError) as e:
        print(f"traceconv: malformed trace dump: {e}", file=sys.stderr)
        return 2
    # Self-check before writing: a converter that emits an unloadable
    # file should fail loudly, not hand Perfetto a mystery.
    errors = validate_chrome_trace(out)
    if errors:
        for e in errors:
            print(f"traceconv: {e}", file=sys.stderr)
        return 1
    with open(args.output, "w") as f:
        json.dump(out, f)
    n_evals = sum(1 for e in out["traceEvents"]
                  if e.get("ph") == "M" and e.get("tid", 0) >= 10)
    print(f"traceconv: wrote {args.output} ({len(out['traceEvents'])} "
          f"events, {n_evals} eval tracks) — load at "
          f"https://ui.perfetto.dev or chrome://tracing")
    return 0


if __name__ == "__main__":
    sys.exit(main())
