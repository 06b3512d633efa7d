#!/usr/bin/env python3
"""The by-hand look at a trace, and the cut of one that the tests keep.

    python3 benchmarks/trace_tool.py --trace-dir benchmarks/.scratch/<cell>/trace \\
        [--describe out.json] [--thin-to fixture.json]

A traced run leaves its ``.xplane.pb`` under the cell's scratch directory;
this reads it after the run has ended (it needs no chip). ``--describe``
writes planes, lines and their heaviest event names; ``--thin-to`` writes the
first events of each device line, for ``tests/data``.
"""

from __future__ import annotations

import argparse
import json

import trace_reduce
from trace_reduce import DEVICE_PLANE


def describe(record: dict, top: int = 12) -> list:
    """Planes, lines and their heaviest event names: the by-hand look."""
    out = []
    for pname, lines in record.items():
        for lname, events in lines.items():
            by_name: dict = {}
            for name, _, dur in events:
                by_name[name] = by_name.get(name, 0.0) + dur
            heavy = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
            out.append({"plane": pname, "line": lname, "events": len(events),
                        "top": [[n, d / 1e9] for n, d in heavy]})
    return out


def thin(record: dict, keep: int = 400) -> dict:
    """A small cut of a record (the first ``keep`` events of each device
    line), for a fixture."""
    out: dict = {}
    for pname, lines in record.items():
        if DEVICE_PLANE.match(pname):
            out[pname] = {ln: sorted(ev, key=lambda e: e[1])[:keep]
                          for ln, ev in lines.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n", 1)[0])
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--describe", default="")
    ap.add_argument("--thin-to", default="")
    args = ap.parse_args(argv)
    record = trace_reduce.load_xplane(args.trace_dir, keep_host=True)
    if args.describe:
        with open(args.describe, "w") as f:
            json.dump(describe(record), f, indent=1)
    if args.thin_to:
        with open(args.thin_to, "w") as f:
            json.dump(thin(record), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
