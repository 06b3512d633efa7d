#!/usr/bin/env python3
"""Name every device idle gap of a traced run by what the host was doing.

    python3 benchmarks/trace_hostspans.py --trace-dir benchmarks/.scratch/<cell>/trace

The program writes one ``jax.profiler.TraceAnnotation`` per phase of its
scheduler tick (``dllama_tpu/observability.py``: ``phase`` / ``tick``), so
the xplane's ``/host:CPU`` plane holds them on the device plane's clock. A
leaf span carries its tick's number as the argument ``tick``; the ``tick``
spans themselves sit on the scheduler thread's line. This lays the leaf
spans over the device's idle gaps (the window, the union of operations and
the gaps are ``trace_reduce``'s, by import) and prints the idle seconds by
phase name, what no span covers as ``unattributed``, and the ten longest
gaps each with its phase and tick. It needs no chip and is not part of a
run: ``reduce()`` still reports ``idle_gaps`` as unattributed, and a later
``benchmark`` PR folds ``attribute`` into it.

A record's host plane here holds ``[name, start_ns, dur_ns, args]`` for the
events that carry arguments (a made-up record in the tests has the same
form); ``trace_reduce.load_xplane`` keeps no arguments, so ``load`` reads
that one plane itself.
"""

from __future__ import annotations

import argparse
import glob
import json
import os

import trace_reduce
from trace_reduce import DEVICE_PLANE, WRAPPERS, _union, op_lines

HOST_PLANE = "/host:CPU"
PARENTS = ("tick", "scheduler_window")


def load(trace_dir: str) -> dict:
    """The device planes as ``trace_reduce.load_xplane`` gives them, and of
    the host plane the events with arguments (the program's spans), line by
    line (lines of one name are kept apart: threads share names)."""
    import jax

    record = trace_reduce.load_xplane(trace_dir)
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = jax.profiler.ProfileData.from_file(files[-1])
    host: dict = {}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            events = []
            for e in line.events:
                args = dict(e.stats)
                if "tick" in args or e.name in PARENTS or e.name == "sse_write":
                    events.append([e.name, float(e.start_ns),
                                   float(e.duration_ns), args])
            if events:
                host[f"{line.name}#{i}"] = events
    record[HOST_PLANE] = host
    return record


def idle_gaps(record: dict) -> tuple:
    """-> (w0, w1, gaps) of the first device plane: ``reduce()``'s window
    (first recorded launch's start to the last one's end, else first to
    last operation) and the gaps its union of operations leaves in it."""
    lines = next(l for p, l in record.items() if DEVICE_PLANE.match(p))
    ops = [(s, s + d) for n, s, d in op_lines(lines)
           if d > 0 and not WRAPPERS.match(n)]
    launches = [(s, s + d) for _, s, d in lines.get("XLA Modules", ()) if d > 0]
    edges = launches or ops
    w0, w1 = min(s for s, _ in edges), max(e for _, e in edges)
    _, merged = _union([(max(s, w0), min(e, w1)) for s, e in ops
                        if e > w0 and s < w1])
    gaps, edge = [], w0
    for s, e in merged:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if edge < w1:
        gaps.append((edge, w1))
    return w0, w1, gaps


def leaf_spans(record: dict) -> list:
    """The program's leaf spans, sorted: (start, end, name, tick), from the
    line(s) that hold the ``tick`` spans (the scheduler thread's)."""
    out = []
    for events in (record.get(HOST_PLANE) or {}).values():
        if not any(e[0] == "tick" for e in events):
            continue
        for name, start, dur, *rest in events:
            args = rest[0] if rest else {}
            if name not in PARENTS and "tick" in args:
                out.append((start, start + dur, name, args["tick"]))
    return sorted(out)


def attribute(record: dict, longest: int = 10) -> dict | None:
    """Idle seconds by phase name, the remainder as ``unattributed``, and
    the longest gaps each with the phase that covers most of it."""
    if not any(DEVICE_PLANE.match(p) for p in record):
        return None
    w0, w1, gaps = idle_gaps(record)
    spans = leaf_spans(record)
    by_phase: dict = {}
    rows = []
    i = 0
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][1] <= g0:
            i += 1
        inside: dict = {}
        j = i
        while j < len(spans) and spans[j][0] < g1:
            s, e, name, tick = spans[j]
            over = min(e, g1) - max(s, g0)
            if over > 0:
                inside[(name, tick)] = inside.get((name, tick), 0.0) + over
            j += 1
        covered = sum(inside.values())
        for (name, _), over in inside.items():
            by_phase[name] = by_phase.get(name, 0.0) + over
        by_phase["unattributed"] = (by_phase.get("unattributed", 0.0)
                                    + max(0.0, (g1 - g0) - covered))
        (name, tick), _ = max(inside.items(), key=lambda kv: kv[1],
                              default=(("unattributed", None), 0.0))
        rows.append({"seconds": (g1 - g0) / 1e9, "at_s": (g0 - w0) / 1e9,
                     "phase": name, "tick": tick,
                     "phases": sorted(
                         ([n, o / 1e9] for (n, _), o in inside.items()),
                         key=lambda r: -r[1])[:4]})
    idle = sum(g1 - g0 for g0, g1 in gaps)
    host_s: dict = {}
    for s, e, name, _ in spans:
        over = min(e, w1) - max(s, w0)
        if over > 0:
            host_s[name] = host_s.get(name, 0.0) + over / 1e9
    ticks = {t for s, e, _, t in spans if e > w0 and s < w1}
    return {
        "window_s": (w1 - w0) / 1e9, "idle_s": idle / 1e9,
        "idle_by_phase": sorted(([n, s / 1e9] for n, s in by_phase.items()),
                                key=lambda r: -r[1]),
        "attributed_share": (1.0 - by_phase.get("unattributed", 0.0) / idle
                             if idle > 0 else None),
        "longest_gaps": sorted(rows, key=lambda r: -r["seconds"])[:longest],
        "gaps": len(gaps), "ticks_in_window": len(ticks),
        # the phases' own seconds inside the window, idle or not
        "host_s_by_phase": sorted(([n, s] for n, s in host_s.items()),
                                  key=lambda r: -r[1]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n", 1)[0])
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--out", default="", help="also write the result here")
    args = ap.parse_args(argv)
    record = load(args.trace_dir)
    result = attribute(record)
    if result is None:
        print("no device plane in the trace: nothing to attribute")
        return 1
    # the same window and gaps as the run's own reduction, or this tool has
    # drifted from it
    reduced = trace_reduce.reduce(record)
    result["reduce_idle_s"] = dict(reduced["idle_gaps"]).get("unattributed")
    print(json.dumps(result, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
