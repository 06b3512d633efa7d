"""From a profiler trace to numbers: busy time, operations, idle gaps.

The process that holds the chip records a few seconds of the window with
``jax.profiler`` and reduces the ``.xplane.pb`` itself. The reduction works on
a plain record of the trace ({plane: {line: [[name, start_ns, dur_ns], ...]}}),
so that a small recorded trace kept with the tests exercises the same code.

What a v5e trace looks like (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Ops`` holding one event per executed
HLO operation (1.46 million in 4 s of cell 1), named by its whole HLO line, a
line ``XLA Modules`` with one event per program launch, and ``Async XLA Ops``
whose copy spans last as long as the loop around them; host threads are lines
of the plane ``/host:CPU``. Busy time is the union of the ``XLA Ops``
intervals, without the ``while`` / ``conditional`` / ``call`` operations that
only bracket their bodies: a gap between two operations inside a fused decode
loop is time in which nothing ran, and counts as idle.

The window is the trace's own: from the start of the first recorded program
launch to the end of the last (``XLA Modules``), so that a launch cut by the
profiler's start or stop is left out, with its operations. Launches, busy
time and the time in custom calls are all counted over that one interval, on
the device's clock: a reader divides the work of the launches it counts here
by the seconds it reads here, and never by a host interval.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
BRACKET_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "Async XLA Ops",
                 "TC Overlay")
#: operations that only bracket the operations of their body
WRAPPERS = re.compile(r"^%?(while|conditional|call)[.\d]*( |$)")
_TRAILING = re.compile(r"[.\d]+$")


def short_name(event_name: str) -> str:
    """An event of ``XLA Ops`` is named by its whole HLO line,
    ``%q40_matmul_stacked.44 = f32[8,28672]{...} custom-call(...)``: keep the
    instruction's name without its number, and say where it is a custom call
    (every Pallas kernel is one: ``custom_call_target="tpu_custom_call"``)."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base = _TRAILING.sub("", head) or head
    if "custom-call(" in event_name or "custom_call_target" in event_name:
        base += " [custom-call]"
    return base


def load_xplane(trace_dir: str, keep_host: bool = False) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a plain record."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    record: dict = {}
    for plane in data.planes:
        if not (keep_host or DEVICE_PLANE.match(plane.name)):
            continue
        lines: dict = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events)
        record[plane.name] = lines
    return record


def _union(intervals: list) -> tuple:
    """(summed length, merged intervals) of [start, end) pairs."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_lines(lines: dict) -> list:
    """The events that are device operations, of one device plane."""
    if "XLA Ops" in lines:
        return list(lines["XLA Ops"])
    out: list = []
    for name, events in lines.items():
        if name not in BRACKET_LINES:
            out.extend(events)
    return out


def _module_name(event_name: str) -> str:
    """``jit__decode_loop_batch(9087109335148933121)`` without its hash."""
    return event_name.split("(", 1)[0]


def reduce(record: dict,
           custom_call: str = r"custom-call|custom_call|pallas|mosaic") -> dict | None:
    """Busy seconds (mean over the chips), the window, the heaviest
    operations, the longest idle gaps, and per program (``modules``) its
    launches, their seconds and the seconds of custom calls inside them:
    all together (``custom_call_s``) and each by its short name
    (``custom_calls``), so that a kernel can have a share of its own.

    The window runs from the first recorded launch's start to the last
    one's end, or, in a trace without ``XLA Modules``, from the first to the
    last device operation. Returns None for a trace without a device plane
    or without operations: there is then nothing to read."""
    planes = {p: l for p, l in record.items() if DEVICE_PLANE.match(p)}
    if not planes:
        return None
    per_chip = [[(s, s + d, n) for n, s, d in op_lines(lines)
                 if d > 0 and not WRAPPERS.match(n)]
                for lines in planes.values()]
    launches = [sorted((s, s + d, _module_name(n))
                       for n, s, d in lines.get("XLA Modules", ()) if d > 0)
                for lines in planes.values()]
    every = [op for ops in per_chip for op in ops]
    if not every:
        return None
    if any(launches):
        w0 = min(ls[0][0] for ls in launches if ls)
        w1 = max(e for ls in launches for _, e, _ in ls)
    else:
        w0, w1 = min(s for s, _, _ in every), max(e for _, e, _ in every)
    pat = re.compile(custom_call, re.I)
    busy_all, by_name, custom_s, gaps, modules = [], {}, 0.0, [], {}
    for ops, ls in zip(per_chip, launches):
        whole = [(s, e, n) for s, e, n in ls if s >= w0 and e <= w1]
        starts = [s for s, _, _ in whole]
        for s, e, n in whole:
            m = modules.setdefault(n, {"launches": 0, "seconds": 0.0,
                                       "custom_call_s": 0.0,
                                       "custom_calls": {}})
            m["launches"] += 1
            m["seconds"] += (e - s) / 1e9
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                   if e > w0 and s < w1]
        busy, merged = _union([(s, e) for s, e, _ in clipped])
        busy_all.append(busy)
        for s, e, n in clipped:
            key = short_name(n)
            by_name[key] = by_name.get(key, 0.0) + (e - s)
            if pat.search(n):
                custom_s += e - s
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < whole[i][1]:
                    m, took = modules[whole[i][2]], (e - s) / 1e9
                    m["custom_call_s"] += took
                    m["custom_calls"][key] = m["custom_calls"].get(key, 0.0) + took
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if edge < w1:
            gaps.append((edge, w1))
    n_chips = len(per_chip)
    for m in modules.values():  # a program over several chips: their mean
        m["launches"] /= n_chips
        m["seconds"] /= n_chips
        m["custom_call_s"] /= n_chips
        m["custom_calls"] = {k: v / n_chips
                             for k, v in m["custom_calls"].items()}
    idle = sum(g1 - g0 for g0, g1 in gaps)
    return {
        "busy_s": sum(busy_all) / n_chips / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "chips": n_chips,
        "custom_call_s": custom_s / n_chips / 1e9,
        "modules": modules,
        "device_ops": [[n, d / n_chips / 1e9] for n, d in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:10]],
        # the program writes no host spans into the profiler yet, so no gap
        # can be named by what the host was doing
        "idle_gaps": ([["unattributed", idle / n_chips / 1e9],
                       ["longest single gap",
                        max(g1 - g0 for g0, g1 in gaps) / 1e9]]
                      if gaps else []),
        "n_ops": sum(len(o) for o in per_chip),
    }
