"""Milliseconds a tick the scheduler thread spent off the CPU inside phases
of its tick, over the window: 1000 x (the delta of
``dllama_tick_phase_seconds_total`` minus the delta of
``dllama_tick_phase_cpu_seconds_total``, over the series whose label text
contains ``args["labels"]``) / the delta of the counter series
``args["per"]``. Off the CPU is waiting: for the interpreter lock, another
lock or a core. Nothing to read from a program without the CPU family, or
where ``per`` did not move."""
from common import promtext

WALL = "dllama_tick_phase_seconds_total"
CPU = "dllama_tick_phase_cpu_seconds_total"


def read(ctx, args):
    a, b = ctx["edge0"], ctx["edge1"]
    if not any(name == CPU for name, _, _ in b["prom"]):
        return None
    n = promtext.delta(a, b, args["per"])
    if n <= 0:
        return None
    labels = args.get("labels", "")
    return 1000.0 * (promtext.delta(a, b, WALL, labels)
                     - promtext.delta(a, b, CPU, labels)) / n
