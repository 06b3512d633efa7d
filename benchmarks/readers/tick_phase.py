"""Milliseconds the scheduler thread spent in phases of its tick, per tick
(or per another counted event), over the window: 1000 x the delta of
``dllama_tick_phase_seconds_total`` over the series whose label text contains
``args["labels"]`` ("" for all; the family's label order is phase, layer,
side, so ``layer="engine",side="host"`` names neighbours) / the delta of the
counter series ``args["per"]``. Nothing to read from a program without the
family, or where ``per`` did not move."""
from common import promtext

FAMILY = "dllama_tick_phase_seconds_total"


def read(ctx, args):
    a, b = ctx["edge0"], ctx["edge1"]
    if not any(name == FAMILY for name, _, _ in b["prom"]):
        return None
    n = promtext.delta(a, b, args["per"])
    if n <= 0:
        return None
    return 1000.0 * promtext.delta(a, b, FAMILY, args.get("labels", "")) / n
