"""A /metrics counter per counted event over the window: ``scale`` (default
1) x the delta of the family ``num`` (all its series) / the delta of the
counter series ``per``. Nothing to read from a program without ``num``'s
family, or where ``per`` did not move."""
from common import promtext


def read(ctx, args):
    a, b = ctx["edge0"], ctx["edge1"]
    if not any(name == args["num"] for name, _, _ in b["prom"]):
        return None
    n = promtext.delta(a, b, args["per"])
    if n <= 0:
        return None
    return float(args.get("scale", 1.0)) * promtext.delta(a, b, args["num"]) / n
