"""Share (%) of a labelled /metrics counter's window delta in its family's."""
from common import promtext


def read(ctx, args):
    whole = promtext.delta(ctx["edge0"], ctx["edge1"], args["family"])
    if whole <= 0:
        return None
    part = promtext.delta(ctx["edge0"], ctx["edge1"], args["family"],
                          args["labels"])
    return 100.0 * part / whole
