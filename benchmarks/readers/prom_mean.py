"""Mean of a /metrics histogram over the window: sum delta / count delta."""
from common import promtext


def read(ctx, args):
    return promtext.hist_mean(ctx["edge0"], ctx["edge1"], args["family"])
