"""A statistic of the client's own timings over the window's requests."""
import loadgen


def read(ctx, args):
    values = ctx["client"].get(args["series"]) or []
    if not values:
        return None
    return loadgen.pct(values, float(args["quantile"]))
