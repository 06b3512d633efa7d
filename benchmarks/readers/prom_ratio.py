"""A ratio of two /metrics series: by default the window's delta of ``num``
over its delta of ``den`` (two counters: a mean per counted event); with
``"at": "end"`` their values at the window's end (gauges: a share of what is
resident). ``num_labels`` / ``den_labels`` choose series by label text ("" for
the whole family), ``scale`` multiplies (100 for a share in %). Nothing to
read from a program without the series, or where ``den`` reads 0."""
from common import promtext


def read(ctx, args):
    a, b = ctx["edge0"], ctx["edge1"]

    def of(name, labels):
        if args.get("at") == "end":
            return promtext.total(b["prom"], name, labels)
        return promtext.delta(a, b, name, labels)

    den = of(args["den"], args.get("den_labels", ""))
    if den <= 0:
        return None
    return (float(args.get("scale", 1.0))
            * of(args["num"], args.get("num_labels", "")) / den)
