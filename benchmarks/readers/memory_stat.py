"""Peak device memory of the serving process after the window, in GB."""


def read(ctx, args):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
