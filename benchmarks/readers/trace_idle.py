"""Idle share (%) of the device: 1 - union of operations / traced window."""


def read(ctx, args):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
