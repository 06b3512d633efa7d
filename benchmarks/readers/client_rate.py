"""A count of the client's over the window, per second of the window."""


def read(ctx, args):
    return ctx["client"][args["count"]] / ctx["client"]["seconds"]
