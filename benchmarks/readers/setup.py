"""Seconds from the parent's start to the window's first instant."""


def read(ctx, args):
    return ctx["setup_s"]
