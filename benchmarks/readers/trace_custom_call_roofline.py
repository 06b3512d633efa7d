"""Roofline share (%) of the q40 matmul kernels over the traced window: the
least time the chip could take for the q40 calls of the launches the trace
counts (``shapes.launch_least_seconds``) / the device time of the Pallas
custom calls inside those same launches. Nothing to read where they hold no
custom call."""
from common import shapes, traced_work


def read(ctx, args):
    w = traced_work(ctx, args)
    if w is None or ctx.get("peaks") is None or w["custom_call_s"] <= 0.0:
        return None
    m, p = ctx["model"], ctx["peaks"]
    least = (w["decode_steps"] * shapes.launch_least_seconds(m, w["rows"], p)
             + w["prefill_pieces"] * shapes.launch_least_seconds(m, w["mean_piece_tokens"], p))
    return 100.0 * least / w["custom_call_s"]
