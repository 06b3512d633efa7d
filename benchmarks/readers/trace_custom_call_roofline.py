"""Roofline share (%) of custom-call kernels over the traced window: the
least time the chip could take for those kernels' work in the launches the
trace counts / the device time of their custom calls inside those same
launches. ``args["least"]`` names the family's function that gives the least
seconds of one launch over so many rows (absent: ``launch_least_seconds``,
the q40 matmuls); ``args["names"]`` is a pattern over the calls' short names,
as ``breakdown.device_ops`` prints them (absent: every custom call, which is
right while one kind of kernel runs in a launch). Nothing to read where the
launches hold no such call."""
import re

from common import traced_work


def read(ctx, args):
    w = traced_work(ctx, args)
    if w is None or ctx.get("peaks") is None:
        return None
    if "names" in args:
        spent = sum(s for call, s in w["custom_calls"].items()
                    if re.search(args["names"], call))
    else:
        spent = w["custom_call_s"]
    if spent <= 0.0:
        return None
    least_s = getattr(ctx["family"], args.get("least", "launch_least_seconds"))
    m, p = ctx["model"], ctx["peaks"]
    least = (w["decode_steps"] * least_s(m, w["rows"], p)
             + w["prefill_pieces"] * least_s(m, w["mean_piece_tokens"], p))
    return 100.0 * least / spent
