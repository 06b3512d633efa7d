"""Share (%) of the chip's memory bandwidth that the launches the trace counts
need at the least: the planes every decode step and every prefill piece must
read once (``shapes.plane_bytes_per_launch``), plus the live keys and values
a decode step attends over, / (the trace's seconds x chips x bandwidth)."""
from common import shapes, traced_work


def read(ctx, args):
    w = traced_work(ctx, args)
    if w is None or ctx.get("peaks") is None:
        return None
    m = ctx["model"]
    kv = w["rows"] * w["mean_context"] * shapes.kv_bytes_per_position(m)
    need = (w["decode_steps"] * (shapes.plane_bytes_per_launch(m, w["rows"]) + kv)
            + w["prefill_pieces"] * shapes.plane_bytes_per_launch(m, w["mean_piece_tokens"]))
    return 100.0 * need / (w["seconds"] * ctx["chips"]
                           * ctx["peaks"]["hbm_bytes_per_s"])
