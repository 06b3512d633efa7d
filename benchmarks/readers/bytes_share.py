"""Share (%) of the chip's memory bandwidth that the launches the trace counts
need at the least: the planes every decode step and every prefill piece must
read once (the family's ``plane_bytes_per_launch``), plus the keys and values
a decode step's rows read at the mean context (its ``kv_read_bytes``),
/ (the trace's seconds x chips x bandwidth)."""
from common import traced_work


def read(ctx, args):
    w = traced_work(ctx, args)
    if w is None or ctx.get("peaks") is None:
        return None
    fam, m = ctx["family"], ctx["model"]
    kv = w["rows"] * fam.kv_read_bytes(m, w["mean_context"])
    need = (w["decode_steps"] * (fam.plane_bytes_per_launch(m, w["rows"]) + kv)
            + w["prefill_pieces"] * fam.plane_bytes_per_launch(m, w["mean_piece_tokens"]))
    return 100.0 * need / (w["seconds"] * ctx["chips"]
                           * ctx["peaks"]["hbm_bytes_per_s"])
