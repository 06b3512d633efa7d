"""The whole step's share (%) of the chip's bf16 peak over the traced window:
FLOPs the model needs for the tokens of the launches the trace counts (the
family's ``flops_per_token``) / (the trace's seconds x chips x peak)."""
from common import traced_work


def read(ctx, args):
    w = traced_work(ctx, args)
    if w is None or ctx.get("peaks") is None:
        return None
    out_tokens = w["decode_steps"] * w["rows"]
    prompt_tokens = w["prefill_pieces"] * w["mean_piece_tokens"]
    # a decoded token attends over its row's context, a prompt token over
    # the part of its prompt before it: half the prompt on average
    flops_per_token, m = ctx["family"].flops_per_token, ctx["model"]
    flops = (out_tokens * flops_per_token(m, w["mean_context"])
             + prompt_tokens * flops_per_token(m, 0.5 * w["mean_prompt"]))
    return 100.0 * flops / (w["seconds"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
