"""What several readers need: the traced interval's launches and tokens.

A reader is a module with ``read(ctx, args)`` that returns a number, or None
where it finds nothing to read. ``ctx`` holds what ``run.py`` gathered: the
scrapes at the window's edges (``edge0``, ``edge1``) and around the trace
(``trace_edges``), the trace's reduction (``trace``), the client's numbers
(``client``, ``results``), the configuration (``model``, ``server``) and its
family (``family``: the counts a share of a peak divides by), the mix, the
peaks of the device and the peak memory. ``run.py`` (and the tests'
``conftest.py``) put ``benchmarks/`` and ``benchmarks/readers/`` on the path.
"""

from __future__ import annotations

import re

import promtext


def launches(trace: dict, pattern: str) -> dict:
    """Launches, seconds and custom-call seconds (all together, and by the
    call's short name) of the traced programs whose name matches ``pattern``."""
    out = {"launches": 0.0, "seconds": 0.0, "custom_call_s": 0.0}
    calls: dict = {}
    for name, m in (trace.get("modules") or {}).items():
        if re.search(pattern, name):
            for k in out:
                out[k] += m[k]
            for call, s in (m.get("custom_calls") or {}).items():
                calls[call] = calls.get(call, 0.0) + s
    return dict(out, custom_calls=calls)


def traced_work(ctx: dict, args: dict):
    """The work of the traced window, or None. Launches and seconds are the
    trace's own, over one interval on the device's clock: decode chunks are
    the launches of the program ``args["decode_module"]`` names (each
    ``batch_chunk`` steps), prefill pieces those of ``args["prefill_module"]``.
    What one launch works on is a ratio of counts: the rows a decode step
    advances are the tokens the clients received between the two scrapes
    around the trace over the steps the server counted between them; a
    piece's tokens and the live context are means over the run's requests."""
    tr = ctx.get("trace")
    if not ctx.get("trace_edges") or not tr or tr.get("window_s", 0) <= 0:
        return None
    dec = launches(tr, args.get("decode_module", r"^jit__decode_loop"))
    pre = launches(tr, args.get("prefill_module", r"^jit__prefill$"))
    if dec["launches"] + pre["launches"] <= 0:
        return None
    a, b = ctx["trace_edges"]
    chunk = int(ctx["server"]["batch_chunk"])
    counted = promtext.delta(a, b, "dllama_decode_chunk_ms_count") * chunk
    received = sum(k for r in ctx["results"] for t, k in r.bursts
                   if a["t"] <= t < b["t"])
    done = [r for r in ctx["results"] if r.ok]
    if not done:
        return None
    rows = received / counted if counted > 0 else 1.0
    piece = int(ctx["server"].get("prefill_chunk", -1))
    if piece < 0:
        piece = chunk * int(ctx["server"]["batch_max"])
    # a prompt of n tokens is prefilled as ceil((n - 1) / piece) pieces (its
    # last token is fed by the first decode step): the mean tokens of a piece
    n_pre = [r.request.prompt_tokens - 1 for r in done]
    mean_piece = sum(n_pre) / sum(-(-n // piece) for n in n_pre)
    mean_ctx = sum(r.request.prompt_tokens + 0.5 * sum(k for _, k in r.bursts)
                   for r in done) / len(done)
    calls = dict(dec["custom_calls"])
    for call, s in pre["custom_calls"].items():
        calls[call] = calls.get(call, 0.0) + s
    return {"decode_steps": dec["launches"] * chunk,
            "prefill_pieces": pre["launches"], "rows": max(rows, 1.0),
            "mean_piece_tokens": mean_piece,
            "mean_prompt": sum(n_pre) / len(n_pre), "mean_context": mean_ctx,
            "custom_call_s": dec["custom_call_s"] + pre["custom_call_s"],
            "custom_calls": calls,
            "seconds": tr["window_s"]}
