"""Operations and bytes the Command A+ layer plan needs, from shapes alone.

Every function counts the least work of the mathematics for THIS chip's share
of the model (the layers, the held experts and the vocabulary's slice that the
configuration file states), whatever implements it: live positions and not
ring slots, the held experts the rows' picks reach and not all sixteen, a
plane once however many rows multiply it. No JAX here: ``run.py``'s readers
call these.

A layer here: attention (q for 128 heads, k and v for 8, the output), four
always-on shared experts held as one gated FFN of their summed width, a
float32 router over ALL published experts, and the held routed experts. Every
layer is an expert layer; window layers read at most the window's keys.

Q40 costs 0.625 bytes a weight (a nibble, two float32 scales for 64 rows).
"""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 0.5 + 8.0 / 64.0


def dims(model: dict) -> dict:
    """The sizes, under short names. ``E`` is the experts the router scores
    (the published count), ``Eh`` those held here; ``Hs`` the shared experts'
    summed width."""
    pub = model.get("published", {})
    he, ns = int(model["intermediate_size"]), int(model["num_shared_experts"])
    return {
        "L": int(model["num_hidden_layers"]), "D": int(model["hidden_size"]),
        "heads": int(model["num_attention_heads"]),
        "kv": int(model["num_key_value_heads"]), "hd": int(model["head_dim"]),
        "He": he, "Ns": ns, "Hs": ns * he,
        "E": int(pub.get("num_experts", model["num_experts"])),
        "Eh": int(model["num_experts"]),
        "k": int(model["num_experts_per_tok"]),
        "V": int(model["vocab_size"]),
        "window": int(model["sliding_window"]),
    }


def plan(model: dict) -> tuple:
    """One (attention kind, FFN kind) a layer held here: the first
    ``num_hidden_layers`` entries of ``layer_types``; every FFN routes."""
    n = int(model["num_hidden_layers"])
    return tuple(("window" if t == "sliding_attention" else "full", "moe")
                 for t in model["layer_types"][:n])


def kinds(model: dict) -> dict:
    """{(attention, FFN): layers of that kind}, in order of first use."""
    out: dict = {}
    for kind in plan(model):
        out[kind] = out.get(kind, 0) + 1
    return out


def qkv_width(model: dict) -> int:
    d = dims(model)
    return (d["heads"] + 2 * d["kv"]) * d["hd"]


def attn_weights(model: dict) -> int:
    """q | k | v and the output, either attention kind: 142.6 M."""
    d = dims(model)
    return d["D"] * qkv_width(model) + d["heads"] * d["hd"] * d["D"]


def shared_weights(model: dict) -> int:
    """The always-on experts, up, gate and down: 201.3 M."""
    d = dims(model)
    return 3 * d["D"] * d["Hs"]


def expert_weights(model: dict) -> int:
    """One routed expert, up, gate and down: 50.3 M."""
    d = dims(model)
    return 3 * d["D"] * d["He"]


def always_on_weights(model: dict) -> int:
    """What every step of a layer reads whatever the rows picked: 343.9 M,
    215 MB of q40."""
    return attn_weights(model) + shared_weights(model)


def held_picks_per_token(model: dict) -> float:
    """Picks of one token that fall on held experts, under even routing."""
    d = dims(model)
    return d["k"] * d["Eh"] / d["E"]


def experts_needed(model: dict, rows: float, reads: float = None) -> float:
    """Held experts of a layer whose planes a step over ``rows`` token rows
    must read. ``reads``: what the program counted a layer-step
    (``dllama_moe_expert_reads_total`` over ``dllama_moe_layer_steps_total``,
    for a caller that has them): the experts the rows' picks reached. Without
    it, what even routing reaches in expectation, each row choosing k of E:
    ``Eh (1 - (1 - k/E)^rows)``, 4.5 of 16 at 5.15 rows; the rows' picks
    overlap, so this is an upper estimate of the least (PERF.md, Open
    question 14)."""
    d = dims(model)
    if reads is not None:
        return min(float(reads), float(d["Eh"]))
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 1.0))


def _layers(model: dict) -> int:
    return sum(kinds(model).values())


def _weights(model: dict, experts: float, part: str = "all") -> float:
    """Q40 weights of all layers and the head, a layer counting ``experts``
    of its routed experts; ``part`` "shared" / "experts": those alone."""
    if part == "shared":
        return _layers(model) * shared_weights(model)
    routed = _layers(model) * experts * expert_weights(model)
    if part == "experts":
        return routed
    d = dims(model)
    return (_layers(model) * always_on_weights(model) + routed
            + d["D"] * d["V"])


def _router_weights(model: dict) -> int:
    d = dims(model)
    return _layers(model) * d["D"] * d["E"]


def flops_per_token(model: dict, context: float) -> float:
    """2 x the weights a token passes through (the float32 router with
    them), plus attention: q.k and p.v over ``head_dim`` for every head and
    live position, a window layer's positions at most the window."""
    d = dims(model)
    att = 0.0
    for (kind, _), n in kinds(model).items():
        seen = min(context, d["window"]) if kind == "window" else context
        att += n * 2.0 * d["heads"] * 2 * d["hd"] * seen
    active = _weights(model, held_picks_per_token(model))
    return 2.0 * (active + _router_weights(model)) + att


def kv_read_bytes(model: dict, context: float, cache_bytes: int = 2) -> float:
    """Keys and values one row's decode step reads at ``context`` live
    positions: 4 KB a position a layer; full layers the context, window
    layers ``min(context, window)``."""
    d = dims(model)
    seen = sum(n * (min(context, d["window"]) if kind == "window" else context)
               for (kind, _), n in kinds(model).items())
    return seen * d["kv"] * 2 * d["hd"] * cache_bytes


def plane_bytes_per_launch(model: dict, rows: float,
                           reads: float = None) -> float:
    """The least bytes of planes one forward over ``rows`` rows must read:
    its Q40 weights (of the routed experts, those the rows' picks reach) and
    the float32 routers."""
    return (_weights(model, experts_needed(model, rows, reads))
            * Q40_BYTES_PER_WEIGHT + 4.0 * _router_weights(model))


def _least_seconds(model: dict, rows: float, peaks: dict, part: str,
                   reads: float = None) -> float:
    by_bytes = (_weights(model, experts_needed(model, rows, reads), part)
                * Q40_BYTES_PER_WEIGHT / peaks["hbm_bytes_per_s"])
    by_flops = (2.0 * rows * _weights(model, held_picks_per_token(model), part)
                / peaks["bf16_flops_per_s"])
    return max(by_bytes, by_flops)


def launch_least_seconds(model: dict, rows: float, peaks: dict,
                         reads: float = None) -> float:
    """The least time all Q40 matmuls of one forward over ``rows`` rows can
    take: the larger of bytes over bandwidth and FLOPs over the peak."""
    return _least_seconds(model, rows, peaks, "all", reads)


def shared_least_seconds(model: dict, rows: float, peaks: dict) -> float:
    """The same for the always-on experts' kernels alone (``shared_*``
    custom calls): 201.3 M x 0.625 B a layer over the bandwidth."""
    return _least_seconds(model, rows, peaks, "shared")


def expert_least_seconds(model: dict, rows: float, peaks: dict,
                         reads: float = None) -> float:
    """The same for the routed experts' kernels alone (``expert_*``)."""
    return _least_seconds(model, rows, peaks, "experts", reads)


def resident_bytes(model: dict) -> float:
    """What the weights hold on the device: Q40 planes (every held expert;
    the tied table's planes once) and, in float32, the lookup table, the
    routers and the norms."""
    d = dims(model)
    n = _layers(model)
    q40 = d["D"] * d["V"] + n * (always_on_weights(model)
                                 + d["Eh"] * expert_weights(model))
    f32 = d["V"] * d["D"] + d["D"] + n * (d["D"] + d["D"] * d["E"])
    return q40 * Q40_BYTES_PER_WEIGHT + 4.0 * f32


def kv_resident_bytes(model: dict, rows: int, slab: int, ring: int,
                      cache_bytes: int = 2) -> dict:
    """Bytes a pool of ``rows`` rows holds by attention kind: full layers a
    slab of ``slab`` positions a row, window layers a ring of ``ring``."""
    d = dims(model)
    out = {"full": 0.0, "window": 0.0}
    for (kind, _), n in kinds(model).items():
        slots = ring if kind == "window" else slab
        out[kind] += n * rows * slots * d["kv"] * 2 * d["hd"] * cache_bytes
    return out
