"""Operations and bytes the MiMo-V2 layer plan needs, from shapes alone.

Every function counts the least work of the mathematics for THIS chip's share
of the model (the layers, the held experts and the vocabulary's slice that the
configuration file states), whatever implements it: an expert read for rows
that did not choose it, a window layer that scores its whole ring, or a padded
plane is the program's cost and not the roofline's. No JAX here: ``run.py``'s
readers call these.

Q40 costs 0.625 bytes a weight (a nibble, two float32 scales for 64 rows).
"""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 0.5 + 8.0 / 64.0


def dims(model: dict) -> dict:
    """The sizes, under short names. ``E`` is the experts the router scores
    (the published count), ``Eh`` those held here."""
    pub = model.get("published", {})
    return {
        "L": int(model["num_hidden_layers"]), "D": int(model["hidden_size"]),
        "heads": int(model["num_attention_heads"]),
        "hd": int(model["head_dim"]), "vd": int(model["v_head_dim"]),
        "kv_full": int(model["num_key_value_heads"]),
        "kv_window": int(model["swa_num_key_value_heads"]),
        "Hd": int(model["intermediate_size"]),
        "He": int(model["moe_intermediate_size"]),
        "E": int(pub.get("n_routed_experts", model["n_routed_experts"])),
        "Eh": int(model["n_routed_experts"]),
        "k": int(model["num_experts_per_tok"]),
        "V": int(model["vocab_size"]),
        "window": int(model["sliding_window"]),
        "rd": rotary_dims(model),
    }


def rotary_dims(model: dict) -> int:
    """``int(head_dim * partial_rotary_factor)``, made even: 64 of 192."""
    rd = int(int(model["head_dim"]) * float(model["partial_rotary_factor"]))
    return rd - rd % 2


def plan(model: dict) -> tuple:
    """One (attention kind, FFN kind) a layer held here: the first
    ``num_hidden_layers`` entries of the published patterns."""
    n = int(model["num_hidden_layers"])
    return tuple(("window" if a else "full", "moe" if f else "dense")
                 for a, f in zip(model["hybrid_layer_pattern"][:n],
                                 model["moe_layer_freq"][:n]))


def kinds(model: dict) -> dict:
    """{(attention, FFN): layers of that kind}, in order of first use."""
    out: dict = {}
    for kind in plan(model):
        out[kind] = out.get(kind, 0) + 1
    return out


def qkv_width(model: dict, attention: str) -> int:
    d = dims(model)
    kv = d["kv_window"] if attention == "window" else d["kv_full"]
    return d["heads"] * d["hd"] + kv * (d["hd"] + d["vd"])


def attn_weights(model: dict, attention: str) -> int:
    d = dims(model)
    return d["D"] * qkv_width(model, attention) + d["heads"] * d["vd"] * d["D"]


def dense_weights(model: dict) -> int:
    d = dims(model)
    return 3 * d["D"] * d["Hd"]


def expert_weights(model: dict) -> int:
    """One expert: up, gate and down."""
    d = dims(model)
    return 3 * d["D"] * d["He"]


def held_picks_per_token(model: dict) -> float:
    """Picks of one token that fall on held experts, under even routing."""
    d = dims(model)
    return d["k"] * d["Eh"] / d["E"]


def experts_needed(model: dict, rows: float) -> float:
    """Held experts of a layer that the picks of ``rows`` token rows reach,
    each choosing k of E evenly: ``Eh (1 - (1 - k/E)^rows)``, 7.2 of 32 at 8
    rows. The benchmark's router planes are random, so its rows choose
    evenly in expectation; what the rows really chose the program counts
    (``dllama_moe_active_experts_total``)."""
    d = dims(model)
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 1.0))


def _weights(model: dict, experts: float, experts_only: bool = False) -> float:
    """Q40 weights of all layers and the classifier, an expert layer
    counting ``experts`` of its experts; ``experts_only``: those alone."""
    total = 0.0
    for (att, ffn), n in kinds(model).items():
        per = 0.0 if experts_only else attn_weights(model, att)
        if ffn == "moe":
            per += experts * expert_weights(model)
        elif not experts_only:
            per += dense_weights(model)
        total += n * per
    d = dims(model)
    return total if experts_only else total + d["D"] * d["V"]


def _q40_weights(model: dict, rows: float, experts_only: bool = False) -> float:
    """Q40 weights one forward over ``rows`` rows must read, each once."""
    return _weights(model, experts_needed(model, rows), experts_only)


def _active_weights(model: dict, experts_only: bool = False) -> float:
    """Matmul weights one token passes through on this chip."""
    return _weights(model, held_picks_per_token(model), experts_only)


def _router_weights(model: dict) -> int:
    d = dims(model)
    return sum(n for (_, ffn), n in kinds(model).items()
               if ffn == "moe") * d["D"] * d["E"]


def flops_per_token(model: dict, context: float) -> float:
    """2 x the weights a token passes through (the float32 router with them),
    plus attention: q.k over ``head_dim`` and p.v over ``v_head_dim`` for
    every head and live position, a window layer's positions at most the
    window."""
    d = dims(model)
    att = 0.0
    for (kind, _), n in kinds(model).items():
        seen = min(context, d["window"]) if kind == "window" else context
        att += n * 2.0 * d["heads"] * (d["hd"] + d["vd"]) * seen
    return 2.0 * (_active_weights(model) + _router_weights(model)) + att


def kv_read_bytes(model: dict, context: float, cache_bytes: int = 2) -> float:
    """Keys and values one row's decode step reads at ``context`` live
    positions: full layers the context, window layers the window."""
    d = dims(model)
    total = 0.0
    for (kind, _), n in kinds(model).items():
        if kind == "window":
            total += n * min(context, d["window"]) * d["kv_window"]
        else:
            total += n * context * d["kv_full"]
    return total * (d["hd"] + d["vd"]) * cache_bytes


def plane_bytes_per_launch(model: dict, rows: float) -> float:
    """The least bytes of planes one forward over ``rows`` rows must read:
    its Q40 weights (of the experts, those the rows' picks reach) and the
    float32 routers."""
    return (_q40_weights(model, rows) * Q40_BYTES_PER_WEIGHT
            + 4.0 * _router_weights(model))


def _least_seconds(model: dict, rows: float, peaks: dict,
                   experts_only: bool) -> float:
    by_bytes = (_q40_weights(model, rows, experts_only) * Q40_BYTES_PER_WEIGHT
                / peaks["hbm_bytes_per_s"])
    by_flops = (2.0 * rows * _active_weights(model, experts_only)
                / peaks["bf16_flops_per_s"])
    return max(by_bytes, by_flops)


def launch_least_seconds(model: dict, rows: float, peaks: dict) -> float:
    """The least time all Q40 matmuls of one forward over ``rows`` rows can
    take: the larger of bytes over bandwidth and FLOPs over the peak."""
    return _least_seconds(model, rows, peaks, experts_only=False)


def expert_least_seconds(model: dict, rows: float, peaks: dict) -> float:
    """The same for the expert kernels alone (``expert_*`` custom calls)."""
    return _least_seconds(model, rows, peaks, experts_only=True)


def resident_bytes(model: dict) -> float:
    """What the weights hold on the device: Q40 planes (every held expert)
    and the float32 embedding, routers, correction biases, sinks, norms."""
    d = dims(model)
    q40, f32 = d["D"] * d["V"], d["V"] * d["D"] + d["D"]
    for (att, ffn), n in kinds(model).items():
        per = attn_weights(model, att)
        per += d["Eh"] * expert_weights(model) if ffn == "moe" else dense_weights(model)
        q40 += n * per
        f32 += n * (2 * d["D"] + (d["heads"] if att == "window" else 0)
                    + ((d["D"] + 1) * d["E"] if ffn == "moe" else 0))
    return q40 * Q40_BYTES_PER_WEIGHT + 4.0 * f32


def kv_resident_bytes(model: dict, rows: int, slab: int, ring: int,
                      cache_bytes: int = 2) -> dict:
    """Bytes a pool of ``rows`` rows holds by attention kind: full layers a
    slab of ``slab`` positions a row, window layers a ring of ``ring``."""
    d = dims(model)
    out = {"full": 0.0, "window": 0.0}
    for (kind, _), n in kinds(model).items():
        slots, kv = ((ring, d["kv_window"]) if kind == "window"
                     else (slab, d["kv_full"]))
        out[kind] += n * rows * slots * kv * (d["hd"] + d["vd"]) * cache_bytes
    return out
