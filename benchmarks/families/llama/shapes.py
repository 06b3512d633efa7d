"""Operations and bytes the two models' algorithm needs, from shapes alone.

Every function counts what the mathematics needs, whatever implements it: a
padded plane, an expert read for rows that did not choose it, or a copied
cache is the program's cost and not the roofline's. Sizes are read from a
configuration file's model keys (the names of the models' ``config.json``).

Q40 costs 0.625 bytes a weight: a nibble, and two float32 scales for every 64
input rows of a column (8 bytes / 64).
"""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 0.5 + 8.0 / 64.0


def dims(model: dict) -> dict:
    hd = int(model.get("head_dim")
             or model["hidden_size"] // model["num_attention_heads"])
    return {
        "L": int(model["num_hidden_layers"]), "D": int(model["hidden_size"]),
        "H": int(model["intermediate_size"]),
        "KV": int(model["num_key_value_heads"]) * hd,
        "V": int(model["vocab_size"]),
        "E": int(model.get("num_local_experts", 0)),
        "k": int(model.get("num_experts_per_tok", 0)),
    }


def attn_weights_per_layer(model: dict) -> int:
    d = dims(model)
    return d["D"] * (d["D"] + 2 * d["KV"]) + d["D"] * d["D"]


def ffn_weights_one(model: dict) -> int:
    """One dense FFN, or one expert: up, gate and down."""
    d = dims(model)
    return 3 * d["D"] * d["H"]


def resident_weights_per_layer(model: dict) -> int:
    """Q40 weights a layer holds (all experts of a MoE layer)."""
    d = dims(model)
    return attn_weights_per_layer(model) + max(1, d["E"]) * ffn_weights_one(model)


def active_weights_per_token(model: dict) -> int:
    """Matmul weights one token passes through: attention, its FFN or its k
    experts (and the float32 router), in every layer, and the classifier."""
    d = dims(model)
    per_layer = attn_weights_per_layer(model) + max(1, d["k"]) * ffn_weights_one(model)
    if d["E"]:
        per_layer += d["D"] * d["E"]
    return d["L"] * per_layer + d["D"] * d["V"]


def flops_per_token(model: dict, context: float) -> float:
    """2 x active weights, plus attention over ``context`` live positions:
    q.k and p.v, 2 x D each a position, in every layer."""
    d = dims(model)
    return 2.0 * active_weights_per_token(model) + 4.0 * d["D"] * context * d["L"]


def kv_bytes_per_position(model: dict, cache_bytes: int = 2) -> int:
    """Keys and values of one position, all layers."""
    d = dims(model)
    return 2 * d["L"] * d["KV"] * cache_bytes


def kv_read_bytes(model: dict, context: float, cache_bytes: int = 2) -> float:
    """Keys and values that one row's decode step reads at ``context`` live
    positions, all layers: every layer attends over the whole context."""
    return context * kv_bytes_per_position(model, cache_bytes)


def experts_needed(model: dict, rows: float) -> float:
    """Experts of a layer that ``rows`` token rows need, each choosing k of
    E: 1 for a dense FFN; for sparse experts the expected number of distinct
    experts under even routing, E (1 - (1 - k/E)^rows), and never under k.
    The benchmark's router planes are random, so its rows choose evenly; the
    union the rows really chose is the program's to know, and a launch that
    reads more than it (every expert, say, at 3 rows) pays for it here."""
    d = dims(model)
    if not d["E"]:
        return 1.0
    return max(float(d["k"]),
               d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 1.0)))


def q40_weights_per_launch(model: dict, rows: float) -> float:
    """Q40 weights one forward over ``rows`` token rows must read, each once:
    attention, the experts the rows need, the classifier."""
    d = dims(model)
    per_layer = (attn_weights_per_layer(model)
                 + experts_needed(model, rows) * ffn_weights_one(model))
    return d["L"] * per_layer + d["D"] * d["V"]


def plane_bytes_per_launch(model: dict, rows: float) -> float:
    """The least bytes of planes one forward over ``rows`` token rows must
    read: its Q40 weights and the float32 router."""
    d = dims(model)
    return (q40_weights_per_launch(model, rows) * Q40_BYTES_PER_WEIGHT
            + d["L"] * d["D"] * d["E"] * 4)


def launch_least_seconds(model: dict, rows: float, peaks: dict) -> float:
    """The least time the Q40 matmuls of one forward over ``rows`` rows can
    take on the chip: the larger of bytes over bandwidth (the planes the rows
    need) and FLOPs over the peak (every row through its k experts)."""
    d = dims(model)
    by_bytes = (q40_weights_per_launch(model, rows) * Q40_BYTES_PER_WEIGHT
                / peaks["hbm_bytes_per_s"])
    active = active_weights_per_token(model) - d["L"] * d["D"] * d["E"]
    by_flops = 2.0 * rows * active / peaks["bf16_flops_per_s"]
    return max(by_bytes, by_flops)


def resident_bytes(model: dict) -> float:
    """What the weights hold on the device: Q40 planes and the float32
    embedding, router and norms."""
    d = dims(model)
    q40 = (d["L"] * resident_weights_per_layer(model) + d["D"] * d["V"])
    f32 = d["V"] * d["D"] + d["L"] * d["D"] * d["E"] + (2 * d["L"] + 1) * d["D"]
    return q40 * Q40_BYTES_PER_WEIGHT + 4.0 * f32
