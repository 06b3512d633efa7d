"""The ``command_a`` family: Command A+ (``model_type: cohere2_moe``) through
the program's ``layer_plan`` path (``models/layer_plan.py``): a parallel block
(one LayerNorm a layer feeding attention and the FFN side by side), window
layers that rotate interleaved pairs and full layers that rotate nothing,
sigmoid-routed experts without a bias of which this chip holds a share,
always-on shared experts beside them, and a head tied to the embedding, over
the vocabulary's slice.

``shapes.py`` holds the counts (no JAX: ``run.py``'s readers call them),
``weights.py`` the init program, ``reference.py`` the plain reference, which
imports nothing of the program. What touches the program is here.
"""

from __future__ import annotations

import os

from . import shapes
from .shapes import (expert_least_seconds, flops_per_token,  # noqa: F401
                     kv_read_bytes, launch_least_seconds,
                     plane_bytes_per_launch, resident_bytes,
                     shared_least_seconds)


def _program_has_the_block() -> bool:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), *[".."] * 3,
                        "dllama_tpu", "models", "config.py")
    try:
        with open(path) as f:
            return "shared_dim" in f.read()
    except OSError:
        return False


if not _program_has_the_block():
    # a checkout from before the parallel block and the shared experts:
    # ``families.load`` fails in one line, before a child starts (run.py
    # prints it and exits 1)
    raise ValueError(
        "the command_a family needs a program whose ModelConfig has a "
        "parallel block, a LayerNorm and shared experts (block, norm, "
        "shared_dim in dllama_tpu/models/config.py): this checkout has none")


def model_config(model: dict, server: dict):
    from dllama_tpu.models.config import ModelConfig

    d = shapes.dims(model)
    share = model.get("share", {})
    return ModelConfig(
        arch="cohere2_moe", dim=d["D"], hidden_dim=d["He"], n_layers=d["L"],
        n_heads=d["heads"], n_kv_heads=d["kv"], vocab_size=d["V"],
        seq_len=int(model["max_position_embeddings"]), head_size=d["hd"],
        kv_dim=d["kv"] * d["hd"], n_experts=d["E"], n_active_experts=d["k"],
        hidden_act=model.get("hidden_act", "silu"),
        rope_theta=float(model["rope_theta"]),
        rope_style="interleaved",  # position_embedding_type: rope_gptj
        logit_scale=float(model.get("logit_scale", 1.0)),
        norm_eps=float(model["layer_norm_eps"]),
        dtype=server.get("dtype", "bfloat16"),
        layer_plan=shapes.plan(model), n_kv_heads_window=d["kv"],
        rope_theta_window=float(model["rope_theta"]), window=d["window"],
        rope_attention=("window",), router="sigmoid",
        expert_first=int(share.get("expert_first", 0)),
        expert_count=d["Eh"] if d["Eh"] != d["E"] else 0,
        shared_dim=d["Hs"], shared_scale=1.0 / d["Ns"],
        norm="layer", block="parallel",
        tied_embedding=bool(model["tie_word_embeddings"]))


def make_planes(model: dict, seed: int) -> dict:
    from . import weights

    return weights.make_planes(model, seed)


def wrap_planes(planes: dict, model: dict) -> dict:
    """The planes as the program's parameter tree: each {"w","s","s2"}
    becomes a ``QuantTensor`` (a view: no copy)."""
    from dllama_tpu.ops.qmatmul import QuantTensor

    from . import weights

    def leaf(name, v):
        if isinstance(v, dict) and set(v) == {"w", "s", "s2"}:
            return QuantTensor(w=v["w"], s=v["s"], s2=v["s2"], kind="q40",
                               k_logical=weights.logical_k(name, model))
        return v

    out = {k: leaf(k, v) for k, v in planes.items() if k != "layers"}
    out["layers"] = {kind: {k: leaf(k, v) for k, v in stack.items()}
                     for kind, stack in planes["layers"].items()}
    return out


def compare(planes: dict, model: dict, samples: list, stand_ins=()) -> dict:
    from . import reference

    modes = {"control": reference.CONTROL, "witness": reference.WITNESS}
    return reference.compare(planes, model, samples,
                             stand_ins={n: modes[n] for n in stand_ins})


def rehearsal(conf: dict) -> list:
    """The init program, the program's ``forward`` at T = 1 and at a prefill
    piece, ``forward_batched`` at the pool's rows and slab (with the live
    mask, as the pooled decode program calls it), and the reference's layer
    of either kind and its head at the comparison's sizes."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models import llama
    from dllama_tpu.ops import qmatmul

    from . import reference, weights

    qmatmul._interpret_default = lambda: False  # compile the real kernels
    shape = jax.ShapeDtypeStruct
    d = shapes.dims(conf)
    out = [("init program", jax.jit(weights._init, static_argnames="dims"),
            (shape((2,), jnp.uint32),), {"dims": weights.dims_of(conf)})]

    planes = weights.planes_shape(conf)
    cfg = model_config(conf, conf["server"])
    params = wrap_planes(planes, conf)
    rope = jax.eval_shape(lambda: llama.rope_tables(cfg))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, jnp.bfloat16))
    for t in (1, 64):
        fn = jax.jit(lambda p, r, tk, c, ps: llama.forward(cfg, p, r, tk, c, ps),
                     donate_argnums=3)
        out.append((f"forward T={t}", fn,
                    (params, rope, shape((t,), jnp.int32), cache,
                     shape((), jnp.int32)), {}))
    rows, slab = int(conf["server"]["batch_max"]), int(conf["server"]["kv_bucket_min"])
    bcache = jax.eval_shape(
        lambda: llama.init_batch_cache(cfg, rows, jnp.bfloat16, seq_len=slab))
    toks = shape((rows,), jnp.int32)
    fn = jax.jit(lambda p, r, tk, c, ps, lv: llama.forward_batched(
        cfg, p, r, tk, c, ps, live=lv), donate_argnums=3)
    out.append((f"forward_batched B={rows} slab={slab}", fn,
                (params, rope, toks, bcache, toks, shape((rows,), jnp.bool_)),
                {}))

    m = reference.sizes(conf)
    n, t_pad = 2, 768
    x = shape((n, t_pad, d["D"]), jnp.float32)
    cs = shape((t_pad, d["hd"] // 2), jnp.float32)
    for kind in shapes.kinds(conf):
        for lower in (None, reference.CONTROL):
            out.append((f"reference layer {kind} N={n} T={t_pad} lower={lower}",
                        reference._layer,
                        (x, planes["layers"][f"{kind[0]}_{kind[1]}"],
                         shape((), jnp.int32), cs, cs),
                        {"m": m, "kind": kind, "lower": lower}))
    out.append(("reference head R=256", reference._head,
                (shape((t_pad, d["D"]), jnp.float32), shape((256,), jnp.int32),
                 planes["rms_final"], shape((d["D"], d["V"]), jnp.float32)),
                {"eps": dict(m)["eps"], "lower": None}))
    return out
