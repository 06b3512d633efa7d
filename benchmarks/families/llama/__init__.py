"""The ``llama`` family: decoder-only transformers through the program's
``arch: llama`` / ``arch: mixtral`` paths (uniform layers, grouped-query
attention, one rotary table, a dense FFN or softmax top-k experts).

``shapes.py`` holds the counts (no JAX: ``run.py``'s readers call them),
``weights.py`` the init program, ``reference.py`` the plain reference, which
imports nothing of the program. What touches the program is here: the
configuration's keys as a ``ModelConfig``, the planes as ``QuantTensor``s and
back, the sharded init, and the programs of the compile rehearsal.
"""

from __future__ import annotations

from . import shapes
from .shapes import (flops_per_token, kv_read_bytes,  # noqa: F401
                     launch_least_seconds, plane_bytes_per_launch,
                     resident_bytes)


def model_config(model: dict, server: dict):
    from dllama_tpu.models.config import ModelConfig

    hd = int(model.get("head_dim")
             or model["hidden_size"] // model["num_attention_heads"])
    return ModelConfig(
        arch=model["arch"], dim=int(model["hidden_size"]),
        hidden_dim=int(model["intermediate_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        vocab_size=int(model["vocab_size"]),
        seq_len=int(model["max_position_embeddings"]),
        head_size=hd, kv_dim=int(model["num_key_value_heads"]) * hd,
        n_experts=int(model.get("num_local_experts", 0)),
        n_active_experts=int(model.get("num_experts_per_tok", 0)),
        hidden_act=model.get("hidden_act", "silu"),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=server.get("dtype", "bfloat16"))


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
    out["layers"] = {k: leaf(k, v) for k, v in planes["layers"].items()}
    return out


def make_sharded_params(model: dict, cfg, n_tp: int, seed: int):
    """``tp > 1``: the planes in the unfused layout, lane-aligned as
    ``quant_tp.prepare_quant_params`` aligns them, made by one jitted program
    under ``out_shardings`` from ``quant_tp.quant_param_specs``: no device
    ever holds a whole matrix (a whole Mixtral cannot be made on one device
    first). -> (params, mesh)"""
    import jax
    from jax.sharding import NamedSharding

    from dllama_tpu.parallel import quant_tp
    from dllama_tpu.parallel.mesh import tp_mesh

    from . import weights

    mesh = tp_mesh(n_tp)
    dims = weights.dims_of(model)

    def init(key):
        planes = weights._init(key, dims, fused=False)
        return quant_tp.prepare_quant_params(wrap_planes(planes, model), cfg, n_tp)

    key = weights.seed_key(seed)
    specs = quant_tp.quant_param_specs(jax.eval_shape(init, key), cfg, n_tp)
    shardings = jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs)
    return jax.jit(init, out_shardings=shardings)(key), mesh


def planes_of(params: dict, model: dict) -> dict:
    """The sharded parameter tree back as the planes the reference reads: at
    their logical widths (the padding that lane alignment added is cut off)
    and in the fused layout."""
    from dllama_tpu.ops.qmatmul import QuantTensor

    from . import weights

    d = shapes.dims(model)
    width = {"wq": d["D"], "wk": d["KV"], "wv": d["KV"], "wo": d["D"],
             "w1": d["H"], "w3": d["H"], "w2": d["D"], "moe_up": d["H"],
             "moe_gate": d["H"], "moe_down": d["D"], "wcls": d["V"]}

    def leaf(name, v):
        if not isinstance(v, QuantTensor):
            return v
        kp = weights._pad_up(weights.logical_k(name, model),
                             weights.Q40_K_MULTIPLE)
        o = width[name]
        return {"w": v.w[..., :kp // 2, :o], "s": v.s[..., :kp // 64, :o],
                "s2": v.s2[..., :kp // 64, :o]}

    out = {k: leaf(k, v) for k, v in params.items() if k != "layers"}
    out["layers"] = {k: leaf(k, v) for k, v in params["layers"].items()}
    return weights.fuse_planes(out)


def compare(planes: dict, model: dict, samples: list, stand_ins=()) -> dict:
    from . import reference

    modes = {"control": reference.CONTROL, "witness": reference.WITNESS}
    return reference.compare(planes, model, samples,
                             stand_ins={n: modes[n] for n in stand_ins})


def rehearsal(conf: dict) -> list:
    """The init program, the program's ``forward`` at T = 1 and at a prefill
    piece, ``forward_batched`` at the pool's rows and slab, and the
    reference's layer and head at the comparison's sizes."""
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
    fn = jax.jit(lambda p, r, tk, c, ps: llama.forward_batched(cfg, p, r, tk, c, ps),
                 donate_argnums=3)
    out.append((f"forward_batched B={rows} slab={slab}", fn,
                (params, rope, toks, bcache, toks), {}))

    m = reference.model_sizes(conf)
    n, t_pad = 6, 640
    x = shape((n, t_pad, d["D"]), jnp.float32)
    cs = shape((t_pad, m[4] // 2), jnp.float32)
    for lower in (None, reference.CONTROL):
        out.append((f"reference layer N={n} T={t_pad} lower={lower}",
                    reference._layer,
                    (x, planes["layers"], shape((), jnp.int32), cs, cs),
                    {"m": m, "lower": lower}))
    out.append(("reference head R=96", reference._head,
                (shape((t_pad, d["D"]), jnp.float32), shape((96,), jnp.int32),
                 planes["rms_final"], planes["wcls"]),
                {"dim": d["D"], "eps": m[7], "lower": None}))
    return out
