"""Dense Llama-family transformer — the single-program SPMD forward pass.

Where the reference unrolls 25 root + 15 worker task functions per layer with
explicit broadcast/gather between them (`/root/reference/src/llama2-tasks.cpp:243-300`),
here the whole forward pass is one jitted function: a ``lax.scan`` over stacked
layer parameters, with tensor-parallel sharding expressed as PartitionSpecs
(see ``dllama_tpu.parallel``) so XLA emits the collectives the reference
hand-rolls over TCP.

Math parity notes:
* rmsnorm eps semantics: `/root/reference/src/funcs.cpp:94-123`.
* attention: `/root/reference/src/llama2-tasks.cpp:54-94` (see ops.attention).
* SwiGLU: ``w2( act(w1 x) * (w3 x) )`` — `/root/reference/src/llama2-tasks.cpp:158-189`.
* logits: final rmsnorm then ``wcls`` matmul — `/root/reference/src/llama2-tasks.cpp:222-241`.

Weights use kernel layout ``[in_features, out_features]`` (transposed from the
file's ``[out, in]`` rows) so activations hit the MXU as plain ``x @ w``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from dllama_tpu.formats.weights import WeightFileReader
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.ops import flash_decode, fused_rope_cache
from dllama_tpu.ops.activations import ACTIVATIONS
from dllama_tpu.ops.attention import gqa_attention
from dllama_tpu.ops.norms import NORMS, centred, rmsnorm
from dllama_tpu.ops.qmatmul import (
    QuantTensor, matmul_any, norm_fusion_engages, qmatmul_norm,
    quantize_tensor, slice_to_in_features,
)
from dllama_tpu.ops.rope import apply_rope, rope_table
from dllama_tpu.parallel.collectives import (
    gather_columns as _gather,
    reduce_scatter_columns as _reduce_scatter,
    rms_inv_scattered as _rms_inv,
    scatter_features as _scatter,
)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def iter_param_tensors(reader: WeightFileReader, cfg: ModelConfig, dtype=None):
    """Yield ``(path, array)`` pairs of the stacked-layer pytree, one tensor
    at a time — ``path`` is ``("embedding",)`` / ``("layers", "wq")`` / etc.

    The streaming unit is one *stacked* tensor (all layers of one matrix), so
    peak host memory is one [L, in, out] array rather than the whole model —
    the TPU analog of the reference's slice-streaming weight distribution
    where no worker ever holds more than its share
    (`/root/reference/src/transformer.cpp:569-598`). Exception: MoE expert
    stacks stream as one [L, E, in, out] tensor per up/gate/down — all
    experts of all layers at once (~1/3 of a Mixtral-class model on the
    host); per-layer expert streaming is future work."""
    dtype = dtype or cfg.jax_dtype
    yield ("embedding",), reader.read_tensor("token_embedding", np.float32)
    yield ("rms_final",), reader.read_tensor("rms_final", np.float32)
    yield ("wcls",), reader.read_tensor("wcls", dtype).T

    mat_names = ["wq", "wk", "wv", "wo"] + ([] if cfg.is_moe else ["w1", "w2", "w3"])
    vec_names = ["rms_att", "rms_ffn"] + (["rms_moe", "rms_ffn2"] if cfg.post_norms else [])
    for n in mat_names:
        yield ("layers", n), np.stack(
            [reader.read_tensor(f"layers.{i}.{n}", dtype).T for i in range(cfg.n_layers)]
        )  # [L, in, out]
    if cfg.is_moe:
        yield ("layers", "moe_router"), np.stack(
            [reader.read_tensor(f"layers.{i}.moe_router", dtype).T for i in range(cfg.n_layers)]
        )
        for kind in ("up", "gate", "down"):
            yield ("layers", f"moe_{kind}"), np.stack(
                [
                    np.stack(
                        [
                            reader.read_tensor(f"layers.{i}.experts.{e}.{kind}", dtype).T
                            for e in range(cfg.n_experts)
                        ]
                    )
                    for i in range(cfg.n_layers)
                ]
            )  # [L, E, in, out]
    for n in vec_names:
        yield ("layers", n), np.stack(
            [reader.read_tensor(f"layers.{i}.{n}", np.float32) for i in range(cfg.n_layers)]
        )


def assemble_params(pairs, transform=None) -> dict:
    """Build the param pytree from ``iter_param_tensors`` pairs, applying
    ``transform(path, arr)`` to each leaf (identity when None). The single
    place that knows the path -> pytree mapping, shared by the full and the
    streaming-sharded loaders."""
    p: dict = {"layers": {}}
    for path, arr in pairs:
        leaf = transform(path, arr) if transform is not None else arr
        if path[0] == "layers":
            p["layers"][path[1]] = leaf
        else:
            p[path[0]] = leaf
    return p


def params_from_reader(reader: WeightFileReader, cfg: ModelConfig, dtype=None) -> dict:
    """Load `.m` tensors into the stacked-layer pytree (dense and MoE archs)."""
    return assemble_params(iter_param_tensors(reader, cfg, dtype))


#: per-layer matrices eligible for fused-quantized storage
QUANTIZABLE = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")
#: expert stacks [L, E, in, out] eligible for fused-quantized storage
MOE_QUANTIZABLE = ("moe_up", "moe_gate", "moe_down")


def quantize_params(params: dict, kind: str, quantize_wcls: bool = True) -> dict:
    """Convert dense layer matrices (and wcls) into stacked ``QuantTensor``s
    for the fused dequant-matmul kernels (ops.qmatmul). Embedding, norms and
    the MoE router stay dense f32 — same split as the reference, which keeps
    rms weights and the embedding table F32 whatever the weight type
    (`/root/reference/converter/convert-llama.py:78-84`; router logits are F32
    at `/root/reference/src/grok1-tasks.cpp:56-60`)."""
    out = dict(params)
    out["layers"] = dict(params["layers"])
    for name in QUANTIZABLE:
        if name not in out["layers"]:
            continue
        stacked = np.asarray(
            jax.device_get(out["layers"][name]), np.float32
        )  # [L, in, out]
        qts = [quantize_tensor(stacked[i], kind) for i in range(stacked.shape[0])]
        out["layers"][name] = jax.tree.map(lambda *xs: jnp.stack(xs), *qts)
    for name in MOE_QUANTIZABLE:
        if name not in out["layers"]:
            continue
        stacked = np.asarray(
            jax.device_get(out["layers"][name]), np.float32
        )  # [L, E, in, out]
        per_layer = [
            jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[quantize_tensor(stacked[l, e], kind) for e in range(stacked.shape[1])],
            )
            for l in range(stacked.shape[0])
        ]
        out["layers"][name] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    if quantize_wcls:
        wcls = np.asarray(jax.device_get(params["wcls"]), np.float32)
        out["wcls"] = quantize_tensor(wcls, kind)
    return out


def quant_params_from_reader(reader: WeightFileReader, cfg: ModelConfig,
                             kind: str = "q40", mesh=None,
                             fuse: bool = True,
                             tp_reduce: bool = False) -> dict:
    """Load a `.m` file with the big matrices kept block-quantized for the
    fused kernels. When the file's own float type matches ``kind``, the file
    bits are repacked losslessly (no dequant->requant roundtrip), so decode
    uses the exact published Q40/Q80 checkpoint values — the TPU equivalent
    of the reference's ``matmulQ40vQ80`` production path
    (`/root/reference/src/funcs.cpp:267-385`). MoE archs load their expert
    stacks as per-expert QuantTensors (the reference runs Q40 Grok-1 314B —
    `/root/reference/src/transformer.cpp:479-487` — a model class that cannot
    exist unquantized).

    Streaming: without a mesh, planes stay host numpy until one whole
    stacked tensor is assembled, then that tensor is placed. With ``mesh``,
    the host never holds more than ONE LAYER of any stacked tensor: each
    [L, ...] stack is preallocated straight into its TP sharding
    (``parallel.quant_tp`` output-axis specs) and filled layer by layer with
    donated in-place ``dynamic_update_slice`` writes. Peak host RAM is
    model_bytes / n_layers — how a Grok-1-314B-class Q40 file loads through
    an ordinary host — and no single device ever holds the full model
    (matching the reference's never-materialize-everything slice streaming,
    `/root/reference/src/transformer.cpp:569-598`)."""
    from dllama_tpu.ops import qmatmul as qm
    from dllama_tpu.quants import blocks

    file_ft = reader.spec.weights_float_type
    lossless = (kind == "q40" and file_ft == blocks.Q40) or (
        kind == "q80" and file_ft == blocks.Q80
    )
    repack = qm.repack_q40 if kind == "q40" else qm.repack_q80

    # the fused kernels need in_features divisible by the packing unit
    # (64 for the q40 nibble pairs, 32 = one block for q80)
    kernel_multiple = 64 if kind == "q40" else 32

    if mesh is not None:
        from jax.sharding import NamedSharding

        from dllama_tpu.parallel import quant_tp
        from dllama_tpu.parallel.mesh import TP

        n_tp = mesh.shape[TP]
        quant_tp.validate_quant_tp(cfg, n_tp)

        def place(name: str, leaf, sharded: bool):
            leaf = quant_tp.prepare_quant_leaf(name, leaf, cfg, n_tp,
                                               tp_reduce=tp_reduce)
            row = (tp_reduce and name in quant_tp.ROW_SHARDED_MATRICES
                   and isinstance(leaf, QuantTensor))
            specs = quant_tp.leaf_specs(leaf, sharded, row=row)
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), leaf, specs
            )

        shard_wcls = cfg.vocab_size % n_tp == 0
    else:
        def place(name: str, leaf, sharded: bool):
            return jax.tree.map(jnp.asarray, leaf)

        shard_wcls = False

    def load_matrix(name: str):
        """Host-side (numpy-plane) QuantTensor or dense array for one matrix."""
        e = reader.entry(name)
        if e.n % kernel_multiple != 0:
            # valid in the file format (blocks are 32-wide) but not packable
            # for the kernel: keep this matrix dense instead of crashing
            return reader.read_tensor(name, cfg.jax_dtype).T
        if lossless:
            return repack(reader.read_raw(name), e.d, e.n, to_device=False)
        return quantize_tensor(
            reader.read_tensor(name, np.float32).T, kind, to_device=False
        )

    def np_stack(items):
        return jax.tree.map(lambda *xs: np.stack(xs), *items)

    p = {
        "embedding": place("embedding", reader.read_tensor("token_embedding", np.float32), False),
        "rms_final": place("rms_final", reader.read_tensor("rms_final", np.float32), False),
        "wcls": place("wcls", load_matrix("wcls"), shard_wcls),
    }
    mat_names = ("wq", "wk", "wv", "wo") if cfg.is_moe else QUANTIZABLE
    vec_names = ["rms_att", "rms_ffn"] + (
        ["rms_moe", "rms_ffn2"] if cfg.post_norms else []
    )
    from dllama_tpu.parallel.quant_tp import SHARDED_MATRICES

    def load_layer_leaf(i: int, n: str):
        pre = f"layers.{i}."
        if n == "moe_router":
            return reader.read_tensor(pre + "moe_router", cfg.jax_dtype).T
        if n.startswith("moe_"):
            return np_stack([
                load_matrix(f"{pre}experts.{e}.{n[4:]}")
                for e in range(cfg.n_experts)
            ])
        return load_matrix(pre + n)

    moe_names = ["moe_router", "moe_up", "moe_gate", "moe_down"] if cfg.is_moe else []

    if mesh is not None:
        # Streamed stacked placement: read one layer of one matrix at a
        # time, lane-align it, and write it into the preallocated SHARDED
        # device stack in place (donated dynamic_update_slice). The host
        # peak is a single layer's planes — for an MoE stack that is
        # 1/n_layers of the expert bytes, not all of them.
        # (quant_tp / NamedSharding are bound above in this mesh branch.)
        from functools import partial

        from jax.sharding import PartitionSpec as P

        @partial(jax.jit, donate_argnums=0)
        def insert(stack, leaf, idx):
            return jax.tree.map(
                lambda s, x: jax.lax.dynamic_update_slice(
                    s, x[None], (idx,) + (0,) * x.ndim),
                stack, leaf,
            )

        def stream_stack(name: str):
            sharded = name in SHARDED_MATRICES
            stack = None
            per_specs = None
            for i in range(cfg.n_layers):
                leaf = quant_tp.prepare_quant_leaf(
                    name, load_layer_leaf(i, name), cfg, n_tp,
                    tp_reduce=tp_reduce)
                if stack is None:
                    row = (tp_reduce
                           and name in quant_tp.ROW_SHARDED_MATRICES
                           and isinstance(leaf, QuantTensor))
                    per_specs = quant_tp.leaf_specs(leaf, sharded, row=row)
                    out_sh = jax.tree.map(
                        lambda x, s: NamedSharding(mesh, P(None, *tuple(s))),
                        leaf, per_specs,
                    )
                    alloc = jax.jit(
                        lambda l=leaf: jax.tree.map(
                            lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), l
                        ),
                        out_shardings=out_sh,
                    )
                    stack = alloc()
                leaf = jax.tree.map(
                    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                    leaf, per_specs,
                )
                stack = insert(stack, leaf, jnp.int32(i))
            return stack

        p["layers"] = {n: stream_stack(n) for n in list(mat_names) + moe_names}
        for n in vec_names:
            vec = np.stack([
                reader.read_tensor(f"layers.{i}.{n}", np.float32)
                for i in range(cfg.n_layers)
            ])
            p["layers"][n] = place(n, vec, False)
        return p

    layers: dict = {}
    for i in range(cfg.n_layers):
        for n in list(mat_names) + moe_names:
            layers.setdefault(n, []).append(load_layer_leaf(i, n))
        for n in vec_names:
            layers.setdefault(n, []).append(
                reader.read_tensor(f"layers.{i}.{n}", np.float32))
    p["layers"] = {k: np_stack(v) for k, v in layers.items()}
    if fuse:
        # single-device: fuse shared-input projections ON HOST (numpy planes)
        # before placement, so the unfused originals never reach HBM —
        # fusing after device placement would double weight residency
        p = fuse_qkv_ffn(p)
    p["layers"] = {
        k: place(k, v, k in SHARDED_MATRICES) for k, v in p["layers"].items()
    }
    return p


def fuse_qkv_ffn(params: dict) -> dict:
    """Concatenate quantized projection matrices that share an input into one
    kernel call each: wq|wk|wv -> ``wqkv`` [D, D+2KV], w1|w3 -> ``w13``
    [D, 2H], moe_up|moe_gate -> ``moe_upgate`` [E, D, 2H].

    Single-device decode win: 7 fused dequant-matmul launches per layer drop
    to 4, each with a larger grid that amortizes pipeline warm-up — the same
    bytes move, in fewer better-overlapped kernels. The forward recognizes
    the fused names and slices the outputs (slices on [T, O] activations are
    free next to the matmul). Quant concat is exact: planes are concatenated
    along the output axis, per-column scales travel with their columns.

    Only for unsharded (mesh-less) params: under TP each part must shard on
    its own output axis, so fusion would put shard boundaries inside the
    wrong matrix. The TP engine keeps the unfused layout.
    """
    out = dict(params)
    out["layers"] = layers = dict(params["layers"])

    def cat(*qts):
        def concat(*xs):
            xp = np if all(isinstance(x, np.ndarray) for x in xs) else jnp
            return xp.concatenate(xs, axis=-1)

        return jax.tree.map(concat, *qts)

    if all(isinstance(layers.get(n), QuantTensor) for n in ("wq", "wk", "wv")):
        layers["wqkv"] = cat(layers.pop("wq"), layers.pop("wk"), layers.pop("wv"))
    if all(isinstance(layers.get(n), QuantTensor) for n in ("w1", "w3")):
        layers["w13"] = cat(layers.pop("w1"), layers.pop("w3"))
    if all(isinstance(layers.get(n), QuantTensor) for n in ("moe_up", "moe_gate")):
        layers["moe_upgate"] = cat(layers.pop("moe_up"), layers.pop("moe_gate"))
    return out


def device_random_quant_params(cfg: ModelConfig, kind: str = "q40", seed: int = 0) -> dict:
    """Random *quantized* params built directly on device — the benchmark's
    7B-shape model with Q40/Q80 HBM residency and no host-side 7B pytree.
    The packed bits are random (valid nibbles/int8) with small scales; the
    model is numerically plausible but meaningless, like device_random_params.
    MoE configs get [L, E, ...] expert plane stacks (the loader's layout:
    TP-within-expert, every chip a slice of every expert) with a dense f32
    router, so Q40 Grok-1/Mixtral-shape decode is benchable without a
    checkpoint.

    The whole build runs as ONE jitted program: ~25 eager randint/astype
    dispatches would be ~25 separate compiles; one program is one compile
    and one execute."""
    return jax.jit(_quant_init, static_argnums=(1, 2))(
        jax.random.PRNGKey(seed), cfg, kind
    )


def _quant_init(key, cfg: ModelConfig, kind: str) -> dict:
    L, D, H, KV = cfg.n_layers, cfg.dim, cfg.hidden_dim, cfg.kv_dim
    ks = iter(jax.random.split(key, 32))

    def qrand(K_, O_, prefix=(L,)):
        """Random QuantTensor, shape prefix () for unstacked (wcls). The
        packed K is padded like pack_q40/pack_q80 (random pad bits are fine:
        padded activation rows are zero, so the pad contributes nothing)."""
        from dllama_tpu.ops.qmatmul import K_MULTIPLE, _pad_up

        kp = _pad_up(K_, K_MULTIPLE[kind])
        if kind == "q40":
            w = jax.random.randint(
                next(ks), (*prefix, kp // 2, O_), 0, 256, jnp.int32
            ).astype(jnp.uint8)
            s = jax.random.uniform(next(ks), (*prefix, kp // 64, O_), jnp.float32) * 0.004
            s2 = jax.random.uniform(next(ks), (*prefix, kp // 64, O_), jnp.float32) * 0.004
            return QuantTensor(w=w, s=s, s2=s2, kind="q40", k_logical=K_)
        w = jax.random.randint(next(ks), (*prefix, kp, O_), -127, 128, jnp.int8)
        s = jax.random.uniform(next(ks), (*prefix, kp // 32, O_), jnp.float32) * 0.0003
        return QuantTensor(
            w=w, s=s, s2=jnp.zeros((*prefix, 0), jnp.float32), kind="q80", k_logical=K_
        )

    layers = {
        "wq": qrand(D, D),
        "wk": qrand(D, KV),
        "wv": qrand(D, KV),
        "wo": qrand(D, D),
        "rms_att": jnp.ones((L, D), jnp.float32),
        "rms_ffn": jnp.ones((L, D), jnp.float32),
    }
    if cfg.is_moe:
        E = cfg.n_experts
        layers.update(
            moe_router=jax.random.normal(next(ks), (L, D, E), jnp.float32) * 0.02,
            moe_up=qrand(D, H, prefix=(L, E)),
            moe_gate=qrand(D, H, prefix=(L, E)),
            moe_down=qrand(H, D, prefix=(L, E)),
        )
        if cfg.post_norms:
            layers["rms_moe"] = jnp.ones((L, D), jnp.float32)
            layers["rms_ffn2"] = jnp.ones((L, D), jnp.float32)
    else:
        layers.update(w1=qrand(D, H), w3=qrand(D, H), w2=qrand(H, D))
    return {
        "embedding": jax.random.normal(next(ks), (cfg.vocab_size, D), jnp.float32) * 0.02,
        "rms_final": jnp.ones(D, jnp.float32),
        "wcls": qrand(D, cfg.vocab_size, prefix=()),
        "layers": layers,
    }


def random_params(cfg: ModelConfig, seed: int = 0, scale: float = 0.02, dtype=None) -> dict:
    """Seeded synthetic weights (the llama2-tasks-test pattern, for tests/bench)."""
    dtype = dtype or cfg.jax_dtype
    rng = np.random.default_rng(seed)

    def w(*shape):
        return (rng.standard_normal(shape) * scale).astype(np.float32).astype(dtype)

    L, D, H, KV = cfg.n_layers, cfg.dim, cfg.hidden_dim, cfg.kv_dim
    layers = {
        "wq": w(L, D, D),
        "wk": w(L, D, KV),
        "wv": w(L, D, KV),
        "wo": w(L, D, D),
        "rms_att": np.ones((L, D), np.float32),
        "rms_ffn": np.ones((L, D), np.float32),
    }
    if cfg.is_moe:
        E = cfg.n_experts
        layers.update(
            {
                "moe_router": w(L, D, E),
                "moe_up": w(L, E, D, H),
                "moe_gate": w(L, E, D, H),
                "moe_down": w(L, E, H, D),
            }
        )
        if cfg.post_norms:
            layers["rms_moe"] = np.ones((L, D), np.float32)
            layers["rms_ffn2"] = np.ones((L, D), np.float32)
    else:
        layers.update({"w1": w(L, D, H), "w2": w(L, H, D), "w3": w(L, D, H)})
    return {
        "embedding": w(cfg.vocab_size, D).astype(np.float32),
        "rms_final": np.ones(D, np.float32),
        "wcls": w(D, cfg.vocab_size),
        "layers": layers,
    }


def device_random_params(
    cfg: ModelConfig, seed: int = 0, dtype=None, scale: float = 0.02, mesh=None
) -> dict:
    """Random params generated ON DEVICE (one jitted program) — a 7B bf16
    pytree never exists in host RAM. With ``mesh``, the program writes each
    tensor directly into its TP sharding, so no chip ever holds the full
    model. For benchmarks and dry-runs."""
    dtype = dtype or cfg.jax_dtype
    L, D, H, KV = cfg.n_layers, cfg.dim, cfg.hidden_dim, cfg.kv_dim

    shapes = {
        "embedding": ((cfg.vocab_size, D), jnp.float32),
        "rms_final": ((D,), jnp.float32),
        "wcls": ((D, cfg.vocab_size), dtype),
        "layers": {
            "wq": ((L, D, D), dtype),
            "wk": ((L, D, KV), dtype),
            "wv": ((L, D, KV), dtype),
            "wo": ((L, D, D), dtype),
            "rms_att": ((L, D), jnp.float32),
            "rms_ffn": ((L, D), jnp.float32),
        },
    }
    if cfg.is_moe:
        E = cfg.n_experts
        shapes["layers"].update(
            moe_router=((L, D, E), jnp.float32),
            moe_up=((L, E, D, H), dtype),
            moe_gate=((L, E, D, H), dtype),
            moe_down=((L, E, H, D), dtype),
        )
        if cfg.post_norms:
            shapes["layers"]["rms_moe"] = ((L, D), jnp.float32)
            shapes["layers"]["rms_ffn2"] = ((L, D), jnp.float32)
    else:
        shapes["layers"].update(
            w1=((L, D, H), dtype), w2=((L, H, D), dtype), w3=((L, D, H), dtype)
        )

    def init(key):
        leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, dt) in zip(keys, leaves):
            # generate directly in the target dtype: an f32 intermediate for a
            # stacked-layer 7B tensor is a multi-GB transient that OOMs a chip
            out.append(jax.random.normal(k, shape, dt) * jnp.asarray(scale, dt))
        return jax.tree.unflatten(treedef, out)

    if mesh is not None:
        from jax.sharding import NamedSharding

        from dllama_tpu.parallel.mesh import TP
        from dllama_tpu.parallel.sharding import param_specs

        specs = param_specs(cfg, mesh.shape[TP])
        out_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        init_fn = jax.jit(init, out_shardings=out_shardings)
    else:
        init_fn = jax.jit(init)
    params = init_fn(jax.random.PRNGKey(seed))
    # norms start at 1 like a real checkpoint
    params["rms_final"] = jnp.ones_like(params["rms_final"])
    for name in ("rms_att", "rms_ffn", "rms_moe", "rms_ffn2"):
        if name in params["layers"]:
            params["layers"][name] = jnp.ones_like(params["layers"][name])
    return params


def init_cache(cfg: ModelConfig, cache_dtype=jnp.float32) -> dict:
    """Fixed-size per-layer KV cache [L, seq_len, n_kv_heads, head_size]
    (a layer plan: one stack an attention kind, ``models.layer_plan``)."""
    if cfg.layer_plan:
        from dllama_tpu.models import layer_plan

        return layer_plan.init_cache(cfg, cache_dtype)
    shape = (cfg.n_layers, cfg.seq_len, cfg.n_kv_heads, cfg.head_size)
    return {"k": jnp.zeros(shape, cache_dtype), "v": jnp.zeros(shape, cache_dtype)}


def rope_tables(cfg: ModelConfig) -> dict:
    if cfg.layer_plan:
        from dllama_tpu.models import layer_plan

        return layer_plan.rope_tables(cfg)
    cos, sin = rope_table(cfg.seq_len, cfg.head_size, cfg.rope_theta)
    return {"cos": jnp.asarray(cos), "sin": jnp.asarray(sin)}


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def _norm_proj(x, norm_w, w, layer, eps, name=None, norm="rms"):
    """``rmsnorm(x, norm_w) @ w`` (``norm`` "layer": a LayerNorm without
    bias, which is the rmsnorm of the centred input, so the fused kernel
    takes ``centred(x)``). With DLLAMA_FUSE_NORM and a quantized
    ``w``, the norm rides inside the matmul kernel as an x-block epilogue
    (qmatmul.qmatmul_norm — bit-identical in the kernel's products; the q40
    recentering term's block sums are XLA's to order, a last place apart in
    one case of tests/test_fused_ops.py — one fewer activation HBM
    round-trip). Callers needing the same normalized activation for several
    projections call this per projection: fused, the epilogue recomputes
    in-register (the point); unfused, XLA CSEs the repeated rmsnorm."""
    if norm_fusion_engages(w):
        return qmatmul_norm(centred(x) if norm == "layer" else x, norm_w, w,
                            layer, eps, name)
    return matmul_any(NORMS[norm](x, norm_w, eps), w, layer, name)


def _check_tp_reduce(cfg: ModelConfig, tp_reduce) -> bool:
    """Static validation of the row-parallel reduce mode; True when active.

    MoE is rejected at trace time with the same machine-visible style as
    ``_check_overlap_split``: the expert stacks keep output-axis shards
    (every device holds a slice of EVERY expert), so there is no K-sharded
    down-projection to feed partials from."""
    if tp_reduce is None:
        return False
    if tp_reduce not in ("plain", "q80"):
        raise ValueError(f"tp_reduce must be None, 'plain' or 'q80', "
                         f"got {tp_reduce!r}")
    if cfg.is_moe:
        raise ValueError(
            "tp_reduce requires a dense FFN: MoE expert stacks shard their "
            "output axis (a slice of every expert per device), so no "
            "row-parallel down-projection exists to produce partial sums")
    return True


def _row_norm_gather(x_s: jnp.ndarray, norm_w, tp_axis, tp_compress: bool,
                     eps: float, full_dim: int) -> jnp.ndarray:
    """The fused norm+reduce epilogue's gather half: rmsnorm the SCATTERED
    residual ``[..., dim/tp]`` (one scalar psum for the mean-square, see
    ``collectives.rms_inv_scattered``) and all-gather the normalized rows.
    The full-width gather that the un-fused path would spend reassembling
    the raw residual is gone — the one gather per sub-block now carries the
    next matmul's already-normalized input. Mirrors ``ops.norms.rmsnorm``'s
    f32 accumulation and ``w * (x * inv)`` ordering."""
    inv = _rms_inv(x_s, tp_axis, full_dim, eps)
    xn = _gather((x_s.astype(jnp.float32) * inv[..., None]).astype(x_s.dtype),
                 tp_axis, tp_compress)
    return (norm_w.astype(jnp.float32) * xn.astype(jnp.float32)
            ).astype(x_s.dtype)


@jax.named_scope("ffn")
def _dense_ffn_row(cfg: ModelConfig, lp: dict, xn: jnp.ndarray,
                   layer=None) -> jnp.ndarray:
    """Row-parallel FFN half on the ALREADY-NORMALIZED full-width input:
    w1/w3 emit their local output shards, which feed the K-sharded w2
    directly — no hidden-width gather at all (the row-parallel point: the
    gathered hidden is ~2.7x dim for 7B). Returns [T, dim] f32 PARTIAL sums
    for the caller's ring reduce-scatter. ``lp['w2']`` is a
    ``row_shard_quant_leaf`` repack whose ``k_logical`` equals the local
    hidden shard width, so the quant kernel pads the activation to the
    per-shard K itself."""
    act = ACTIVATIONS[cfg.hidden_act]
    h = (act(matmul_any(xn, lp["w1"], layer, name="w1"))
         * matmul_any(xn, lp["w3"], layer, name="w3"))
    return matmul_any(h, lp["w2"], layer, name="w2").astype(jnp.float32)


@jax.named_scope("ffn")
def _dense_ffn(cfg: ModelConfig, lp: dict, x: jnp.ndarray, norm_w, tp_axis=None,
               tp_compress: bool = False, layer=None) -> jnp.ndarray:
    """FFN half on the RAW (pre-norm) residual ``x``: the ``rms_ffn`` norm is
    applied via ``_norm_proj`` so it can fuse into the up/gate kernels."""
    act = ACTIVATIONS[cfg.hidden_act]
    eps = cfg.norm_eps
    if "w13" in lp:  # fused single-kernel up|gate projection (fuse_qkv_ffn)
        u = _norm_proj(x, norm_w, lp["w13"], layer, eps, "w13", cfg.norm)
        half = u.shape[-1] // 2
        h = act(u[..., :half]) * u[..., half:]
        return matmul_any(h, lp["w2"], layer, name="w2")
    h = (act(_norm_proj(x, norm_w, lp["w1"], layer, eps, "w1", cfg.norm))
         * _norm_proj(x, norm_w, lp["w3"], layer, eps, "w3", cfg.norm))
    h = slice_to_in_features(_gather(h, tp_axis, tp_compress), lp["w2"])
    return _gather(matmul_any(h, lp["w2"], layer, name="w2"), tp_axis,
                   tp_compress)


def _ffn_residual(cfg: ModelConfig, lp: dict, x: jnp.ndarray, att_out: jnp.ndarray,
                  tp_axis=None, tp_compress: bool = False, layer=None):
    """Post-attention half of a layer, all three arch variants:

    * llama: ``x += att; x += dense_ffn(rmsnorm(x, rms_ffn))``
      (`/root/reference/src/llama2-tasks.cpp:125-212`)
    * mixtral: same joins with the MoE FFN
      (`/root/reference/src/mixtral-tasks.cpp:24-46`)
    * grok1: the attention output and the MoE output are each rmsnorm'd
      BEFORE their residual adds, with an extra pre-MoE norm:
      ``x += rmsnorm(att, rms_ffn); x += rmsnorm(moe(rmsnorm(x, rms_moe)), rms_ffn2)``
      (`/root/reference/src/grok1-tasks.cpp:16-54,239-262,280-320`)
    """
    from dllama_tpu.models.moe import moe_ffn

    if cfg.is_moe and cfg.post_norms:  # grok1
        x = x + rmsnorm(att_out, lp["rms_ffn"], cfg.norm_eps)
        xb = rmsnorm(x, lp["rms_moe"], cfg.norm_eps)
        return x + rmsnorm(moe_ffn(cfg, lp, xb, layer, tp_axis, tp_compress),
                           lp["rms_ffn2"], cfg.norm_eps)
    x = x + att_out
    if cfg.is_moe:
        xb = rmsnorm(x, lp["rms_ffn"], cfg.norm_eps)
        return x + moe_ffn(cfg, lp, xb, layer, tp_axis, tp_compress)
    return x + _dense_ffn(cfg, lp, x, lp["rms_ffn"], tp_axis, tp_compress,
                          layer)


def _layer_params(layers: dict, idx) -> dict:
    """Layer ``idx``'s parameters out of the stacked ``layers``. A dense
    leaf is sliced (a dense dynamic-slice fuses into its dot). A
    ``QuantTensor`` stays STACKED: slicing the planes in the scan's body
    (``w[idx]``) would make XLA materialize a full copy of every layer's
    weights each step (a Pallas custom-call operand can't fuse a
    dynamic-slice) — ~3x the per-token HBM traffic of reading the weights
    once. Instead the scalar-prefetched ``idx`` steers each kernel's own DMA
    straight into the stacked plane (qmatmul.*_stacked)."""
    return {
        name: (leaf if isinstance(leaf, QuantTensor)
               else jax.lax.dynamic_index_in_dim(leaf, idx, 0, keepdims=False))
        for name, leaf in layers.items()
    }


def _qkv(cfg: ModelConfig, lp: dict, x, layer, lead: tuple,
         row_mode: bool = False, widths=None):
    """The q/k/v projections of ``x`` [N, dim], split into heads:
    q ``[*lead, heads, hd]``, k ``[*lead, kv, hd]``, v ``[*lead, kv, v_hd]``
    (``lead`` is ``(N,)``, or the verify step's ``(B, T)``). Head counts
    derive from the ARRAY shapes, never from cfg: under tp the projections
    are output-sharded and the counts are the local slices (the reference's
    ``MultiHeadAttSlice`` head split,
    `/root/reference/src/transformer.cpp:161-181`).

    ``row_mode`` (the --tp-reduce row-parallel path): ``x`` arrives ALREADY
    normalized (the caller's fused norm+gather epilogue), so the projections
    skip ``_norm_proj``. ``widths``: the (q, k) columns of a fused ``wqkv``
    where they are not the uniform model's (a layer plan's kinds)."""
    eps = cfg.norm_eps
    if row_mode:  # pre-normalized input; rms_att was applied by the caller
        q, k, v = (matmul_any(x, lp[n], layer, name=n)
                   for n in ("wq", "wk", "wv"))
    elif "wqkv" in lp:  # fused single-kernel projection (fuse_qkv_ffn; no TP)
        qkv = _norm_proj(x, lp["rms_att"], lp["wqkv"], layer, eps, "wqkv",
                         cfg.norm)
        d, kv = widths or (cfg.dim, cfg.kv_dim)
        q, k, v = qkv[:, :d], qkv[:, d : d + kv], qkv[:, d + kv :]
    else:
        q, k, v = (_norm_proj(x, lp["rms_att"], lp[n], layer, eps, n, cfg.norm)
                   for n in ("wq", "wk", "wv"))
    def heads(a, size):
        return a.reshape(*lead, -1, size)

    if cfg.value_scale != 1.0:
        # scaled values are split first: the order in which the programs of
        # the models that scale them were compiled
        v = heads(v, cfg.v_size) * jnp.asarray(cfg.value_scale, v.dtype)
        return heads(q, cfg.head_size), heads(k, cfg.head_size), v
    return (heads(q, cfg.head_size), heads(k, cfg.head_size),
            heads(v, cfg.v_size))


def _attn_out(lp: dict, out, layer, tp_axis=None, tp_compress: bool = False,
              row_mode: bool = False):
    """The attention's output half on the head concat ``out`` [N, local
    heads * hd]: gather the heads, ``wo``, gather the output. ``row_mode``:
    ``wo`` is K-sharded, so the LOCAL head concat feeds it with NO gather
    and the result is a full-width f32 PARTIAL sum for the caller's ring
    reduce-scatter — both of the attention sub-block's gathers disappear."""
    if row_mode:
        return matmul_any(out, lp["wo"], layer, name="wo").astype(jnp.float32)
    out = _gather(out, tp_axis, tp_compress)  # local heads -> full
    return _gather(matmul_any(out, lp["wo"], layer, name="wo"), tp_axis,
                   tp_compress)


@jax.named_scope("attention")
def _attention(cfg: ModelConfig, lp: dict, x, core, k_cache, v_cache, layer,
               lead=None, tp_axis=None, tp_compress: bool = False,
               row_mode: bool = False, widths=None):
    """One attention sub-block around a *core*: the projections of ``x``
    [N, dim], the core, the output half. Returns (attn output [N, dim], new
    k/v cache).

    ``core(q, k, v, k_cache, v_cache, layer) -> (out, k_cache, v_cache)``
    is what the entry points differ in: where a row's rope angles come
    from, how the step's K/V rows are written, and what each query attends
    (``_solo_core``, ``_rows_core``, ``_verify_core``; a layer plan's own in
    ``models.layer_plan``).

    With ``tp_axis`` (inside shard_map, quantized TP) the attention runs on
    this device's heads against its kv-head slice of the cache
    (``KvCacheSlice``). With ``layer`` (the scalar-prefetch scan path):
    quant matrices in ``lp`` are layer-stacked and k_cache/v_cache are the
    FULL stacked caches; without it they are this layer's own.
    ``row_mode``: see ``_qkv`` and ``_attn_out``."""
    q, k, v = _qkv(cfg, lp, x, layer, lead or x.shape[:1], row_mode, widths)
    out, k_cache, v_cache = core(q, k, v, k_cache, v_cache, layer)
    return (_attn_out(lp, out.reshape(x.shape[0], -1), layer, tp_axis,
                      tp_compress, row_mode), k_cache, v_cache)


def _layer(cfg: ModelConfig, lp: dict, layer, streams: list, cores: list,
           tp_axis=None, tp_compress: bool = False, tp_reduce=None) -> list:
    """One transformer layer over one stream ``(x, k_cache, v_cache)`` or
    over the two microbatches of an overlap step, each with its core ->
    the streams after the layer. ``x`` is [N, dim], or the verify step's
    [B, T, dim], whose rows every matmul sees flattened.

    Gather mode: every stream's attention, then every stream's
    ``_ffn_residual``. With two streams, microbatch A's attention (ending in
    its head + wo gathers) is issued before microbatch B's in program order;
    the two chains share only the layer's weights (read-only), so XLA's
    latency-hiding scheduler is free to run B's matmuls while A's gather is
    on the wire.

    ``tp_reduce`` ('plain' | 'q80'; the caller passes it only where row mode
    is active): the row-parallel sequence, a stream at a time. ``x`` rides
    SCATTERED [N, dim/tp]; the fused norm+gather feeds the projections, the
    K-sharded ``wo``/``w2`` partials take the ring reduce-scatter
    (Q80-compressed hops when 'q80') and the residual adds happen on the
    shard. The reduce-scatters are tp-1 ppermute hops by construction, so
    they give the scheduler the same hop-granular boundaries the ring
    gathers do."""
    def rows(x):
        return x.reshape(-1, x.shape[-1])

    if tp_reduce is not None:
        red_compress = tp_reduce == "q80"
        out = []
        for (x, k_cache, v_cache), core in zip(streams, cores):
            x_s = rows(x)  # scattered residual rows
            xn = _row_norm_gather(x_s, lp["rms_att"], tp_axis, tp_compress,
                                  cfg.norm_eps, cfg.dim)
            att_p, k_cache, v_cache = _attention(
                cfg, lp, xn, core, k_cache, v_cache, layer, x.shape[:-1],
                tp_axis, tp_compress, row_mode=True)
            x_s = x_s + _reduce_scatter(att_p, tp_axis,
                                        red_compress).astype(x_s.dtype)
            xn = _row_norm_gather(x_s, lp["rms_ffn"], tp_axis, tp_compress,
                                  cfg.norm_eps, cfg.dim)
            ffn_p = _dense_ffn_row(cfg, lp, xn, layer=layer)
            x_s = x_s + _reduce_scatter(ffn_p, tp_axis,
                                        red_compress).astype(x_s.dtype)
            out.append((x_s.reshape(x.shape), k_cache, v_cache))
        return out
    atts = [_attention(cfg, lp, rows(x), core, k_cache, v_cache, layer,
                       x.shape[:-1], tp_axis, tp_compress)
            for (x, k_cache, v_cache), core in zip(streams, cores)]
    return [(_ffn_residual(cfg, lp, rows(x), att, tp_axis, tp_compress,
                           layer=layer).reshape(x.shape), k_cache, v_cache)
            for (x, _, _), (att, k_cache, v_cache) in zip(streams, atts)]


def _scan_layers(layers: dict, n_layers: int, step, streams: list,
                 index_scan: bool = True) -> list:
    """``step(lp, layer, streams) -> streams`` over every layer; a stream is
    ``(x, k_cache, v_cache)``, and two of them are the microbatches of an
    overlap step: both advance inside ONE layer scan, so weights still
    stream from HBM once per layer for all rows.

    ``index_scan``: scan over a layer INDEX with the stacked planes closed
    over as scan constants (``_layer_params``) and the stacked caches in the
    carry, updated in place at (idx, pos). Otherwise dense weights and the
    caches scan as scan-xs (per-layer slabs), ``layer`` is None and the
    stream's caches are one layer's."""
    if index_scan:
        def body(carry, idx):
            out = step(_layer_params(layers, idx), idx, list(zip(*carry)))
            return tuple(zip(*out)), None

        carry, _ = jax.lax.scan(body, tuple(zip(*streams)),
                                jnp.arange(n_layers, dtype=jnp.int32))
        return list(zip(*carry))
    (x, k_caches, v_caches), = streams

    def body(x, layer):
        lp, k_cache, v_cache = layer
        (x, k_cache, v_cache), = step(lp, None, [(x, k_cache, v_cache)])
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(body, x, (layers, k_caches, v_caches))
    return [(x, new_k, new_v)]


def _quant_scan(layers: dict) -> bool:
    return any(isinstance(v, QuantTensor) for v in layers.values())


def _scan_choice(layers: dict, allow_flash: bool, T: int, cache: dict,
                 overlap: bool = False) -> tuple:
    """-> (index scan?, flash decode?) of a forward over ``T`` tokens a
    sequence: the one place that asks the flash gate and ``allow_flash``.

    Dense weights normally scan the layer stack as scan-xs (per-layer
    slabs); when flash decode engages, take the index-scan instead so the
    stacked KV cache rides the carry and the flash kernel reads its live
    prefix in place — dense weight slices still fuse into the dots (a
    dense dynamic-slice is fusable, unlike a Pallas operand). Quantized
    planes and the two streams of an overlap step always take the index
    scan, and there the kernel engages whatever ``allow_flash`` says.

    DLLAMA_FLASH_DECODE=1: online-softmax kernel reading ONLY the live
    cache prefix, straight from the stacked cache — no per-layer slab
    materialization, bytes scale with pos not seq_len (ops.flash_decode;
    opt-in until benchmark-proven on hardware)."""
    stacked = overlap or _quant_scan(layers)
    flash = (stacked or allow_flash) and flash_decode.engages(
        T, cache["k"].shape[-3], cache["k"].dtype)
    return stacked or flash, flash


def _row_mode(cfg: ModelConfig, layers: dict, tp_axis, tp_reduce) -> bool:
    """Whether the row-parallel reduce path is active: it needs the
    quantized index-scan (row_shard_quant_leaf repacks quant planes; the
    Engine declines it elsewhere)."""
    return (_check_tp_reduce(cfg, tp_reduce) and tp_axis is not None
            and _quant_scan(layers))


def _final_norm(cfg: ModelConfig, params: dict, x, tp_axis=None,
                tp_compress: bool = False, row: bool = False):
    """``rms_final``; in row mode one last fused norm+gather reassembles the
    scattered residual already normalized for the classifier."""
    if row:
        return _row_norm_gather(x, params["rms_final"], tp_axis, tp_compress,
                                cfg.norm_eps, cfg.dim)
    return NORMS[cfg.norm](x, params["rms_final"], cfg.norm_eps)


def _head(cfg: ModelConfig, params: dict, x, tp_axis=None,
          gather_logits: bool = True, tp_compress: bool = False,
          row: bool = False, last_pos=None, normed: bool = False):
    """The step's tail: the residual ``x`` [..., dim] -> logits
    [..., vocab] f32. ``last_pos``: row ``last_pos`` alone (see
    ``forward``). ``normed``: ``x`` has had its ``_final_norm`` (the halves
    of an overlap step in row mode take it before they rejoin). A tied head
    (``cfg.tied_embedding``) multiplies by the table's own planes where the
    parameters bring them as ``wcls``, else by ``embedding`` transposed."""
    if last_pos is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=0)
    if not normed:
        x = _final_norm(cfg, params, x, tp_axis, tp_compress, row)
    if cfg.tied_embedding and "wcls" not in params:
        wcls = params["embedding"].T.astype(x.dtype)
    else:
        wcls = params["wcls"]
    logits = matmul_any(x.reshape(-1, x.shape[-1]), wcls,
                        name="wcls").astype(jnp.float32)
    if tp_axis is not None and gather_logits:
        # slice off any lane-alignment vocab padding (zero logits there would
        # beat real negative logits in an argmax) — no-op when unpadded
        logits = _gather(logits, tp_axis)[..., : cfg.vocab_size]
    logits = logits.reshape(*x.shape[:-1], -1)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    return logits


def _write_kv_seq(k_cache, v_cache, k, v, layer, pos):
    """Land one sequence's T new rows ``k``/``v`` [T, kv, hd] at
    ``(layer, pos..pos+T)`` of the stacked ``[L, S, kv, hd]`` caches, in
    place in the scan's carry."""
    zero = jnp.int32(0)
    with jax.named_scope("kv_slab_write"):
        return (jax.lax.dynamic_update_slice(
                    k_cache, k.astype(k_cache.dtype)[None],
                    (layer, pos, zero, zero)),
                jax.lax.dynamic_update_slice(
                    v_cache, v.astype(v_cache.dtype)[None],
                    (layer, pos, zero, zero)))


def _write_kv_rows(k_cache, v_cache, k, v, layer, pos):
    """Land each sequence's new K/V rows in the stacked ``[L, B, S, kv, hd]``
    caches: ``k``/``v`` are ``[B, T, kv, hd]`` and sequence b's T rows go to
    ``(layer, b, pos[b]..pos[b]+T)``. One scatter a cache, which XLA runs in
    place on the donated scan carry: the compiled step writes ``B*T*kv*hd``
    elements a layer and copies no slab out or back. The start clamps as
    ``dynamic_update_slice`` clamps, to ``S - T``: a row stepped at
    ``pos >= S`` lands in the last slot, where free rows pin.

    Keep it a scatter: ``B`` unrolled ``dynamic_update_slice``s, or one
    vmapped over the row axis, make the v5e compiler carry the whole cache
    in another layout and turn it there and back around every launch
    (PERF.md, PR 25)."""
    B, T = k.shape[:2]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    cols = (jnp.clip(pos, 0, k_cache.shape[2] - T)[:, None]
            + jnp.arange(T, dtype=jnp.int32))
    with jax.named_scope("kv_slab_write"):
        return (k_cache.at[layer, rows, cols].set(k.astype(k_cache.dtype)),
                v_cache.at[layer, rows, cols].set(v.astype(v_cache.dtype)))


def _write_rows_at(k_cache, v_cache, k, v, layer, rows, cols):
    """Land the K/V rows ``k``/``v`` ``[n, kv, hd]`` at ``(layer, rows[i],
    cols[i])`` of the stacked caches (``layer`` None: of this layer's
    ``[B, S, kv, hd]`` slab): ``_write_kv_rows``'s in-place scatter for a
    step whose rows are not one a sequence (decode rows and riders
    together). A column out of bounds drops its row."""
    idx = (rows, cols) if layer is None else (layer, rows, cols)
    with jax.named_scope("kv_slab_write"):
        return (k_cache.at[idx].set(k.astype(k_cache.dtype), mode="drop"),
                v_cache.at[idx].set(v.astype(v_cache.dtype), mode="drop"))


def _plain_layout(*arrays) -> tuple:
    """Each K/V array held to the layout it is carried in, where that is
    known to be plain row-major: heads a whole number of 128 lanes wide. The
    v5e compiler asks a slice's operand for the layout the slice's consumer
    likes; where the operand is a conditional's parameter it then turns the
    WHOLE stacked cache around before every slice (0.8 GB a window layer a
    step at Command A+'s sizes, PERF.md PR 32). Pinning the stacked caches
    and the slice leaves the one copy of the slice that a plain read makes.
    A head of another width (MiMo's 192-wide keys) is carried with the slots
    minor, which is what the contraction reads: it needs no pin, and one
    would cost a copy in and out of every program."""
    return tuple(
        with_layout_constraint(a, Layout(major_to_minor=tuple(range(a.ndim))))
        if a.shape[-1] % 128 == 0 else a for a in arrays)


def _layer_slabs(k_cache, v_cache, layer, slots: int = None):
    """The layer's ``[(B,) S, kv, hd]`` K and V out of the stacked caches, to
    be read only: on the v5e this is the one pass over those bytes that
    attention needs (the slice is staged for the score and value
    contractions, which then read no HBM again). With ``slots``: only the
    first ``slots`` of the S, for a caller whose queries see no further
    (``layer_plan._attend``, inside a conditional: see ``_plain_layout``)."""
    with jax.named_scope("kv_slab_read"):
        if slots is None:
            return (jax.lax.dynamic_index_in_dim(k_cache, layer, 0,
                                                 keepdims=False),
                    jax.lax.dynamic_index_in_dim(v_cache, layer, 0,
                                                 keepdims=False))
        zero = jnp.int32(0)
        return _plain_layout(*(jax.lax.dynamic_slice(
            cache, (layer, *(zero,) * (cache.ndim - 1)),
            (1, *cache.shape[1:-3], slots, *cache.shape[-2:]))[0]
            for cache in (k_cache, v_cache)))


def _solo_core(cfg: ModelConfig, rope: dict, pos, flash: bool = False):
    """The core of ``forward``: one sequence, T tokens at ``pos..pos+T``.
    With ``layer`` the update touches only (layer, pos..pos+T) of the
    stacked [L, S, kv, hd] caches and the attention reads the layer's slab
    (``flash``: the kernel reads the live prefix in place, from BOTH
    engines: the quantized layer-scan and the dense index-scan); without it
    the caches are this layer's [S, kv, hd]."""
    def core(q, k, v, k_cache, v_cache, layer):
        T = q.shape[0]
        cos = jax.lax.dynamic_slice_in_dim(rope["cos"], pos, T)[:, None, :]
        sin = jax.lax.dynamic_slice_in_dim(rope["sin"], pos, T)[:, None, :]
        q = apply_rope(q, cos, sin, cfg.rope_style)
        if layer is None:
            k = apply_rope(k, cos, sin, cfg.rope_style)
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), pos, axis=0)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), pos, axis=0)
            return gqa_attention(q, k_cache, v_cache, pos), k_cache, v_cache
        if fused_rope_cache.engages(T, k_cache.dtype):
            # DLLAMA_FUSE_ROPE_CACHE=1: K rotates in-kernel and lands with V
            # in the stacked cache in one pass (ops.fused_rope_cache) —
            # bit-identical to the apply_rope + dynamic_update_slice below
            k_cache, v_cache = fused_rope_cache.rope_cache_update(
                k, v, cos, sin, k_cache, v_cache, pos, layer, cfg.rope_style)
        else:
            k = apply_rope(k, cos, sin, cfg.rope_style)
            k_cache, v_cache = _write_kv_seq(k_cache, v_cache, k, v, layer,
                                             pos)
        if flash:
            out = flash_decode.flash_decode_attention(q, k_cache, v_cache,
                                                      pos, layer)
        else:
            out = gqa_attention(q, *_layer_slabs(k_cache, v_cache, layer),
                                pos)
        return out, k_cache, v_cache

    return core


def _ride_step(rope: dict, pos, ride, slab_len: int) -> dict:
    """What a decode step that carries riders needs of its ``ride`` =
    ``(tokens [t], row, start, n)``, worked out ONCE a step, outside the
    layer loop, over the step's B decode rows followed by its t riders:
    ``cos`` / ``sin`` the rope angles of every row's position; ``rows`` /
    ``cols`` the pool row and slot where each row's K/V land (a decode row
    at its clamped position, as ``_write_kv_rows`` clamps; a rider at
    ``start + i`` of ``row``; a padded rider, ``i >= n``, at the SLAB's
    length: out of bounds, so the scatter drops it and it touches no slot);
    ``row`` / ``start`` for the riders' attention."""
    tokens, row, start, n = ride
    i = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    at = jnp.concatenate([pos, start + i])
    return {
        "row": row, "start": start,
        "rows": jnp.concatenate([jnp.arange(pos.shape[0], dtype=jnp.int32),
                                 jnp.full(i.shape, row, jnp.int32)]),
        "cols": jnp.concatenate([jnp.clip(pos, 0, slab_len - 1),
                                 jnp.where(i < n, start + i, slab_len)]),
        # a gather clamps a padded rider's position past the table
        "cos": rope["cos"][at][:, None, :], "sin": rope["sin"][at][:, None, :],
    }


def _rows_core(cfg: ModelConfig, rope: dict, pos, flash: bool = False,
               ride=None):
    """The core of ``forward_batched``: B INDEPENDENT sequences, one token
    each, row b at its own position ``pos[b]``. The projections around it
    are ordinary [B, K] matmuls (identical to a T=B prefill row block — the
    quant kernels need no batching rule); only rope/cache/attention are
    per-row, via gather and vmap over the pure-jnp attention. Caches are
    [L, B, S, kv, hd] under the layer scan (``layer`` given) or this layer's
    [B, S, kv, hd] slab. Either way the step's B rows of K and V are written
    where they live, in the scan's donated carry, and attention then reads
    the layer's slab: no slab is copied out, updated and written back
    (``_write_kv_rows``). ``flash``: the kernel reads each row's OWN live
    prefix from the stacked cache.

    ``ride`` (``_ride_step``, from ``forward_batched``): the rows past the B
    of ``pos`` are prompt tokens of one pool row. They share the
    projections, the rope and the cache write (one scatter for the step's
    B + t rows) with the decode rows, and their queries attend their own
    row's slab alone, causally, after the write."""
    B = pos.shape[0]

    def core(q, k, v, k_cache, v_cache, layer):
        if ride is None:
            cos = rope["cos"][pos][:, None, :]  # per-row angle: [B, 1, hs/2]
            sin = rope["sin"][pos][:, None, :]
        else:
            cos, sin = ride["cos"], ride["sin"]  # [B + t, 1, hs/2]
        q = apply_rope(q, cos, sin, cfg.rope_style)

        fused_kv = (layer is not None
                    and fused_rope_cache.engages(1, k_cache.dtype))
        # this step's rows go where they live, in the scan's donated carry,
        # before whichever attention reads them (write-before-attend)
        if ride is not None and not fused_kv:
            k = apply_rope(k, cos, sin, cfg.rope_style)
            k_cache, v_cache = _write_rows_at(k_cache, v_cache, k, v, layer,
                                              ride["rows"], ride["cols"])
        elif fused_kv:
            # DLLAMA_FUSE_ROPE_CACHE=1: rotate each row's K in-kernel and land
            # K/V at (layer, b, pos[b]) in one pass — bit-identical to the
            # scatter/DUS writes below, including their end-of-sequence clamp
            kd, vd, cd, sd = ((k, v, cos, sin) if ride is None
                              else (k[:B], v[:B], cos[:B], sin[:B]))
            k_cache, v_cache = fused_rope_cache.rope_cache_update_batched(
                kd, vd, cd, sd, k_cache, v_cache, pos, layer, cfg.rope_style)
            if ride is not None:  # the kernel knows the decode rows only
                k_cache, v_cache = _write_rows_at(
                    k_cache, v_cache,
                    apply_rope(k[B:], cos[B:], sin[B:], cfg.rope_style), v[B:],
                    layer, ride["rows"][B:], ride["cols"][B:])
        else:
            k = apply_rope(k, cos, sin, cfg.rope_style)
            if layer is None:
                # dense xs-scan: the carry IS this layer's slab
                with jax.named_scope("kv_slab_write"):
                    write = jax.vmap(
                        lambda c, kk, p: jax.lax.dynamic_update_slice_in_dim(
                            c, kk[None].astype(c.dtype), p, axis=0))
                    k_cache, v_cache = (write(k_cache, k, pos),
                                        write(v_cache, v, pos))
            else:
                # layer scan: the stacked cache rides the carry
                k_cache, v_cache = _write_kv_rows(
                    k_cache, v_cache, k[:, None], v[:, None], layer, pos)
        if ride is not None:
            q_r, q = q[B:], q[:B]

        slabs = None
        if layer is not None and flash:
            out = flash_decode.flash_decode_attention_batched(
                q, k_cache, v_cache, pos, layer)  # [B, local heads, hs]
        else:
            slabs = ((k_cache, v_cache) if layer is None
                     else _layer_slabs(k_cache, v_cache, layer))
            out = jax.vmap(
                lambda qb, ks, vs, p: gqa_attention(qb[None], ks, vs, p)[0]
            )(q, *slabs, pos)  # [B, local heads, hs]
        if ride is not None:
            if slabs is None:
                slabs = _layer_slabs(k_cache, v_cache, layer)
            row_k, row_v = (jax.lax.dynamic_index_in_dim(s, ride["row"], 0,
                                                         keepdims=False)
                            for s in slabs)
            out = jnp.concatenate(
                [out, gqa_attention(q_r, row_k, row_v, ride["start"])], axis=0)
        return out, k_cache, v_cache

    return core


def _verify_core(cfg: ModelConfig, rope: dict, pos):
    """The core of ``forward_batched_verify``: B sequences of T tokens, row
    b's at ``pos[b]..pos[b]+T``; q/k/v arrive [B, T, heads, hd] and the
    caches are the stacked [L, B, S, kv, hd]. Dense attention only (the
    batched flash kernel is one-token-per-row)."""
    def core(q, k, v, k_cache, v_cache, layer):
        T = q.shape[1]
        # per-row angles for positions pos[b]..pos[b]+T-1 (the table gather
        # clamps at seq_len-1; rows that close are emission-capped by the
        # caller's budgets before any clamped position could be emitted)
        ppos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        cos = rope["cos"][ppos][:, :, None, :]  # [B, T, 1, hs/2]
        sin = rope["sin"][ppos][:, :, None, :]
        q = apply_rope(q, cos, sin, cfg.rope_style)
        if fused_rope_cache.engages(T, k_cache.dtype):
            # DLLAMA_FUSE_ROPE_CACHE=1: rotate the draft rows' K in-kernel
            # and land K/V at (layer, b, pos[b]..pos[b]+T) in one pass —
            # bit-identical to the apply_rope + per-row slab writes below
            k_cache, v_cache = fused_rope_cache.rope_cache_update_verify(
                k, v, cos, sin, k_cache, v_cache, pos, layer, cfg.rope_style)
        else:
            k = apply_rope(k, cos, sin, cfg.rope_style)
            k_cache, v_cache = _write_kv_rows(k_cache, v_cache, k, v, layer,
                                              pos)
        out = jax.vmap(gqa_attention)(
            q, *_layer_slabs(k_cache, v_cache, layer), pos)  # [B, T, H, hd]
        return out, k_cache, v_cache

    return core


def _overlap_axis(tp_axis, ring: bool):
    from dllama_tpu.parallel.collectives import RingAxis

    return RingAxis(tp_axis) if (ring and tp_axis is not None) else tp_axis


def _check_overlap_split(cfg: ModelConfig, batch: int) -> int:
    """Static validation of the two-microbatch split; returns the cut row.

    MoE is rejected at trace time: ``_moe_decode_selected`` computes the
    selected-experts union over ALL rows (cap ``min(E, T*k)`` from the
    column maxima), so a row-split changes which experts run and the
    result would not be bit-identical to the monolithic step."""
    if cfg.is_moe:
        raise ValueError(
            "tp_overlap requires a dense FFN: the MoE selected-experts "
            "union spans all rows, so a microbatch split changes the "
            "expert schedule (not bit-identical)")
    if batch < 2:
        raise ValueError(f"tp_overlap needs batch >= 2 rows, got {batch}")
    return batch // 2


def _model_step(cfg: ModelConfig, params: dict, tokens, cache: dict, pos,
                  core_of, tp_axis=None, gather_logits: bool = True,
                  tp_compress: bool = False, tp_reduce=None,
                  index_scan: bool = True, split=None, ring: bool = True,
                  interleave: bool = True, n_logits=None,
                  last_pos=None) -> tuple:
    """The model step around ``core_of(pos) -> core``: embed, every layer
    (``_scan_layers`` over ``_layer``), the head. The T tokens of
    ``forward``, the B rows of ``forward_batched`` or the B x T of
    ``forward_batched_verify``; the pooled steps whole or, with ``split``
    (``_check_overlap_split``), as two microbatches ``[:split]`` and
    ``[split:]`` that advance inside one layer scan.

    The split is EXACT by construction: every op in the layer body is
    per-row (rmsnorm, rope, cache write, attention, sampling upstream), the
    matmuls compute each output row from the full K independent of the other
    rows, and the gathered chunk concatenation order is fixed — so splitting
    [B] into [B//2] + [B - B//2] permutes nothing. With ``ring`` each gather
    is the ``lax.ppermute`` chunk rotation
    (`parallel.collectives.RingAxis`): tp-1 small async hops instead of one
    fused blocking all-gather, giving the scheduler hop-granular boundaries
    to hide. ``ring=False`` keeps fused all-gathers and relies on XLA alone
    over the two-microbatch HLO. ``interleave``: both halves go through
    ``_layer`` together (the decode step); without it each half's layer runs
    whole before the other's (the verify step).

    ``last_pos``: see ``forward``.
    ``n_logits``: the classifier sees the first ``n_logits`` rows only."""
    layers = params["layers"]
    row = _row_mode(cfg, layers, tp_axis, tp_reduce)
    x = embed(cfg, params, tokens)
    if split is None:
        axis, xs, poss = tp_axis, [x], [pos]
        ks, vs = [cache["k"]], [cache["v"]]
    else:
        axis = _overlap_axis(tp_axis, ring)
        xs, poss = [x[:split], x[split:]], [pos[:split], pos[split:]]
        ks = [cache["k"][:, :split], cache["k"][:, split:]]
        vs = [cache["v"][:, :split], cache["v"][:, split:]]
    if row:  # residual rides the scan scattered
        xs = [_scatter(x, axis) for x in xs]
    cores = [core_of(p) for p in poss]
    red = tp_reduce if row else None

    def step(lp, layer, streams):
        if interleave:
            return _layer(cfg, lp, layer, streams, cores, axis, tp_compress,
                          red)
        return [_layer(cfg, lp, layer, [s], [c], axis, tp_compress, red)[0]
                for s, c in zip(streams, cores)]

    xs, ks, vs = zip(*_scan_layers(layers, cfg.n_layers, step,
                                   list(zip(xs, ks, vs)), index_scan))
    if split is None:
        x, new_k, new_v = xs[0], ks[0], vs[0]
        if n_logits is not None:
            x = x[:n_logits]  # the riders' rows end with the last layer's K/V
    else:
        if row:  # per-half fused final norm (rmsnorm is per-row, so exact)
            xs = [_final_norm(cfg, params, x, axis, tp_compress, row)
                  for x in xs]
        # rejoin, then a tail IDENTICAL to the whole step's: the final
        # rmsnorm, logits matmul and (plain fused) logits gather see the
        # same rows
        x = jnp.concatenate(xs, axis=0)
        new_k = jnp.concatenate(ks, axis=1)
        new_v = jnp.concatenate(vs, axis=1)
    logits = _head(cfg, params, x, tp_axis, gather_logits, tp_compress, row,
                   last_pos, normed=row and split is not None)
    return logits, {"k": new_k, "v": new_v}


def forward(
    cfg: ModelConfig,
    params: dict,
    rope: dict,
    tokens: jnp.ndarray,  # [T] int32
    cache: dict,  # {"k","v": [L, S, n_kv, hd]}
    pos,  # scalar int32: sequence position of tokens[0]
    tp_axis: str | None = None,
    gather_logits: bool = True,
    tp_compress: bool = False,
    allow_flash: bool = True,
    last_pos=None,
    tp_reduce=None,
) -> tuple:
    """Process T tokens starting at ``pos``. Returns (logits [T, vocab] f32, new cache).

    T==1 is the decode step; larger T is batched prefill (the reference feeds
    prompt tokens one at a time — batching them is the first TPU win).

    ``tp_axis``: when called inside shard_map over a tp mesh axis (the
    quantized-TP path, parallel.quant_tp), params/cache are local shards and
    activations are re-gathered after each output-sharded matmul. With
    ``gather_logits=False`` the classifier is replicated (vocab not divisible
    by tp) and the final gather is skipped.

    ``allow_flash=False``: the caller runs this forward under pjit with
    sharded dense params (runtime.generate's dense-mesh path). GSPMD cannot
    partition a Pallas custom call, so routing into the flash kernel there
    would compile it replicated against an all-gathered cache — the caller
    must pin the dense xs-scan instead.

    ``last_pos`` (traced scalar): compute the lm_head only at that row —
    logits come back [1, vocab]. Prefill reads exactly one row of logits,
    and at a 128k vocab the [bucket, vocab] classifier matmul dwarfs the
    one row actually consumed; every layer still processes (and caches) all
    T positions.

    ``tp_reduce`` (None | 'plain' | 'q80'): the row-parallel reduce path —
    wo/w2 are K-sharded (``quant_tp.row_shard_quant_leaf`` repacks), the
    residual rides the layer scan SCATTERED to [T, dim/tp], each sub-block's
    partial sums take a ring reduce-scatter (Q80-compressed hops when
    'q80'), and the fused norm+reduce epilogue folds residual-add + rmsnorm
    into the scattered shard so the one gather per sub-block carries the
    next matmul's already-normalized input. Quantized shard_map path only.

    A configuration with a ``layer_plan`` (layers of different kinds) runs
    ``models.layer_plan.forward``: runs of like layers, a cache tree by
    attention kind; single device only.
    """
    if cfg.layer_plan:
        from dllama_tpu.models import layer_plan

        if tp_axis is not None:
            cfg.refuse_for_plan("the tensor-parallel forward (--tp > 1)")
        return layer_plan.forward(cfg, params, rope, tokens, cache, pos,
                                  last_pos=last_pos)
    index_scan, flash = _scan_choice(params["layers"], allow_flash,
                                     tokens.shape[0], cache)
    return _model_step(
        cfg, params, tokens, cache, pos,
        lambda p: _solo_core(cfg, rope, p, flash), tp_axis, gather_logits,
        tp_compress, tp_reduce, index_scan, last_pos=last_pos)


def init_batch_cache(cfg: ModelConfig, batch: int, cache_dtype=jnp.float32,
                     seq_len: int = None) -> dict:
    """KV cache for ``batch`` independent sequences: [L, B, S, kv, hd].

    ``seq_len`` overrides the context length of the slab (default
    ``cfg.seq_len``) — the bucketed slot pools allocate short-context slabs
    for short rows; attention masks by ``pos``, so a slab shorter than the
    model context is exact as long as every row's pos stays inside it."""
    if cfg.layer_plan:
        from dllama_tpu.models import layer_plan

        return layer_plan.init_batch_cache(cfg, batch, cache_dtype, seq_len)
    S = cfg.seq_len if seq_len is None else seq_len
    shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_size)
    return {"k": jnp.zeros(shape, cache_dtype), "v": jnp.zeros(shape, cache_dtype)}


def forward_batched(
    cfg: ModelConfig,
    params: dict,
    rope: dict,
    tokens: jnp.ndarray,  # [B] int32 — one pending token per sequence
    cache: dict,  # {"k","v": [L, B, S, n_kv, hd]}
    pos: jnp.ndarray,  # [B] int32 — each sequence's own position
    tp_axis: str | None = None,
    gather_logits: bool = True,
    tp_compress: bool = False,
    allow_flash: bool = True,
    tp_reduce=None,
    live=None,
    ride=None,
) -> tuple:
    """One decode step for B independent sequences -> (logits [B, vocab], cache).

    The TPU throughput move the reference's batch=1 design cannot make
    (`/root/reference/src/tasks.cpp:199-210`): decode is weight-bandwidth
    bound, and the [B, K] activation streams every weight from HBM ONCE for
    all B sequences — ~B x aggregate tokens/s at nearly the single-stream
    step latency. Row b's math is exactly ``forward`` at T=1, pos[b]
    (greedy-tested per row); MoE routing/union selection is per-row already.
    ``tp_axis``: inside shard_map over a tp mesh (quant-TP batched serving,
    parallel.quant_tp.make_tp_forward_batched) — same gathers as ``forward``.
    ``allow_flash=False``: caller runs under pjit with sharded dense params
    (see ``forward``) — pin the dense xs-scan.
    ``tp_reduce``: the row-parallel wo/w2 reduce path, see ``forward``.
    ``live`` [B] bool (a ``layer_plan`` only, see ``forward``): the rows
    that are decoding; a third value then counts what they routed to
    (``layer_plan.forward_batched``).
    ``ride`` = ``(tokens [t], row, pos, n)``: the prompt rides the decode
    step. The first ``n`` of ``t`` further tokens are the next of the prompt
    that pool row ``row`` is waiting on, the first of them at position
    ``pos``. Their embedded rows are appended to the B decode rows, so every
    projection and the FFN (an MoE's expert scans too) stream their weights
    once for ``[B + t, K]``; rope, the cache write and attention are their
    own (``_rows_core``), and the classifier sees the B decode rows
    only: a prompt's last token is fed by its row's first decode step, so no
    rider needs logits. Single device, a uniform model; the row must not be
    decoding (its decode-side position pins at the slab's last slot, which
    no rider writes or attends).
    """
    if cfg.layer_plan:
        from dllama_tpu.models import layer_plan

        if tp_axis is not None:
            cfg.refuse_for_plan("the tensor-parallel forward (--tp > 1)")
        return layer_plan.forward_batched(cfg, params, rope, tokens, cache,
                                          pos, live=live)
    B = tokens.shape[0]
    if ride is not None:
        tokens = jnp.concatenate([tokens, ride[0]])
        ride = _ride_step(rope, pos, ride, cache["k"].shape[2])
    # same routing as `forward`: the stacked [L, B, S, kv, hd] cache stays
    # in the carry where each row reads only its own live prefix
    index_scan, flash = _scan_choice(params["layers"], allow_flash, 1, cache)
    return _model_step(
        cfg, params, tokens, cache, pos,
        lambda p: _rows_core(cfg, rope, p, flash, ride), tp_axis,
        gather_logits, tp_compress, tp_reduce, index_scan,
        n_logits=None if ride is None else B)


def forward_batched_overlap(
    cfg: ModelConfig,
    params: dict,
    rope: dict,
    tokens: jnp.ndarray,  # [B] int32 — one pending token per sequence
    cache: dict,  # {"k","v": [L, B, S, n_kv, hd]}
    pos: jnp.ndarray,  # [B] int32 — each sequence's own position
    tp_axis: str | None = None,
    gather_logits: bool = True,
    tp_compress: bool = False,
    allow_flash: bool = True,
    ring: bool = True,
    tp_reduce=None,
) -> tuple:
    """``forward_batched`` with the rows split into two microbatches whose
    per-layer schedules interleave — the TokenWeave-style compute/comm
    overlap for TP decode, EXACT by construction (``_model_step``,
    ``_layer``; tested bit-identical with the monolithic step across tp
    degrees with and without ``tp_compress``). MoE is rejected (see
    ``_check_overlap_split``).

    ``tp_reduce`` composes: each microbatch runs the row-parallel sequence
    inside the same scan. Row mode is NOT bit-identical to the monolithic
    gather path (split-K reassociation); it IS the same math as the
    non-overlap row-parallel step, microbatch-split exactly."""
    cfg.refuse_for_plan("the microbatch-overlap forward (--tp-overlap)")
    split = _check_overlap_split(cfg, tokens.shape[0])
    _, flash = _scan_choice(params["layers"], allow_flash, 1, cache,
                            overlap=True)
    return _model_step(
        cfg, params, tokens, cache, pos,
        lambda p: _rows_core(cfg, rope, p, flash), tp_axis, gather_logits,
        tp_compress, tp_reduce, split=split, ring=ring)


def forward_batched_verify(
    cfg: ModelConfig,
    params: dict,
    rope: dict,
    tokens: jnp.ndarray,  # [B, T] int32 — pending + draft rows per sequence
    cache: dict,  # {"k","v": [L, B, S, n_kv, hd]}
    pos: jnp.ndarray,  # [B] int32 — position of tokens[b, 0]
    tp_axis: str | None = None,
    gather_logits: bool = True,
    tp_compress: bool = False,
    tp_reduce=None,
) -> tuple:
    """T tokens for each of B independent sequences -> (logits [B, T, vocab]
    f32, cache): the BATCHED speculative-verify step. Row b's math is
    exactly ``forward`` at (T, pos[b]) — T=draft_len+1 candidate positions
    scored in one weight-streaming pass for ALL rows, composing the two
    bandwidth wins (batching shares the weight stream across sequences,
    speculation shares it across positions within each sequence).

    All matmuls run on the flattened [B*T, dim] activation (one kernel call
    per matrix — the quant kernels never see the batch structure); rope,
    cache writes, and attention are per-row (vmap over the pure attention).
    MoE routing on the flattened rows is exact: the selected-experts union
    caps at min(E, B*T*k). Dense attention only (``_verify_core``).
    ``tp_axis``: inside shard_map over a tp mesh
    (quant-TP, parallel.quant_tp.make_tp_verify_batched) — local heads +
    kv-shard caches, the same activation gathers as ``forward_batched``.
    """
    cfg.refuse_for_plan("the speculative verify step (--spec-draft)")
    return _model_step(
        cfg, params, tokens, cache, pos,
        lambda p: _verify_core(cfg, rope, p), tp_axis, gather_logits,
        tp_compress, tp_reduce)


def forward_batched_verify_overlap(
    cfg: ModelConfig,
    params: dict,
    rope: dict,
    tokens: jnp.ndarray,  # [B, T] int32 — pending + draft rows per sequence
    cache: dict,  # {"k","v": [L, B, S, n_kv, hd]}
    pos: jnp.ndarray,  # [B] int32 — position of tokens[b, 0]
    tp_axis: str | None = None,
    gather_logits: bool = True,
    tp_compress: bool = False,
    ring: bool = True,
    tp_reduce=None,
) -> tuple:
    """``forward_batched_verify`` with the rows split into two microbatches
    inside one layer scan — the spec-verify twin of
    ``forward_batched_overlap`` (same exactness argument: the layer is
    per-row throughout, the flattened [h*T, dim] matmuls compute each row
    from the full K, and ring-gather chunk order is fixed). ``tp_reduce``
    composes the same way: each half runs the row-parallel layer against the
    ring axis."""
    cfg.refuse_for_plan("the speculative verify step (--spec-draft)")
    split = _check_overlap_split(cfg, tokens.shape[0])
    return _model_step(
        cfg, params, tokens, cache, pos,
        lambda p: _verify_core(cfg, rope, p), tp_axis, gather_logits,
        tp_compress, tp_reduce, split=split, ring=ring, interleave=False)


def forward_train(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,
    rope: dict = None,
    mesh=None,
    sp_axis: str = "sp",
) -> jnp.ndarray:
    """Batched cache-free causal forward: tokens [B, T] -> logits [B, T, vocab].

    The inference path above is exact for the reference's decode-only scope;
    this variant exists for the training step (gradients need the whole
    sequence, no cache) and for throughput-style prefill. Same math per
    position — the attention just runs against the in-flight K/V of the same
    sequence instead of a cache.

    Long context: pass a ``mesh`` whose ``sp_axis`` has size > 1 and the
    attention runs as ring attention (``ops.ring_attention``) — each device
    keeps its sequence chunk of K/V, chunks rotate over ICI, per-device
    memory stays O(T / n_sp). Everything else (QKV/FFN matmuls, scan over
    layers) is unchanged; XLA keeps shardings the surrounding pjit chose.
    The sequence axis of ``tokens`` must be sharded over ``sp_axis`` in ring
    order (plain ``P(..., "sp")`` contiguous chunks).
    """
    cfg.refuse_for_plan("forward_train")
    use_ring = mesh is not None and mesh.shape.get(sp_axis, 1) > 1
    T = tokens.shape[1]
    x = embed(cfg, params, tokens)

    rope_t = rope if rope is not None else rope_tables(cfg)
    cos = rope_t["cos"][:T][None, :, None, :]  # [1, T, 1, hs/2]
    sin = rope_t["sin"][:T][None, :, None, :]

    ring = (mesh, sp_axis) if use_ring else None

    def layer_step(x, lp):
        return train_layer(cfg, lp, cos, sin, x, ring=ring), None

    x, _ = jax.lax.scan(layer_step, x, params["layers"])
    x = rmsnorm(x, params["rms_final"], cfg.norm_eps)
    logits = (x @ params["wcls"]).astype(jnp.float32)
    return logits * cfg.logit_scale if cfg.logit_scale != 1.0 else logits


def embed(cfg: ModelConfig, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup (+ Grok's input scale) in the compute dtype."""
    x = params["embedding"][tokens].astype(cfg.jax_dtype)
    if cfg.embedding_scale != 1.0:
        x = x * jnp.asarray(cfg.embedding_scale, cfg.jax_dtype)
    return x


def train_layer(
    cfg: ModelConfig,
    lp: dict,
    cos: jnp.ndarray,  # [1, T, 1, hs/2]
    sin: jnp.ndarray,
    x: jnp.ndarray,  # [B, T, dim]
    ring=None,  # (mesh, sp_axis) -> ring attention over that axis
) -> jnp.ndarray:
    """One cache-free causal transformer layer (the batched-training twin of
    the incremental ``_attention``/``_ffn_residual`` pair). Shared by the
    ``forward_train`` layer scan and the pipeline-parallel stage body."""
    B, T = x.shape[:2]
    group = cfg.n_heads // cfg.n_kv_heads

    xb = rmsnorm(x, lp["rms_att"], cfg.norm_eps)
    q = (xb @ lp["wq"]).reshape(B, T, cfg.n_heads, cfg.head_size)
    k = (xb @ lp["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.head_size)
    v = (xb @ lp["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.head_size)
    q = apply_rope(q, cos, sin, cfg.rope_style)
    k = apply_rope(k, cos, sin, cfg.rope_style)

    if ring is not None:
        from dllama_tpu.ops.ring_attention import ring_self_attention

        mesh, sp_axis = ring
        out = ring_self_attention(q, k, v, mesh, axis_name=sp_axis)
    else:
        causal = jnp.tril(jnp.ones((T, T), bool))
        qf = q.astype(jnp.float32).reshape(B, T, cfg.n_kv_heads, group, cfg.head_size)
        scores = jnp.einsum("btkgh,bskh->bkgts", qf, k.astype(jnp.float32))
        scores = scores / jnp.sqrt(jnp.float32(cfg.head_size))
        scores = jnp.where(causal[None, None, None], scores, jnp.float32(-1e30))
        att = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgts,bskh->btkgh", att, v.astype(jnp.float32))
        out = out.astype(x.dtype)
    out = out.reshape(B, T, cfg.dim)
    return _ffn_residual(cfg, lp, x, out @ lp["wo"])
