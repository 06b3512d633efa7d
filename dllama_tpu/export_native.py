"""Export a model for the native C++ PJRT runtime (``native/``).

Produces a directory the ``dllama-native`` CLI consumes:

* ``model.mlir`` — StableHLO bytecode of the jitted single-token decode step
  (``jax.export``), KV-cache args donated so the loop runs in-place on device.
* ``compile_options.pb`` — serialized ``xla.CompileOptionsProto`` for
  ``PJRT_Client_Compile``.
* ``executable.bin`` — (best effort) AOT-serialized executable from this
  process's backend; lets the native CLI skip compilation when the plugin
  version matches.
* ``weights.bin`` + ``manifest.txt`` — flat little-endian tensor blob and the
  text manifest describing every program argument (see native/src/manifest.h).
* ``tokenizer.t`` — copied next to the model when provided.

This replaces the reference's startup weight streaming over sockets
(`/root/reference/src/transformer.cpp:569-728`): the native runtime uploads
each tensor straight to device HBM.

Usage:
    python -m dllama_tpu.export_native --model m.m --tokenizer t.t --out dir/
"""

from __future__ import annotations

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np

_DTYPE_NAMES = {
    "float32": "f32",
    "bfloat16": "bf16",
    "float16": "f16",
    "int32": "i32",
    "uint32": "u32",
    "int8": "i8",
    "uint8": "u8",
}


def plugin_path() -> str:
    """The PJRT plugin the native runtime will ``dlopen``:
    ``DLLAMA_PJRT_PLUGIN`` when set, else the installed ``libtpu`` package's
    shared object. No plugin is an error, not a guessed path."""
    path = os.environ.get("DLLAMA_PJRT_PLUGIN")
    if path:
        return path
    try:
        import libtpu
    except ImportError as e:
        raise RuntimeError(
            "no PJRT plugin for the native runtime: set DLLAMA_PJRT_PLUGIN "
            "to a plugin .so or install the libtpu package") from e
    return libtpu.get_library_path()


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts) or "leaf"


def plugin_options() -> tuple:
    """(plugin_path, [(type_char, name, value_str)]) for the current backend.

    Reads the registered PJRT plugin's client-creation options out of JAX's
    backend factory so the native runtime can create an identical client.
    Returns defaults when no C-API plugin is registered (pure-CPU test runs).
    """
    plugin = plugin_path()
    opts = []
    try:
        from jax._src import xla_bridge as xb

        reg = xb._backend_factories.get("tpu")
        keywords = (getattr(reg.factory, "keywords", None) or {}) if reg else {}
        for key, val in (keywords.get("options") or {}).items():
            if isinstance(val, bool):
                opts.append(("b", key, "1" if val else "0"))
            elif isinstance(val, int):
                opts.append(("i", key, str(val)))
            elif isinstance(val, float):
                opts.append(("f", key, repr(val)))
            elif isinstance(val, str) and val and " " not in val:
                opts.append(("s", key, val))
            else:
                # manifest records are space-separated scalars; anything
                # else can't round-trip — make the omission visible
                print(
                    f"⚠️  plugin option {key!r}={val!r} not representable "
                    "in the manifest; dropped (native client creation may "
                    "need it via env)"
                )
    except (ImportError, AttributeError) as e:
        # jax internals moved (xla_bridge is private API): fall back to a
        # bare client, but say so — silent loss of plugin options produces
        # a native client that can't reach the device
        print(f"⚠️  could not read PJRT plugin options from jax ({e}); "
              "native client will be created with defaults")
    return plugin, opts


#: decode steps fused into one device program in the native chunked loop
LOOP_STEPS = 32

#: prompt positions one prefill Execute consumes (clamped to seq_len) — the
#: native prompt phase costs ceil(T/bucket) dispatches instead of T
PREFILL_BUCKET = 64


def export_model(
    cfg,
    params: dict,
    out_dir: str,
    *,
    tokenizer_path: str = None,
    cache_dtype=jnp.bfloat16,
    model_name: str = "llama",
    aot: bool = True,
) -> str:
    """Export ``llama.forward`` for the native runtime. Two programs:

    * ``model.mlir`` — one decode step (token in, logits out); used for
      prompt feeding and the tail of a generation.
    * ``model_loop.mlir`` — ``LOOP_STEPS`` decode steps fused into ONE device
      program (lax.scan, sampling on device via runtime.sampler), so the
      native loop dispatches once per chunk and pulls ``LOOP_STEPS`` token
      ids (4 bytes each) instead of a full f32 logits vector per token —
      the north star's "no per-token host round-trips" for the C++ path,
      matching the Python engine's fused ``_decode_loop``.
    * ``model_prefill.mlir`` — a ``PREFILL_BUCKET``-token batched prompt
      step (traced real count ``n``), the native twin of the Python
      engine's bucketed prefill: long prompts cost ceil(T/bucket)
      dispatches instead of one per position (the reference feeds prompts
      one position at a time, `/root/reference/src/apps/dllama/dllama.cpp:43-55`).

    Returns ``out_dir``.
    """
    from jax import export as jax_export

    from dllama_tpu.models import llama
    from dllama_tpu.runtime.sampler import sample_dynamic

    os.makedirs(out_dir, exist_ok=True)
    rope = llama.rope_tables(cfg)

    weights = {"params": params, "rope": rope}
    flat, treedef = jax.tree_util.tree_flatten_with_path(weights)
    names = [_leaf_name(path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]

    cache = llama.init_cache(cfg, cache_dtype)

    def step(weight_leaves, k_cache, v_cache, token, pos):
        wts = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(weights), weight_leaves
        )
        logits, new_cache = llama.forward(
            cfg, wts["params"], wts["rope"], token,
            {"k": k_cache, "v": v_cache}, pos,
        )
        return logits[0], new_cache["k"], new_cache["v"]

    def loop(weight_leaves, k_cache, v_cache, token, pos, temp, topp, seed):
        wts = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(weights), weight_leaves
        )

        def body(carry, _):
            k_c, v_c, tok, p, key = carry
            key, sub = jax.random.split(key)
            logits, new_cache = llama.forward(
                cfg, wts["params"], wts["rope"], tok, {"k": k_c, "v": v_c}, p
            )
            nxt = sample_dynamic(logits[0], sub, temp, topp)
            return (new_cache["k"], new_cache["v"], nxt[None], p + 1, key), nxt

        key0 = jax.random.PRNGKey(seed)
        (k_c, v_c, _, _, _), toks = jax.lax.scan(
            body, (k_cache, v_cache, token, pos, key0), length=LOOP_STEPS
        )
        return toks, k_c, v_c

    prefill_bucket = min(PREFILL_BUCKET, cfg.seq_len)

    def prefill(weight_leaves, k_cache, v_cache, tokens, pos, n_tokens):
        wts = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(weights), weight_leaves
        )
        logits, new_cache = llama.forward(
            cfg, wts["params"], wts["rope"], tokens,
            {"k": k_cache, "v": v_cache}, pos,
        )
        # only the last REAL position's logits are meaningful (pad rows are
        # garbage); n_tokens is traced so one compile serves every prompt
        # length within the bucket
        last = jax.lax.dynamic_index_in_dim(logits, n_tokens - 1, keepdims=False)
        return last, new_cache["k"], new_cache["v"]

    token = jnp.zeros((1,), jnp.int32)
    pos = jnp.int32(0)
    temp, topp, seed = jnp.float32(0.8), jnp.float32(0.9), jnp.int32(1)

    def check_kept(exp, n_args, what):
        kept = getattr(exp, "module_kept_var_idx", None)
        if kept is not None and len(kept) != n_args:
            raise RuntimeError(
                f"exported {what} dropped arguments ({len(kept)}/{n_args} "
                "kept); the manifest arg order would be wrong"
            )

    jitted = jax.jit(step, donate_argnums=(1, 2))
    exp = jax_export.export(jitted)(leaves, cache["k"], cache["v"], token, pos)
    check_kept(exp, len(leaves) + 4, "step module")
    with open(os.path.join(out_dir, "model.mlir"), "wb") as f:
        f.write(exp.mlir_module_serialized)

    jitted_loop = jax.jit(loop, donate_argnums=(1, 2))
    loop_args = (leaves, cache["k"], cache["v"], token, pos, temp, topp, seed)
    exp_loop = jax_export.export(jitted_loop)(*loop_args)
    check_kept(exp_loop, len(leaves) + 7, "loop module")
    with open(os.path.join(out_dir, "model_loop.mlir"), "wb") as f:
        f.write(exp_loop.mlir_module_serialized)

    jitted_prefill = jax.jit(prefill, donate_argnums=(1, 2))
    prefill_args = (
        leaves, cache["k"], cache["v"],
        jnp.zeros((prefill_bucket,), jnp.int32), pos, jnp.int32(1),
    )
    exp_prefill = jax_export.export(jitted_prefill)(*prefill_args)
    check_kept(exp_prefill, len(leaves) + 5, "prefill module")
    with open(os.path.join(out_dir, "model_prefill.mlir"), "wb") as f:
        f.write(exp_prefill.mlir_module_serialized)

    from jax._src.lib import xla_client as xc

    with open(os.path.join(out_dir, "compile_options.pb"), "wb") as f:
        f.write(xc.CompileOptions().SerializeAsString())

    executable_file = ""
    loop_executable_file = ""
    prefill_executable_file = ""
    if aot:
        try:
            compiled = jitted.lower(
                leaves, cache["k"], cache["v"], token, pos
            ).compile()
            ser = compiled.runtime_executable().serialize()
            with open(os.path.join(out_dir, "executable.bin"), "wb") as f:
                f.write(ser)
            executable_file = "executable.bin"
            ser_loop = (
                jitted_loop.lower(*loop_args).compile().runtime_executable().serialize()
            )
            with open(os.path.join(out_dir, "executable_loop.bin"), "wb") as f:
                f.write(ser_loop)
            loop_executable_file = "executable_loop.bin"
            ser_prefill = (
                jitted_prefill.lower(*prefill_args).compile()
                .runtime_executable().serialize()
            )
            with open(os.path.join(out_dir, "executable_prefill.bin"), "wb") as f:
                f.write(ser_prefill)
            prefill_executable_file = "executable_prefill.bin"
        except Exception as e:  # serialization is backend-dependent
            print(f"⚠️  AOT executable serialization unavailable: {e}")

    # Flat weight blob + manifest records.
    lines = [
        "dllama_native 1",
        f"model {model_name}",
        f"vocab_size {cfg.vocab_size}",
        f"seq_len {cfg.seq_len}",
    ]
    plugin, opts = plugin_options()
    lines.append(f"plugin {plugin}")
    for t, k, v in opts:
        lines.append(f"option {t} {k} {v}")
    lines += [
        "weights_file weights.bin",
        "mlir_file model.mlir",
        "compile_options_file compile_options.pb",
    ]
    if executable_file:
        lines.append(f"executable_file {executable_file}")
    # loop program args = the step program's inputs (same order) followed by
    # temp f32[], topp f32[], seed i32[]; outputs = tokens i32[loop_steps]
    # then the caches (same order as the cache inputs)
    lines.append("loop_mlir_file model_loop.mlir")
    lines.append(f"loop_steps {LOOP_STEPS}")
    if loop_executable_file:
        lines.append(f"loop_executable_file {loop_executable_file}")
    # prefill program args = the step program's inputs with the token slot
    # widened to i32[prefill_bucket], plus one trailing scalar n i32[];
    # outputs = last real position's logits then the caches
    lines.append("prefill_mlir_file model_prefill.mlir")
    lines.append(f"prefill_bucket {prefill_bucket}")
    if prefill_executable_file:
        lines.append(f"prefill_executable_file {prefill_executable_file}")

    def dtype_name(arr) -> str:
        return _DTYPE_NAMES[str(arr.dtype)]

    def dims_str(shape) -> str:
        return " ".join([str(len(shape))] + [str(d) for d in shape])

    offset = 0
    with open(os.path.join(out_dir, "weights.bin"), "wb") as f:
        for name, leaf in zip(names, leaves):
            arr = np.asarray(leaf)
            data = arr.tobytes()
            lines.append(
                f"input {name} weight {dtype_name(arr)} {offset} {len(data)} "
                f"{dims_str(arr.shape)}"
            )
            f.write(data)
            offset += len(data)

    for cname, carr in (("cache.k", cache["k"]), ("cache.v", cache["v"])):
        lines.append(
            f"input {cname} cache {dtype_name(carr)} -1 {carr.nbytes} "
            f"{dims_str(carr.shape)}"
        )
    lines.append("input token token i32 -1 4 1 1")
    lines.append("input pos pos i32 -1 4 0")

    lines.append(f"output logits logits f32 1 {cfg.vocab_size}")
    for cname, carr in (("cache.k", cache["k"]), ("cache.v", cache["v"])):
        lines.append(f"output {cname} cache {dtype_name(carr)} {dims_str(carr.shape)}")

    with open(os.path.join(out_dir, "manifest.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")

    if tokenizer_path:
        shutil.copy(tokenizer_path, os.path.join(out_dir, "tokenizer.t"))
    return out_dir


def export_sharded_step(cfg, params: dict, mesh, out_path: str,
                        cache_dtype=jnp.bfloat16) -> str:
    """Multi-device export groundwork: serialize the TENSOR-PARALLEL decode
    step over ``mesh`` with its shardings baked in (``jax.export`` records
    per-argument HLO shardings and the device-count contract).

    The native runtime does not execute multi-device programs yet — this is
    the forward-half of that path: the serialized artifact deserializes with
    ``jax.export.deserialize`` and runs on any ``n`` same-shape devices (the
    dry-run test drives it on the virtual CPU mesh). The reference's
    equivalent is the root/worker program pair streamed over sockets
    (`/root/reference/src/transformer.cpp:569-728`); here one SPMD program
    carries the partitioning in its sharding annotations.

    Uses the dense pjit forward (XLA auto-partitions it; the shard_map quant
    path needs per-device Pallas custom calls, which land with native
    multi-device execution). Returns ``out_path``.
    """
    from jax import export as jax_export
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dllama_tpu.models import llama
    from dllama_tpu.parallel.sharding import cache_spec, shard_params

    sharded = shard_params(params, mesh, cfg)
    rope = llama.rope_tables(cfg)
    cache_sh = NamedSharding(mesh, cache_spec())
    cache = jax.jit(
        lambda: llama.init_cache(cfg, cache_dtype),
        out_shardings={"k": cache_sh, "v": cache_sh},
    )()
    repl = NamedSharding(mesh, P())

    def step(params, rope, k_cache, v_cache, token, pos):
        # allow_flash=False: dense pjit program — a Pallas call would not
        # auto-partition (same constraint as runtime.generate's dense path)
        logits, new_cache = llama.forward(
            cfg, params, rope, token, {"k": k_cache, "v": v_cache}, pos,
            allow_flash=False,
        )
        return logits[0], new_cache["k"], new_cache["v"]

    jitted = jax.jit(step, donate_argnums=(2, 3))
    exp = jax_export.export(jitted)(
        sharded, jax.device_put(rope, repl), cache["k"], cache["v"],
        jax.device_put(jnp.zeros((1,), jnp.int32), repl),
        jax.device_put(jnp.int32(0), repl),
    )
    if exp.nr_devices != mesh.size:
        raise RuntimeError(
            f"export recorded {exp.nr_devices} devices, mesh has {mesh.size}"
        )
    with open(out_path, "wb") as f:
        f.write(exp.serialize())
    return out_path


def main(argv=None) -> int:
    import argparse

    from dllama_tpu.formats.weights import WeightFileReader
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig, resolve_dtype

    p = argparse.ArgumentParser(prog="dllama_tpu.export_native")
    p.add_argument("--model", required=True, help=".m weight file")
    p.add_argument("--tokenizer", default=None, help=".t tokenizer file")
    p.add_argument("--out", required=True, help="output directory")
    p.add_argument("--dtype", default="bfloat16", choices=["float32", "bfloat16"])
    p.add_argument(
        "--cache-dtype", default="bfloat16",
        choices=["float32", "bfloat16", "f8"],
        help="KV cache element type baked into the exported programs "
        "(f8 = float8_e4m3fn, half the cache HBM of bf16)",
    )
    p.add_argument("--no-aot", action="store_true", help="skip executable.bin")
    p.add_argument(
        "--tp", type=int, default=1,
        help="also export a tensor-parallel decode step over a tp-device "
        "mesh (model_tpN.mlir; groundwork — the native runtime executes "
        "single-device programs today)",
    )
    args = p.parse_args(argv)

    with WeightFileReader(args.model) as reader:
        cfg = ModelConfig.from_spec(reader.spec, dtype=args.dtype)
        params = llama.params_from_reader(reader, cfg)
    cache_dtype = resolve_dtype(args.cache_dtype, default="bfloat16")
    export_model(
        cfg,
        params,
        args.out,
        tokenizer_path=args.tokenizer,
        cache_dtype=cache_dtype,
        aot=not args.no_aot,
    )
    if args.tp > 1:
        from dllama_tpu.parallel.mesh import tp_mesh

        name = f"model_tp{args.tp}.mlir"
        export_sharded_step(
            cfg, params, tp_mesh(args.tp), os.path.join(args.out, name),
            cache_dtype=cache_dtype,
        )
        with open(os.path.join(args.out, "manifest.txt"), "a") as f:
            f.write(f"tp_mlir_file {name}\ntp_degree {args.tp}\n")
        print(f"📦 wrote {name} (tp={args.tp} sharded step)")
    print(f"📦 exported to {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
