"""A throwaway second family, for the harness's own tests: a dense decoder
whose configuration speaks its own key names (``width``, ``depth``, ...),
with its own init program, its own plain reference (``reference.py``, numpy)
and its own counts. It maps onto the program's ``arch: llama`` path, since
the program is not the benchmark's to change; what it proves is that the
harness reaches a model only through ``families.load``: the tests copy this
directory to ``benchmarks/families/toy/`` of a scratch copy of the benchmark
and touch no file that was there. It cannot shard (no ``make_sharded_params``).
"""

from __future__ import annotations

Q40_BYTES_PER_WEIGHT = 0.625
N_FIXED_PIECES = 259  # <unk> <s> </s> and the byte pieces never win


def sizes(conf: dict) -> dict:
    hd = conf["width"] // conf["heads"]
    return {"L": conf["depth"], "D": conf["width"], "F": conf["ffn_width"],
            "KV": conf["kv_heads"] * hd, "V": conf["vocab"], "hd": hd}


def model_config(conf: dict, server: dict):
    from dllama_tpu.models.config import ModelConfig

    s = sizes(conf)
    return ModelConfig(
        arch="llama", dim=s["D"], hidden_dim=s["F"], n_layers=s["L"],
        n_heads=conf["heads"], n_kv_heads=conf["kv_heads"], vocab_size=s["V"],
        seq_len=conf["context"], head_size=s["hd"], kv_dim=s["KV"],
        n_experts=0, n_active_experts=0, hidden_act="silu",
        rope_theta=float(conf["rope_base"]), norm_eps=float(conf["eps"]),
        dtype=server["dtype"])


def make_planes(conf: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    s = sizes(conf)

    def init(key):
        keys = iter(jax.random.split(key, 24))

        def plane(k_in, out, prefix=()):
            kp = -(-k_in // 512) * 512  # the program packs K to a multiple
            w = jax.random.bits(next(keys), (*prefix, kp // 2, out), jnp.uint8)
            lo, hi = w & 0xF, w >> 4  # nibble 0 (q = -8) becomes q = 0: mean 0
            w = (jnp.where(hi == 0, jnp.uint8(8), hi) << 4) \
                | jnp.where(lo == 0, jnp.uint8(8), lo)
            scale = lambda: 0.004 * jax.random.uniform(
                next(keys), (*prefix, kp // 64, out), jnp.float32)
            return {"w": w, "s": scale(), "s2": scale()}

        norm = lambda shape: 1.0 + 0.1 * jax.random.normal(next(keys), shape)
        L, D, F = s["L"], s["D"], s["F"]
        wcls = plane(D, s["V"])
        live = (jnp.arange(s["V"]) >= N_FIXED_PIECES).astype(jnp.float32)
        wcls.update(s=wcls["s"] * live, s2=wcls["s2"] * live)
        return {"embedding": 0.02 * jax.random.normal(next(keys), (s["V"], D)),
                "rms_final": norm((D,)), "wcls": wcls,
                "layers": {"wqkv": plane(D, D + 2 * s["KV"], (L,)),
                           "wo": plane(D, D, (L,)), "w13": plane(D, 2 * F, (L,)),
                           "w2": plane(F, D, (L,)), "rms_att": norm((L, D)),
                           "rms_ffn": norm((L, D))}}

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(init)(key)


def wrap_planes(planes: dict, conf: dict) -> dict:
    from dllama_tpu.ops.qmatmul import QuantTensor

    def leaf(name, v):
        if not isinstance(v, dict):
            return v
        k = conf["ffn_width"] if name == "w2" else conf["width"]
        return QuantTensor(w=v["w"], s=v["s"], s2=v["s2"], kind="q40", k_logical=k)

    out = {k: leaf(k, v) for k, v in planes.items() if k != "layers"}
    out["layers"] = {k: leaf(k, v) for k, v in planes["layers"].items()}
    return out


def compare(planes: dict, conf: dict, samples: list, stand_ins=()) -> dict:
    from . import reference

    return reference.compare(planes, conf, samples, stand_ins)


# the counts: what the mathematics needs

def weights_per_token(conf: dict) -> int:
    s = sizes(conf)
    layer = s["D"] * (2 * s["D"] + 2 * s["KV"]) + 3 * s["D"] * s["F"]
    return s["L"] * layer + s["D"] * s["V"]


def flops_per_token(conf: dict, context: float) -> float:
    s = sizes(conf)
    return 2.0 * weights_per_token(conf) + 4.0 * s["D"] * context * s["L"]


def plane_bytes_per_launch(conf: dict, rows: float) -> float:
    return weights_per_token(conf) * Q40_BYTES_PER_WEIGHT


def kv_read_bytes(conf: dict, context: float) -> float:
    """This family states its cache in float32: 4 bytes an element."""
    s = sizes(conf)
    return context * 2 * s["L"] * s["KV"] * 4


def launch_least_seconds(conf: dict, rows: float, peaks: dict) -> float:
    w = weights_per_token(conf)
    return max(w * Q40_BYTES_PER_WEIGHT / peaks["hbm_bytes_per_s"],
               2.0 * rows * w / peaks["bf16_flops_per_s"])


def classifier_least_seconds(conf: dict, rows: float, peaks: dict) -> float:
    """One kernel's own numerator: the classifier's plane, read once."""
    s = sizes(conf)
    return s["D"] * s["V"] * Q40_BYTES_PER_WEIGHT / peaks["hbm_bytes_per_s"]


def resident_bytes(conf: dict) -> float:
    s = sizes(conf)
    return (weights_per_token(conf) * Q40_BYTES_PER_WEIGHT
            + 4.0 * (s["V"] * s["D"] + (2 * s["L"] + 1) * s["D"]))


def rehearsal(conf: dict) -> list:
    return []  # nothing of it is compiled for a chip
