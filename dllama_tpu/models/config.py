"""Runtime model configuration, derived from the on-disk ModelSpec.

What a model IS lives here, each field defaulting to a uniform Llama's: the
norm (``norm``: "rms", or "layer", a LayerNorm without bias), the block
(``block``: "sequential", attention then FFN each behind its own norm, or
"parallel", ONE norm a layer feeding attention and FFN side by side), the
routers (``router``: "softmax", "sigmoid_bias", or "sigmoid" without a
bias), experts that are always on beside the routed ones (``shared_dim``,
``shared_scale``), which attention kinds of a layer plan rotate
(``rope_attention``) and a head tied to the embedding (``tied_embedding``).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from dllama_tpu.formats.spec import ArchType, HiddenAct, ModelSpec
from dllama_tpu.ops import rope as rope_ops

# Grok-1 scalings (`/root/reference/src/grok1-tasks.cpp:11-14,269-272`)
GROK_EMBEDDING_SCALE = 78.38367176906169
GROK_LOGIT_SCALE = 0.5773502691896257

#: user-facing dtype aliases (CLI / exporter flags -> numpy dtype names)
DTYPE_ALIASES = {"f8": "float8_e4m3fn"}


def resolve_dtype(name: str | None, default: str) -> jnp.dtype:
    """Flag string (or None) -> jnp.dtype, honoring DTYPE_ALIASES."""
    return jnp.dtype(DTYPE_ALIASES.get(name, name) or default)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # "llama" | "grok1" | "mixtral" | "mimo_v2" | "cohere2_moe" (the last two
    # need a layer_plan)
    arch: str
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    head_size: int
    kv_dim: int
    n_experts: int = 0
    n_active_experts: int = 0
    hidden_act: str = "silu"
    rope_theta: float = 10000.0
    # None = "derive from arch" for the four arch-implied fields below; an
    # explicitly passed value always wins (ablation configs stay expressible)
    rope_style: str | None = None
    embedding_scale: float | None = None
    logit_scale: float | None = None
    # grok1 re-normalizes after attention / moe output
    # (`/root/reference/src/grok1-tasks.cpp:16-41,244-262`)
    post_norms: bool | None = None
    norm_eps: float = 1e-5
    dtype: str = "float32"
    # --- layers of different kinds (all unset for a uniform model) ---------
    # ``layer_plan``: one (attention kind, FFN kind) pair a layer, attention
    # "full" | "window", FFN "dense" | "moe". With a plan the parameters are
    # one stack a kind (``plan_kinds``), the forward scans runs of like
    # layers (``plan_runs``) and the cache is a stack a attention kind. The
    # fields below say what the kinds differ in; ``n_kv_heads`` /
    # ``rope_theta`` / ``hidden_dim`` are then the full layers' KV heads and
    # theta and the EXPERTS' width (a dense layer's width is its planes').
    layer_plan: tuple | None = None
    n_kv_heads_window: int = 0  # KV heads of the window layers
    v_head_size: int = 0  # value heads' width (0: head_size)
    rope_dim: int = 0  # leading dims of a head that rotate (0: head_size)
    rope_theta_window: float = 0.0
    window: int = 0  # a window layer's query i sees keys i - window < j <= i
    window_sink: bool = False  # one learned score a head in the denominator
    value_scale: float = 1.0  # v <- value_scale * v after its projection
    # "softmax": softmax over all experts, the top k renormalised;
    # "sigmoid_bias": sigmoid scores, the top k of score + bias chosen, the
    # chosen experts' unbiased scores renormalised; "sigmoid": the same
    # without a bias (the scores choose)
    router: str = "softmax"
    # the experts this process holds of the ``n_experts`` the router scores:
    # [expert_first, expert_first + expert_count); 0 = all of them. An expert
    # layer then returns its held experts' part of the sum
    expert_first: int = 0
    expert_count: int = 0
    # experts that are always on beside the routed ones, held as ONE gated FFN
    # of their summed width (``shared_upgate`` / ``shared_down`` in the
    # kind's stack; 0: none), its output times ``shared_scale`` (1 / their
    # number where they are averaged)
    shared_dim: int = 0
    shared_scale: float = 1.0
    # "rms": w * x / sqrt(mean(x^2) + eps); "layer": the same of x - mean(x)
    # (a LayerNorm without bias)
    norm: str = "rms"
    # "sequential": x += att(norm_att(x)); x += ffn(norm_ffn(x)). "parallel":
    # h = norm_att(x); x += att(h) + ffn(h), one norm a layer (no ``rms_ffn``)
    block: str = "sequential"
    # the attention kinds of a layer plan whose q and k rotate
    rope_attention: tuple = ("full", "window")
    # the head is the embedding: ``_head`` multiplies by ``wcls`` where the
    # parameters bring the table's planes, else by ``embedding`` transposed
    tied_embedding: bool = False

    def __post_init__(self):
        # Arch-implied semantics, resolved from None sentinels: the Grok
        # scalings, post-norms and the half-split rotary ARE the arch
        # (`/root/reference/src/grok1-tasks.cpp`; from_spec hard-derives all
        # of them from arch alone), so an unset field follows the arch —
        # while an EXPLICIT value (even one equal to the generic default,
        # e.g. grok1 with logit_scale=1.0 in an ablation) is preserved
        # as passed. hidden_act is NOT derived: it is an independent
        # file-header field (formats.spec.HiddenAct) that a grok1
        # checkpoint can legitimately set to silu.
        grok = self.arch == "grok1"
        if self.rope_style is None:
            object.__setattr__(
                self, "rope_style",
                rope_ops.HALF if self.arch in ("grok1", "mixtral", "mimo_v2")
                else rope_ops.INTERLEAVED)
        if self.embedding_scale is None:
            object.__setattr__(
                self, "embedding_scale", GROK_EMBEDDING_SCALE if grok else 1.0)
        if self.logit_scale is None:
            object.__setattr__(
                self, "logit_scale", GROK_LOGIT_SCALE if grok else 1.0)
        if self.post_norms is None:
            object.__setattr__(self, "post_norms", grok)
        if self.layer_plan is not None:
            plan = tuple(tuple(k) for k in self.layer_plan)
            object.__setattr__(self, "layer_plan", plan)
            bad = [k for k in plan if len(k) != 2
                   or k[0] not in ("full", "window")
                   or k[1] not in ("dense", "moe")]
            if bad or len(plan) != self.n_layers:
                raise ValueError(
                    f"layer_plan needs n_layers={self.n_layers} pairs of "
                    f"(full|window, dense|moe), got {len(plan)} with {bad}")
            if self.plan_count("window") and self.window < 1:
                raise ValueError(
                    f"window layers need a window, got {self.window}")
        if self.router not in ("softmax", "sigmoid_bias", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r}")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.block not in ("sequential", "parallel"):
            raise ValueError(f"unknown block {self.block!r}")
        object.__setattr__(self, "rope_attention", tuple(self.rope_attention))
        if ((self.block == "parallel" or self.shared_dim
             or self.norm == "layer") and not self.layer_plan):
            raise ValueError(
                "a parallel block, a LayerNorm and shared experts are built "
                "for a model with a layer_plan (models/layer_plan.py)")
        if not 0 <= self.expert_first <= self.n_experts - self.expert_count:
            raise ValueError(
                f"held experts [{self.expert_first}, +{self.expert_count}) "
                f"lie outside the {self.n_experts} the router scores")

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_experts_held(self) -> int:
        """Experts in this process's stacks (all of them unless cut)."""
        return self.expert_count or self.n_experts

    @property
    def v_size(self) -> int:
        return self.v_head_size or self.head_size

    @property
    def ring_slots(self) -> int:
        """Slots of a window layer's ring: position p lives in slot
        ``p % ring_slots``. A forward over T tokens writes its T keys before
        it attends, so the ring must hold the window of the FIRST query
        beside them, ``window + T - 1`` slots: twice the window takes a
        piece of up to ``window + 1`` tokens."""
        return 2 * self.window

    @property
    def max_prefill_piece(self) -> int:
        """The longest forward a window layer's ring takes (0: any)."""
        if not self.layer_plan or not self.window:
            return 0
        return self.ring_slots - self.window + 1

    @property
    def plan_kinds(self) -> tuple:
        """The distinct (attention, FFN) kinds, in order of first use: one
        parameter stack each, named ``attention_ffn``."""
        return tuple(dict.fromkeys(self.layer_plan or ()))

    @property
    def plan_runs(self) -> tuple:
        """Runs of like layers: (kind, first index in the kind's parameter
        stack, first index in the attention kind's cache stack, count)."""
        runs: list = []
        seen_kind: dict = {}
        seen_att: dict = {}
        for kind in self.layer_plan or ():
            p, c = seen_kind.get(kind, 0), seen_att.get(kind[0], 0)
            if runs and runs[-1][0] == kind:
                runs[-1][3] += 1
            else:
                runs.append([kind, p, c, 1])
            seen_kind[kind], seen_att[kind[0]] = p + 1, c + 1
        return tuple(tuple(r) for r in runs)

    def plan_count(self, attention: str = None, ffn: str = None) -> int:
        return sum(1 for a, f in self.layer_plan or ()
                   if attention in (None, a) and ffn in (None, f))

    def plan_text(self) -> str:
        """The plan in a line: ``F.D W.E*4 F.E ...``."""
        return " ".join(
            f"{k[0][0].upper()}.{'D' if k[1] == 'dense' else 'E'}"
            + (f"*{n}" if n > 1 else "") for k, _, _, n in self.plan_runs)

    def refuse_for_plan(self, what: str) -> None:
        """One line for what is not built for layers of different kinds."""
        if self.layer_plan:
            raise ValueError(
                f"{what} is not built for a model whose layers differ in kind "
                f"(layer plan {self.plan_text()}): serve it with --tp 1 on "
                f"the slab pool, without --kv-pages and --spec-draft "
                f"(ROADMAP.md R5)")

    @classmethod
    def from_spec(cls, spec: ModelSpec, dtype: str = "float32") -> "ModelConfig":
        arch = {ArchType.LLAMA: "llama", ArchType.GROK1: "grok1", ArchType.MIXTRAL: "mixtral"}[
            spec.arch
        ]
        # Grok/Mixtral use the half-split (NeoX) rotary layout, Llama the
        # interleaved one (`/root/reference/src/transformer.cpp:398-402`).
        rope_style = rope_ops.HALF if arch in ("grok1", "mixtral") else rope_ops.INTERLEAVED
        return cls(
            arch=arch,
            dim=spec.dim,
            hidden_dim=spec.hidden_dim,
            n_layers=spec.n_layers,
            n_heads=spec.n_heads,
            n_kv_heads=spec.n_kv_heads,
            vocab_size=spec.vocab_size,
            seq_len=spec.seq_len,
            head_size=spec.head_size,
            kv_dim=spec.kv_dim,
            n_experts=spec.n_experts,
            n_active_experts=spec.n_active_experts,
            hidden_act="gelu" if spec.hidden_act == HiddenAct.GELU else "silu",
            rope_theta=spec.rope_theta,
            rope_style=rope_style,
            embedding_scale=GROK_EMBEDDING_SCALE if arch == "grok1" else 1.0,
            logit_scale=GROK_LOGIT_SCALE if arch == "grok1" else 1.0,
            post_norms=arch == "grok1",
            dtype=dtype,
        )
