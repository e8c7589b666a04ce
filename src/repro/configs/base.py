"""Config dataclasses for every architecture family in the framework.

Every assigned architecture (plus the paper's own Qwen2.5 models) is expressed
as a ``ModelConfig``.  Configs are plain frozen dataclasses: hashable, usable
as jit static args, and trivially serializable for checkpoint metadata.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Tuple

#: the attention kinds a layer of ``ModelConfig.layer_types`` may be
LAYER_KINDS = ("sliding", "full")


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings (None on dense models)."""

    num_experts: int
    top_k: int
    expert_d_ff: int
    # router jitter / load-balance aux loss weight (train only)
    router_aux_weight: float = 0.01
    # number of shared (always-on) experts; 0 for the assigned archs
    num_shared_experts: int = 0


class RopeConfig(NamedTuple):
    """Rotary settings of one attention kind.  Plain RoPE sets only
    ``theta``; YaRN (arXiv:2309.00071, Hugging Face ``rope_type: "yarn"``)
    sets the other five too.  A tuple, so a configuration file's numbers
    compare equal to it."""

    theta: float
    factor: Optional[float] = None            # YaRN: context scale
    original_max_len: Optional[int] = None    # YaRN: trained context
    beta_fast: Optional[float] = None         # YaRN: ramp ends, in
    beta_slow: Optional[float] = None         # rotations over that context
    attention_factor: Optional[float] = None  # YaRN: cos/sin scale


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD settings."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma recurrent-block settings."""

    lru_width: Optional[int] = None  # defaults to d_model
    conv1d_width: int = 4
    # local (sliding-window) attention width used in the attention blocks
    attention_window: int = 2048
    # block pattern: 1 attention block per `pattern` blocks (1:2 -> every 3rd? the
    # Griffin pattern is (recurrent, recurrent, attention) repeated)
    pattern: Tuple[str, ...] = ("recurrent", "recurrent", "attention")


@dataclass(frozen=True)
class EncoderConfig:
    """Auxiliary encoder (Whisper audio encoder / InternViT vision tower).

    The modality frontend is a STUB per the assignment: ``input_specs()``
    provides precomputed frame/patch embeddings of shape
    ``(batch, num_positions, d_model)``; the conv/patchify stems are not built.
    """

    num_layers: int
    d_model: int
    num_heads: int
    d_ff: int
    num_positions: int  # e.g. 1500 audio frames, or vision patches


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture's full configuration."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // num_heads
    # attention options
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None
    # per-layer attention kinds ("sliding" | "full"); None: every layer
    # alike, windowed by ``sliding_window`` if it is set
    layer_types: Optional[Tuple[str, ...]] = None
    # ((kind, RopeConfig), ...): the rotary settings of each kind in
    # ``layer_types``; a kind left out rotates by ``rope_theta``
    rope_by_kind: Tuple[Tuple[str, RopeConfig], ...] = ()
    # norm options
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    # sub-family configs
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    encoder: Optional[EncoderConfig] = None
    # dtype of parameters/activations for the production path
    dtype: str = "bfloat16"
    # citation per the assignment table
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        if self.num_heads == 0:  # attention-free (SSM family)
            return 0
        return self.d_model // self.num_heads

    @property
    def experts_per_token(self) -> int:
        """Routed experts per token (0 on a dense model)."""
        return self.moe.top_k if self.moe is not None else 0

    def layer_kind(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "full"

    def layer_window(self, i: int) -> Optional[int]:
        """Layer ``i``'s sliding window, None where it attends to all."""
        if self.layer_types is None:
            return self.sliding_window
        return self.sliding_window if self.layer_types[i] == "sliding" \
            else None

    def rope_for(self, kind: str) -> RopeConfig:
        return dict(self.rope_by_kind).get(kind, RopeConfig(self.rope_theta))

    @property
    def uniform_layers(self) -> bool:
        """True when every layer attends with the same window and rotary
        settings, so one static program serves them all."""
        n = self.num_layers
        return (len({self.layer_window(i) for i in range(n)}) <= 1
                and len({self.rope_for(self.layer_kind(i))
                         for i in range(n)}) <= 1)

    def __post_init__(self) -> None:
        if self.layer_types is not None:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(f"{self.name}: {len(self.layer_types)} "
                                 f"layer_types for {self.num_layers} layers")
            bad = set(self.layer_types) - set(LAYER_KINDS)
            if bad:
                raise ValueError(f"{self.name}: unknown layer kinds {bad}; "
                                 f"known: {LAYER_KINDS}")
            if "sliding" in self.layer_types and not self.sliding_window:
                raise ValueError(f"{self.name}: sliding layers need a "
                                 "sliding_window")

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """True if decode state is O(1) in sequence length (SSM / hybrid)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (embedding + layers + head)."""
        d, h = self.d_model, self.resolved_head_dim
        n_q = self.num_heads * h
        n_kv = self.num_kv_heads * h
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        per_layer = 0
        if self.family in ("dense", "moe", "vlm", "encdec", "hybrid"):
            attn = d * n_q + 2 * d * n_kv + n_q * d
            if self.qkv_bias:
                attn += n_q + 2 * n_kv
            per_layer += attn
            per_layer += 2 * d  # two rmsnorm weights
        if self.family == "moe":
            assert self.moe is not None
            e = self.moe
            per_layer += d * e.num_experts  # router
            per_layer += e.num_experts * 3 * d * e.expert_d_ff
            per_layer += e.num_shared_experts * 3 * d * e.expert_d_ff
        elif self.family == "ssm":
            assert self.ssm is not None
            s = self.ssm
            di = s.d_inner(d)
            nh = s.n_heads(d)
            per_layer += d * (2 * di + 2 * s.d_state + nh)  # in_proj(z,x,B,C,dt)
            per_layer += di * s.d_conv  # conv
            per_layer += nh * 2  # A_log, D
            per_layer += di * d  # out_proj
            per_layer += d  # norm
        elif self.family == "hybrid":
            # approximation: mix of recurrent and attention blocks
            per_layer += 3 * d * self.d_ff
        else:
            per_layer += 3 * d * self.d_ff  # SwiGLU gate/up/down
        if self.family == "hybrid":
            pass
        n = emb + head + self.num_layers * per_layer
        if self.encoder is not None:
            enc = self.encoder
            n += enc.num_layers * (4 * enc.d_model**2 + 2 * enc.d_model * enc.d_ff)
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        total = self.param_count()
        all_expert = self.num_layers * e.num_experts * 3 * self.d_model * e.expert_d_ff
        active_expert = self.num_layers * (e.top_k + e.num_shared_experts) * (
            3 * self.d_model * e.expert_d_ff
        )
        return total - all_expert + active_expert

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def reduced(cfg: ModelConfig, *, layers: int = 2, d_model: int = 64,
            heads: int = 4, kv_heads: Optional[int] = None, d_ff: int = 128,
            vocab: int = 256, experts: Optional[int] = None) -> ModelConfig:
    """Shrink a config to a CPU-smoke-test size, preserving family structure:
    a per-layer pattern keeps at least one whole period, and routing stays
    top-k of more experts than k."""
    period = _period(cfg.layer_types) if cfg.layer_types else ()
    layers = max(layers, len(period))
    kv = kv_heads if kv_heads is not None else max(1, min(cfg.num_kv_heads, heads // 2))
    kw = dict(
        num_layers=layers, d_model=d_model, num_heads=heads, num_kv_heads=kv,
        d_ff=d_ff, vocab_size=vocab, head_dim=d_model // heads, dtype="float32",
    )
    if cfg.moe is not None:
        n_e = experts if experts is not None else min(cfg.moe.num_experts, 8)
        kw["moe"] = MoEConfig(
            num_experts=n_e,
            top_k=min(cfg.moe.top_k, max(1, n_e // 2)),
            expert_d_ff=32,
            num_shared_experts=cfg.moe.num_shared_experts,
        )
    if cfg.ssm is not None:
        kw["ssm"] = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, chunk_size=32)
    if cfg.rglru is not None:
        kw["rglru"] = RGLRUConfig(lru_width=d_model, conv1d_width=4,
                                  attention_window=32, pattern=cfg.rglru.pattern)
    if cfg.encoder is not None:
        kw["encoder"] = EncoderConfig(num_layers=1, d_model=d_model, num_heads=heads,
                                      d_ff=d_ff, num_positions=16)
    if cfg.sliding_window is not None:
        kw["sliding_window"] = 32
    if period:
        kw["layer_types"] = tuple(period[i % len(period)]
                                  for i in range(layers))
    return cfg.replace(**kw)


def _period(kinds: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest prefix whose repetition gives ``kinds``."""
    for n in range(1, len(kinds) + 1):
        if all(k == kinds[i % n] for i, k in enumerate(kinds)):
            return kinds[:n]
    return kinds
