"""Architecture/config system.

Every assigned architecture is expressed as an ``ArchConfig``. The model substrate
(`repro.models`) consumes these; the launchers select them via ``--arch <id>``.

Families:
  dense   — decoder-only transformer (GQA, SwiGLU)
  moe     — decoder-only transformer with top-k mixture-of-experts FFNs
  hybrid  — Mamba-2 backbone with a shared attention block every `attn_every` layers
  hybrid_moe — per-layer Mamba-2 or attention mixer (``layer_types``), each
            followed by a held-expert MoE FFN with a shared expert
            (granite-4.0-h)
  ssm     — pure Mamba-2 (attention-free)
  vlm     — dense LM backbone consuming stub patch embeddings + text tokens
  audio   — encoder-decoder transformer (Whisper-style); conv/mel frontend stubbed
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str      # dense | moe | hybrid | hybrid_moe | ssm | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int                   # query heads (0 for attention-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    activation: str = "swiglu"       # swiglu | gelu
    norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    max_seq_len: int = 131_072
    dtype: str = "bfloat16"          # weights and KV cache when served
    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim (0 -> d_ff)
    moe_every: int = 1               # MoE FFN every k-th layer (others dense)
    moe_shared_expert: bool = False  # always-on shared expert on MoE layers
    capacity_factor: float = 1.25
    moe_held: int = 0                # experts this chip holds (0 -> all)
    moe_shared_d_ff: int = 0         # shared expert's own width (hybrid_moe)
    # --- SSM (Mamba-2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # --- hybrid ---
    attn_every: int = 0              # shared attn block every k layers (0 = never)
    # --- hybrid_moe: the mixer of each layer, "mamba" or "attention" ---
    layer_types: tuple = ()
    # --- muP scalars (hybrid_moe; 1 and 0 leave a block as it was) ---
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0  # softmax scale (0 -> 1/sqrt(head_dim))
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0      # logits are divided by it
    # --- encoder-decoder (audio) ---
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_seq_len: int = 0         # whisper: 1500 frames
    # --- vlm ---
    num_patches: int = 0             # stub patch embeddings prepended to text

    # ------------------------------------------------------------------ derived
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def d_inner(self) -> int:
        """Mamba-2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def sub_quadratic(self) -> bool:
        """True when decode cost/state is O(1)-ish in context (SSM/hybrid)."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def uses_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_held(self) -> int:
        return self.moe_held or self.num_experts

    @property
    def mixers(self) -> tuple:
        """The mixer of each of the ``num_layers`` layers (hybrid_moe)."""
        return tuple(self.layer_types[:self.num_layers])

    @property
    def period(self) -> int:
        """Length of the shortest repeating unit of ``layer_types``."""
        t = self.layer_types
        return next(p for p in range(1, len(t) + 1)
                    if all(t[i] == t[i % p] for i in range(len(t))))

    # ------------------------------------------------------------ param counts
    def param_count(self) -> int:
        """Analytic parameter count (logical, unpadded). Used by tests + roofline."""
        d, v = self.d_model, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.family == "hybrid_moe":
            return emb + d + sum(self._hybrid_moe_layer(m)
                                 for m in self.mixers)
        if self.family in ("ssm", "hybrid"):
            n = emb
            di, N, G, H = self.d_inner, self.ssm_state, self.ssm_groups, self.ssm_heads
            in_proj = d * (2 * di + 2 * G * N + H)
            conv = self.ssm_conv_width * (di + 2 * G * N)
            out_proj = di * d
            per_layer = in_proj + conv + out_proj + di + 2 * H + d  # norm+A,D,dt_bias
            n += self.num_layers * per_layer
            if self.family == "hybrid" and self.attn_every:
                hd = self.resolved_head_dim
                qk = d * self.num_heads * hd + d * self.num_kv_heads * hd
                vo = d * self.num_kv_heads * hd + self.num_heads * hd * d
                mlp = 3 * d * self.d_ff
                n += qk + vo + mlp + 2 * d  # one shared block
            n += d  # final norm
            return n
        hd = self.resolved_head_dim
        attn = (d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd
                + self.num_heads * hd * d)
        if self.qkv_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * hd
        mlp_mult = 3 if self.activation == "swiglu" else 2
        dense_mlp = mlp_mult * d * self.d_ff
        n = emb + d  # embeddings + final norm
        if self.is_encoder_decoder:
            enc_layer = attn + dense_mlp + 2 * d
            dec_layer = 2 * attn + dense_mlp + 3 * d  # self + cross
            n += self.encoder_layers * enc_layer + self.num_layers * dec_layer
            n += self.encoder_seq_len * 0  # sinusoidal enc pos: not learned
            n += self.max_decoder_pos * d  # learned decoder positions
            return n
        for layer in range(self.num_layers):
            n += attn + 2 * d
            if self.uses_moe and layer % self.moe_every == 0:
                e_ff = self.moe_d_ff or self.d_ff
                n += self.num_experts * mlp_mult * d * e_ff + d * self.num_experts
                if self.moe_shared_expert:
                    n += mlp_mult * d * e_ff
            else:
                n += dense_mlp
        return n

    def _hybrid_moe_layer(self, mixer: str) -> int:
        """One hybrid_moe layer: its mixer, its norms and its MoE FFN (all
        ``num_experts``, the router and the shared expert)."""
        d, hd = self.d_model, self.resolved_head_dim
        if mixer == "mamba":
            di, N, G, H = (self.d_inner, self.ssm_state, self.ssm_groups,
                           self.ssm_heads)
            conv_ch = di + 2 * G * N
            mix = (d * (2 * di + 2 * G * N + H) + (self.ssm_conv_width + 1)
                   * conv_ch + di * d + di + 3 * H)
        else:
            mix = 2 * d * (self.num_heads + self.num_kv_heads) * hd
        e_ff = self.moe_d_ff or self.d_ff
        moe = (d * self.num_experts + self.num_experts * 3 * d * e_ff
               + 3 * d * self.moe_shared_d_ff)
        return mix + moe + 2 * d

    @property
    def max_decoder_pos(self) -> int:
        # Learned decoder positions sized to the assigned shape set (the real
        # whisper-base table is 448; the assigned prefill_32k cell requires
        # 32k — the +17M params are recorded in DESIGN.md §8).
        return max(self.max_seq_len, 4096) if self.is_encoder_decoder else 0

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts). Drives 6·N_active·D."""
        if not self.uses_moe:
            return self.param_count()
        d = self.d_model
        e_ff = self.moe_d_ff or self.d_ff
        mlp_mult = 3 if self.activation == "swiglu" else 2
        n_moe_layers = len([l for l in range(self.num_layers) if l % self.moe_every == 0])
        inactive = n_moe_layers * (self.num_experts - self.num_experts_per_tok) \
            * mlp_mult * d * e_ff
        return self.param_count() - inactive

    # ------------------------------------------------------------------ reduced
    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests. A hybrid_moe config
        keeps one whole period of its layer pattern and holds fewer experts
        than its router routes over."""
        if self.family == "hybrid_moe":
            return dataclasses.replace(
                self, name=self.name + "-reduced", dtype="float32",
                num_layers=min(self.num_layers, self.period), d_model=128,
                num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
                moe_d_ff=0, moe_shared_d_ff=384, vocab_size=512,
                max_seq_len=512, num_experts=8,
                num_experts_per_tok=min(self.num_experts_per_tok, 3),
                moe_held=4, ssm_state=16, ssm_head_dim=32,
                ssm_chunk=32)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            dtype="float32",
            num_layers=min(self.num_layers, 4 if self.family in ("ssm", "hybrid") else 2),
            d_model=128,
            num_heads=4 if self.num_heads else 0,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            head_dim=32 if self.num_heads else 0,
            d_ff=256 if self.d_ff else 0,
            moe_d_ff=128 if self.moe_d_ff else 0,
            vocab_size=512,
            max_seq_len=512,
            num_experts=min(self.num_experts, 4),
            num_experts_per_tok=min(self.num_experts_per_tok, 2),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32 if self.ssm_state else 64,
            ssm_chunk=32,
            attn_every=2 if self.attn_every else 0,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq_len=min(self.encoder_seq_len, 64),
            num_patches=min(self.num_patches, 16),
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def applicable_shapes(cfg: ArchConfig) -> list:
    """The assigned shape cells that apply to this arch (long_500k is
    sub-quadratic-only per the brief)."""
    out = [SHAPES["train_4k"], SHAPES["prefill_32k"], SHAPES["decode_32k"]]
    if cfg.sub_quadratic:
        out.append(SHAPES["long_500k"])
    return out


# Populated by repro.configs.__init__: the published architectures, and
# the chip shares cut from them (kept apart so the published list stays the
# published list)
REGISTRY: dict = {}
SHARES: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    REGISTRY[cfg.name] = cfg
    return cfg


def register_share(cfg: ArchConfig) -> ArchConfig:
    SHARES[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    """A published architecture, a chip share, or ``<either>-reduced`` (the
    tiny same-family CPU config, ``ArchConfig.reduced``)."""
    import repro.configs  # noqa: F401  (trigger registration)
    if name in REGISTRY:
        return REGISTRY[name]
    if name in SHARES:
        return SHARES[name]
    base = name.removesuffix("-reduced")
    if base != name:
        return get_config(base).reduced()
    raise KeyError(f"unknown arch {name!r}; have "
                   f"{sorted(REGISTRY) + sorted(SHARES)}")
