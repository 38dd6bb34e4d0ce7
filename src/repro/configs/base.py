"""Model configuration schema shared by every architecture.

A single frozen dataclass covers all ten assigned families (dense / ssm /
moe / hybrid / vlm / audio enc-dec). Per-layer structure is described by a
repeating ``block_pattern`` of two-character codes::

    first char  — mixer:  'a' attention (GQA/MLA)   's' mamba-1 SSM
    second char — ffn:    'm' dense MLP   'M' MoE   '-' none (mamba-1 arch)

``layer_groups`` turns (pattern × n_layers) into scan groups: consecutive
repeats of the same pattern period are stacked along a leading axis and
executed with ``lax.scan`` so HLO size stays O(pattern), not O(depth).
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
from jax import ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (``rope_scaling.type == "yarn"``), as the
    DeepSeek-V2 reference ``modeling_deepseek.py`` applies it."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def get_mscale(scale: float, mscale: float) -> float:
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    @property
    def attn_factor(self) -> float:
        """Factor on the softmax scale: mscale(factor, mscale_all_dim)²."""
        if not self.mscale_all_dim:
            return 1.0
        return self.get_mscale(self.factor, self.mscale_all_dim) ** 2

    @property
    def cos_sin_factor(self) -> float:
        """Factor on cos and sin: mscale(factor, mscale) over
        mscale(factor, mscale_all_dim)."""
        return (self.get_mscale(self.factor, self.mscale)
                / self.get_mscale(self.factor, self.mscale_all_dim))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | ssm | moe | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    ffn_act: str = "swiglu"          # swiglu | relu2 | gelu
    rope_theta: float = 1_000_000.0
    yarn: YarnScaling | None = None  # None: plain rotary frequencies
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    n_experts_per_tok: int = 0
    n_shared_experts: int = 0        # deepseek: always-on shared expert(s)
    first_dense_layers: int = 0      # deepseek: first k layers use dense FFN
    moe_d_ff: int = 0                # expert hidden dim (0 -> d_ff)
    norm_topk_prob: bool = True      # renormalise the top-k probabilities
    expert_first: int = 0            # first expert id held on this chip
    experts_held: int = 0            # experts held here (0 -> n_experts)
    # --- MLA (deepseek) ---
    mla: bool = False
    q_lora_rank: int = 1536          # 0: a direct q projection
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    mtp_depth: int = 0               # deepseek multi-token-prediction modules
    # --- SSM (mamba-1) ---
    block_pattern: tuple[str, ...] = ("am",)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0                 # 0 -> ceil(d_model / 16)
    # --- enc-dec / modality frontends (STUBS per assignment) ---
    n_encoder_layers: int = 0
    cross_attention: bool = False
    frontend: str = ""               # "" | "audio" | "vision"
    frontend_tokens: int = 0         # whisper: 1500 frames; phi3v: patches
    causal_encoder: bool = False
    max_wavelength_pos: int = 4096   # learned-pos table size for enc-dec

    # ---- derived ----
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_r(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this chip holds: ids
        ``expert_first .. expert_first + n_held - 1`` of ``n_experts``."""
        return self.experts_held or self.n_experts

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def attn_free(self) -> bool:
        """True when no decoder layer has attention (no KV cache exists)."""
        return all(e[0] != "a" for e in self.block_pattern)

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k: SSM / hybrid archs."""
        return any(e[0] == "s" for e in self.block_pattern)

    def pattern_for_layer(self, i: int) -> str:
        if i < self.first_dense_layers:
            base = self.block_pattern[i % len(self.block_pattern)]
            return base[0] + ("m" if base[1] == "M" else base[1])
        return self.block_pattern[i % len(self.block_pattern)]


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """A scannable group: ``repeats`` copies of the ``entries`` period."""
    entries: tuple[str, ...]
    repeats: int


def layer_groups(cfg: ModelConfig) -> list[LayerGroup]:
    """Split the decoder stack into homogeneous scan groups.

    Deepseek-style ``first_dense_layers`` get their own group; the remainder
    must tile the block pattern exactly.
    """
    groups: list[LayerGroup] = []
    period = len(cfg.block_pattern)
    fd = cfg.first_dense_layers
    if fd:
        entries = tuple(cfg.pattern_for_layer(i) for i in range(fd))
        # collapse identical entries into one scanned group
        if len(set(entries)) == 1:
            groups.append(LayerGroup((entries[0],), fd))
        else:
            groups.append(LayerGroup(entries, 1))
    rest = cfg.n_layers - fd
    if rest:
        if rest % period != 0:
            raise ValueError(
                f"{cfg.name}: {rest} layers not a multiple of pattern "
                f"period {period}")
        groups.append(LayerGroup(cfg.block_pattern, rest // period))
    return groups


# ---------------------------------------------------------------------------
# Input shape sets (assignment: 4 shapes per LM arch)
# ---------------------------------------------------------------------------

SHAPES = {
    "train_4k":    {"kind": "train",   "seq": 4_096,   "batch": 256},
    "prefill_32k": {"kind": "prefill", "seq": 32_768,  "batch": 32},
    "decode_32k":  {"kind": "decode",  "seq": 32_768,  "batch": 128},
    "long_500k":   {"kind": "decode",  "seq": 524_288, "batch": 1},
}


def shape_applicable(cfg: ModelConfig, shape_name: str) -> bool:
    """long_500k needs sub-quadratic attention (SSM / hybrid only)."""
    if shape_name == "long_500k":
        return cfg.sub_quadratic
    return True


def input_specs(cfg: ModelConfig, shape_name: str,
                batch: int | None = None, seq: int | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.

    ``kind=train``   -> tokens + labels over the full sequence
    ``kind=prefill`` -> prompt tokens (KV cache is an *output*)
    ``kind=decode``  -> the current token per sequence (cache is a donated
                        carry built by ``serving.cache_specs``)
    Modality frontends are stubs: precomputed frame/patch embeddings arrive
    as inputs (assignment note).
    """
    sh = SHAPES[shape_name]
    b = batch or sh["batch"]
    s = seq or sh["seq"]
    kind = sh["kind"]
    tok = jnp.int32
    specs: dict = {}
    n_front = cfg.frontend_tokens if cfg.frontend else 0

    if kind in ("train", "prefill"):
        s_text = s - (n_front if cfg.frontend == "vision" else 0)
        specs["tokens"] = ShapeDtypeStruct((b, s_text), tok)
        if kind == "train":
            specs["labels"] = ShapeDtypeStruct((b, s_text), tok)
    else:  # decode: one new token per sequence
        specs["tokens"] = ShapeDtypeStruct((b, 1), tok)

    if cfg.frontend == "vision" and kind in ("train", "prefill"):
        specs["patch_embeds"] = ShapeDtypeStruct(
            (b, n_front, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "audio":
        # encoder frames are needed whenever the encoder runs (train/prefill)
        if kind in ("train", "prefill"):
            specs["frame_embeds"] = ShapeDtypeStruct(
                (b, n_front, cfg.d_model), jnp.bfloat16)
    return specs
