"""Model / shape / run configuration for the repro framework.

Every assigned architecture gets one module in this package defining a
``CONFIG`` (full size, exact values from the assignment) and a
``SMOKE_CONFIG`` (same family, tiny dims) used by CPU smoke tests.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Literal

Family = Literal["dense", "moe", "rwkv6", "griffin", "encdec"]


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0            # routed experts
    num_shared_experts: int = 0
    top_k: int = 1
    expert_d_ff: int = 0            # per-expert hidden width
    capacity_factor: float = 1.25
    first_k_dense: int = 0          # leading layers that use a dense MLP
    seq_groups: int = 16            # seq chunks per sequence for dispatch
                                    # grouping (aligns groups with the
                                    # model-axis activation sharding)
    router_aux_coef: float = 0.001  # load-balance loss coefficient
    router_z_coef: float = 0.0001   # router z-loss coefficient
    norm_topk_prob: bool = True     # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0  # multiplies the routed experts' gates
    experts_held: int = 0           # routed experts this device holds, from
    expert_offset: int = 0          # this one: an expert-parallel chip's
                                    # share (0 held = all of them)

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0            # 0 = no query compression (V2-Lite)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class RopeScaling:
    """YaRN context extension (Peng et al. 2023, arXiv:2309.00071) as
    DeepSeek-V2 publishes it (``rope_scaling`` of its ``config.json``)."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class GriffinConfig:
    lru_width: int = 0              # 0 => d_model
    conv_width: int = 4
    window: int = 2048              # local-attention window
    pattern: tuple[str, ...] = ("rec", "rec", "attn")  # repeating block types
    c: float = 8.0                  # RG-LRU decay sharpness


@dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    ddlerp_rank: int = 32           # token-shift LoRA rank
    decay_rank: int = 64            # decay LoRA rank
    gate_rank: int = 0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # encoder-decoder
    num_encoder_layers: int = 0
    # attention flavour
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, ...] = ()      # (t, h, w) halves; empty = 1-D RoPE
    rope_scaling: RopeScaling | None = None   # YaRN; None = plain rope
    use_mla: bool = False
    mla: MLAConfig = field(default_factory=MLAConfig)
    # MoE
    moe: MoEConfig = field(default_factory=MoEConfig)
    # griffin / rwkv
    griffin: GriffinConfig = field(default_factory=GriffinConfig)
    rwkv: RWKVConfig = field(default_factory=RWKVConfig)
    # misc
    mlp_kind: Literal["swiglu", "relu2", "geglu"] = "swiglu"
    norm_kind: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    norm_eps: float = 1e-6
    vocab_pad_to: int = 256          # pad embedding/unembed rows (Megatron-style)
    tie_embeddings: bool = False
    scale_emb: float = 1.0           # MiniCPM embedding scale
    scale_depth: float = 0.0         # MiniCPM residual scale (0 = off)
    dim_model_base: int = 0          # MiniCPM logit scaling base (0 = off)
    # modality frontend stub: inputs are precomputed embeddings, not token ids
    input_kind: Literal["tokens", "embeds", "embeds_mrope"] = "tokens"
    # implementation knobs (hillclimb surface)
    attn_impl: Literal["naive", "chunked", "pallas"] = "chunked"
    kernels_impl: Literal["xla", "pallas", "pallas_interpret"] = "xla"
    # "xla": pure-jnp paths (CPU dry-run/tests); "pallas": TPU kernels for
    # wkv6 / rglru (flash attention selects via attn_impl="pallas")
    attn_kv_chunk: int = 1024
    remat: Literal["none", "full", "dots"] = "full"
    scan_unroll: bool = False        # python-loop layers (used by cost probes)
    logits_chunk: int = 512          # sequence-chunked cross-entropy
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # per-arch logical-axis rule overrides (merged over DEFAULT_RULES)
    sharding_overrides: dict[str, tuple[str, ...] | None] = field(default_factory=dict)
    # which shape cells are applicable (long_500k only for sub-quadratic archs)
    supports_long_context: bool = False
    notes: str = ""

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p

    @property
    def lru_width(self) -> int:
        return self.griffin.lru_width or self.d_model

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


_REGISTRY: dict[str, "ArchEntry"] = {}


@dataclass(frozen=True)
class ArchEntry:
    config: ModelConfig
    smoke_config: ModelConfig


def register(config: ModelConfig, smoke_config: ModelConfig) -> None:
    _REGISTRY[config.name] = ArchEntry(config, smoke_config)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    _ensure_loaded()
    entry = _REGISTRY.get(name)
    if entry is None:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return entry.smoke_config if smoke else entry.config


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def applicable_shapes(cfg: ModelConfig) -> list[ShapeConfig]:
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and not cfg.supports_long_context:
            continue
        out.append(s)
    return out


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    import importlib

    for mod in (
        "rwkv6_3b",
        "qwen2_0_5b",
        "minitron_4b",
        "minicpm_2b",
        "qwen3_14b",
        "deepseek_v2_lite_16b",
        "phi35_moe_42b",
        "seamless_m4t_large_v2",
        "recurrentgemma_9b",
        "qwen2_vl_7b",
    ):
        importlib.import_module(f"repro.configs.{mod}")
