"""A latent-attention mixture-of-experts decoder (DeepSeek-V2) as a
configuration file states it, and the operations and bytes of its work.

``LatentMoE.from_config`` reads every key of the file's ``config`` (the
catalog's copy of the model's ``config.json``) and refuses a key it does not
know, so no key is dropped on the way to the program: a shape key becomes a
field, and a key that names a variant the program implements one way only
(the activation, the router's scoring, ...) must hold that value.  The
file's ``deployment`` gives what the cut keeps apart from the published
config: the published expert count and this chip's share.

Counts follow ``benchlib.flops``: 2 FLOPs per parameter a token touches,
attention's scores and values per live key, real tokens and live keys only.
A token touches the embedding row, every attention and dense weight, the
shared experts, the router, and the held experts its picks land on: on one
chip of an expert-parallel deployment, ``top_k * held / n_experts`` experts
on average (the other picks run on other chips).
"""

from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class LatentMoE:
    d_model: int
    n_layers: int
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int                  # the leading dense layers' width
    expert_d_ff: int
    n_experts: int             # routed experts of the published model
    experts_held: int          # this chip's share
    expert_offset: int
    top_k: int
    n_shared: int
    first_k_dense: int
    vocab: int
    vocab_pad_to: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    norm_eps: float
    norm_topk_prob: bool
    routed_scaling_factor: float

    @property
    def vocab_padded(self) -> int:
        p = max(self.vocab_pad_to, 1)
        return -(-self.vocab // p) * p

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @classmethod
    def from_config(cls, c: dict) -> "LatentMoE":
        m = dict(c["config"])
        dep = c["deployment"]

        def take(key, *want):
            if key not in m:
                raise KeyError(f"configuration lacks {key!r}")
            v = m.pop(key)
            if want and v != want[0]:
                raise ValueError(f"{key} = {v!r}: the program implements "
                                 f"{want[0]!r} only")
            return v

        take("model_type", "deepseek_v2")
        take("hidden_act", "silu")
        take("attention_bias", False)
        take("q_lora_rank", None)            # no query compression
        take("scoring_func", "softmax")
        take("topk_method", "greedy")
        take("n_group", 1)
        take("topk_group", 1)
        take("moe_layer_freq", 1)
        take("tie_word_embeddings", False)
        # the auxiliary loss is training's; serving leaves it out
        take("seq_aux")
        heads = int(take("num_attention_heads"))
        if int(take("num_key_value_heads")) != heads:
            raise ValueError("MLA: every query head has its key and value")
        rs = dict(take("rope_scaling"))
        if rs.pop("type") != "yarn":
            raise ValueError("rope_scaling: the program implements yarn only")
        held = int(take("n_routed_experts"))
        if held != int(dep["experts_held"]):
            raise ValueError("n_routed_experts is the share held here")
        out = cls(
            d_model=int(take("hidden_size")),
            n_layers=int(take("num_hidden_layers")),
            n_heads=heads,
            kv_lora_rank=int(take("kv_lora_rank")),
            qk_nope=int(take("qk_nope_head_dim")),
            qk_rope=int(take("qk_rope_head_dim")),
            v_head=int(take("v_head_dim")),
            d_ff=int(take("intermediate_size")),
            expert_d_ff=int(take("moe_intermediate_size")),
            n_experts=int(dep["n_routed_experts_published"]),
            experts_held=held,
            expert_offset=int(dep["expert_offset"]),
            top_k=int(take("num_experts_per_tok")),
            n_shared=int(take("n_shared_experts")),
            first_k_dense=int(take("first_k_dense_replace")),
            vocab=int(take("vocab_size")),
            vocab_pad_to=int(c["as_run"].get("vocab_pad_to", 1)),
            rope_theta=float(take("rope_theta")),
            yarn_factor=float(rs.pop("factor")),
            yarn_original=int(rs.pop("original_max_position_embeddings")),
            yarn_beta_fast=float(rs.pop("beta_fast")),
            yarn_beta_slow=float(rs.pop("beta_slow")),
            yarn_mscale=float(rs.pop("mscale")),
            yarn_mscale_all_dim=float(rs.pop("mscale_all_dim")),
            norm_eps=float(take("rms_norm_eps")),
            norm_topk_prob=bool(take("norm_topk_prob")),
            routed_scaling_factor=float(take("routed_scaling_factor")),
        )
        positions = int(take("max_position_embeddings"))
        if positions != int(out.yarn_factor * out.yarn_original):
            raise ValueError("max_position_embeddings is YaRN's reach")
        if rs or m:
            raise KeyError(f"keys the runner does not map: {sorted(rs) + sorted(m)}")
        if not out.expert_offset + held <= out.n_experts:
            raise ValueError("the held experts lie outside the router")
        return out


def attn_params(m: LatentMoE) -> int:
    D, H, r = m.d_model, m.n_heads, m.kv_lora_rank
    return (D * H * (m.qk_nope + m.qk_rope) + D * r + D * m.qk_rope + r
            + r * H * m.qk_nope + r * H * m.v_head + H * m.v_head * D)


def expert_params(m: LatentMoE) -> int:
    return 3 * m.d_model * m.expert_d_ff


def n_params(m: LatentMoE) -> int:
    """Parameters held on the chip (the head untied)."""
    D = m.d_model
    dense = attn_params(m) + 3 * D * m.d_ff + 2 * D
    moe = (attn_params(m) + D * m.n_experts + m.experts_held * expert_params(m)
           + m.n_shared * expert_params(m) + 2 * D)
    return (2 * m.vocab_padded * D + D + m.first_k_dense * dense
            + m.n_moe_layers * moe)


def active_params(m: LatentMoE) -> float:
    """Parameters one token touches on this chip: everything but the
    embedding table and the experts, one embedding row, and the share of its
    picks that lands on held experts."""
    D = m.d_model
    picked = m.top_k * m.experts_held / m.n_experts
    per_moe = (attn_params(m) + D * m.n_experts
               + (m.n_shared + picked) * expert_params(m))
    dense = attn_params(m) + 3 * D * m.d_ff
    return (m.first_k_dense * dense + m.n_moe_layers * per_moe
            + m.vocab_padded * D)


def prefill_flops(m: LatentMoE, n: int) -> float:
    """A causal prefill of ``n`` prompt tokens, attention expanded: scores
    over ``d_nope + d_rope`` and values over ``v_head`` per head."""
    per_key = 2 * m.n_layers * m.n_heads * (m.qk_nope + m.qk_rope + m.v_head)
    return 2.0 * active_params(m) * n + per_key * n * (n + 1) / 2


def latent_decode_call(m: LatentMoE, kv_lens: list[int]) -> tuple[float, float]:
    """One layer's latent decode kernel call over the live slots: (FLOPs,
    bytes).  Each slot reads its live rows, ``r + dr`` wide in bfloat16 (not
    the lane padding), and its query, and writes a float32 context."""
    H, r, dr = m.n_heads, m.kv_lora_rank, m.qk_rope
    kv = sum(kv_lens)
    flops = 2.0 * H * (2 * r + dr) * kv
    nbytes = (r + dr) * kv * BF16 + len(kv_lens) * H * ((r + dr) * BF16 + r * F32)
    return flops, nbytes


def expert_gmm_call(m: LatentMoE, picks: list[int]) -> tuple[float, float]:
    """One layer's grouped expert call, ``picks`` rows per held expert:
    (FLOPs, bytes).  It reads the weights of every held expert that got a
    pick, once, and each picked row in and out (bfloat16)."""
    D = m.d_model
    rows = sum(picks)
    used = sum(1 for p in picks if p)
    flops = 2.0 * expert_params(m) * rows
    nbytes = used * expert_params(m) * BF16 + 2 * rows * D * BF16
    return flops, nbytes
