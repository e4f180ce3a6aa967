"""Operations and bytes of the work a cell asks for, counted from its shapes.

Model FLOPs follow the usual count: 2 per parameter per token forward, 6
forward and backward, plus the attention scores and values (4 L H dh per
token per key forward).  Recomputed work (remat) is never counted, nor are
padded buckets or table widths: only real prompt tokens and live keys.

``n_params`` counts the model as it runs, the embedding with its rows padded
to ``vocab_pad_to`` (the padded rows take part in the output matmul), once
when it is tied to the output head.
"""

from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Dense:
    """A decoder-only dense transformer as a config file states it."""
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_pad_to: int = 1
    qkv_bias: bool = False
    tied: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0

    @property
    def vocab_padded(self) -> int:
        p = max(self.vocab_pad_to, 1)
        return -(-self.vocab // p) * p

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        m = c["config"]
        heads = int(m["num_attention_heads"])
        return cls(
            d_model=int(m["hidden_size"]),
            n_layers=int(m["num_hidden_layers"]),
            n_heads=heads,
            n_kv_heads=int(m["num_key_value_heads"]),
            head_dim=int(m.get("head_dim", int(m["hidden_size"]) // heads)),
            d_ff=int(m["intermediate_size"]),
            vocab=int(m["vocab_size"]),
            vocab_pad_to=int(c["as_run"].get("vocab_pad_to", 1)),
            qkv_bias=bool(c["as_run"].get("qkv_bias", False)),
            tied=bool(m.get("tie_word_embeddings", False)),
            rope_theta=float(m.get("rope_theta", 10000.0)),
            norm_eps=float(m.get("rms_norm_eps", 1e-6)),
            scale_emb=float(m.get("scale_emb", 1.0)),
            scale_depth=float(m.get("scale_depth", 0.0)),
            dim_model_base=int(m.get("dim_model_base", 0)),
        )


def layer_params(m: Dense) -> int:
    D, H, K, dh, F = m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff
    attn = D * H * dh + 2 * D * K * dh + H * dh * D
    if m.qkv_bias:
        attn += (H + 2 * K) * dh
    return attn + 3 * D * F + 2 * D


def n_params(m: Dense) -> int:
    emb = m.vocab_padded * m.d_model * (1 if m.tied else 2)
    return emb + m.n_layers * layer_params(m) + m.d_model


def attn_flops_per_key(m: Dense) -> int:
    """Forward FLOPs of one query against one key, all layers (QK^T and PV)."""
    return 4 * m.n_layers * m.n_heads * m.head_dim


def train_flops_per_token(m: Dense, seq: int) -> float:
    """6N + 12 L H dh S: forward and backward of a full (non-causal) count."""
    return 6.0 * n_params(m) + 3.0 * attn_flops_per_key(m) * seq


def train_step_flops(m: Dense, seq: int, batch: int) -> float:
    return train_flops_per_token(m, seq) * seq * batch


def prefill_flops(m: Dense, n: int) -> float:
    """A causal prefill of ``n`` real prompt tokens: token i sees i + 1 keys."""
    return 2.0 * n_params(m) * n + attn_flops_per_key(m) * n * (n + 1) / 2


def decode_flops(m: Dense, ctx: int) -> float:
    """One decoded token that attends to ``ctx`` keys (itself included)."""
    return 2.0 * n_params(m) + attn_flops_per_key(m) * ctx


@dataclass
class KernelWork:
    """Operations, bytes and least time of a set of kernel calls."""
    flops: float = 0.0
    bytes: float = 0.0
    calls: int = 0
    t_flops: float = 0.0     # least time of the calls bound by compute
    t_bytes: float = 0.0     # least time of the calls bound by bandwidth

    def add(self, flops: float, nbytes: float, peak_flops: float,
            peak_bw: float, calls: int = 1) -> None:
        self.flops += flops * calls
        self.bytes += nbytes * calls
        self.calls += calls
        tf, tb = flops / peak_flops, nbytes / peak_bw
        if tf >= tb:
            self.t_flops += tf * calls
        else:
            self.t_bytes += tb * calls

    @property
    def least_s(self) -> float:
        return self.t_flops + self.t_bytes

    @property
    def bound(self) -> str:
        return "compute" if self.t_flops >= self.t_bytes else "bandwidth"


def paged_decode_call(m: Dense, kv_lens: list[int]) -> tuple[float, float]:
    """One layer's paged decode kernel call over the live slots: (FLOPs,
    bytes).  Each slot reads its live K and V (bf16) and its query (bf16),
    and writes its output (f32)."""
    H, K, dh = m.n_heads, m.n_kv_heads, m.head_dim
    kv = sum(kv_lens)
    flops = 4.0 * H * dh * kv
    nbytes = 2.0 * K * dh * kv * BF16 + len(kv_lens) * H * dh * (BF16 + F32)
    return flops, nbytes


def flash_prefill_call(m: Dense, n: int) -> tuple[float, float]:
    """One layer's flash prefill kernel call over ``n`` real prompt tokens:
    causal attention (n (n + 1) / 2 query-key pairs); reads q, K and V
    (bf16) once, writes the output (f32)."""
    H, K, dh = m.n_heads, m.n_kv_heads, m.head_dim
    flops = 4.0 * H * dh * n * (n + 1) / 2
    nbytes = n * H * dh * (BF16 + F32) + 2.0 * n * K * dh * BF16
    return flops, nbytes
