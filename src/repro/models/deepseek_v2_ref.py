"""Plain float32 forward of DeepSeek-V2 (arXiv:2405.04434), for the tests.

One sequence, its whole prefix, no cache, no kernels, no batching, every
matmul at ``precision="highest"``; it reads the program's parameter tree
(``lm.init`` layout: ``embedding`` / ``unembed`` / ``final_norm`` and the
layer stacks ``seg0`` (the leading dense layers) and ``seg1`` (the MoE
layers)) and a ``ModelConfig``, and imports nothing of the model code, so it
checks the program's algebra rather than repeating it:

- multi-head latent attention computed *expanded*: ``c = RMSNorm(x W_dkv)``,
  ``k_nope = c W_uk`` and ``v = c W_uv`` per head, ``k_pe = rope(x W_kr)``
  shared by the heads, ``q = x W_q`` split into ``[q_nope | q_pe]`` with
  ``q_pe`` roped (no query compression, ``q_lora_rank`` null), causal softmax
  at ``(d_nope + d_rope)^-0.5 * mscale^2``;
- YaRN rope (``DeepseekV2YarnRotaryEmbedding``): frequencies
  ``theta^(-2i/d)`` blended with the same divided by ``factor`` by the linear
  ramp between floor / ceil of the correction dimensions of ``beta_fast`` and
  ``beta_slow`` over the original context; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
- the leading dense SwiGLU layers (``first_k_dense_replace``);
- the router: float32 logits over all routed experts, softmax, greedy top-k,
  gates renormalised only where ``norm_topk_prob``, times
  ``routed_scaling_factor``; each pick on a held expert adds its gate times
  that expert's SwiGLU, picks on experts this share does not hold add
  nothing; the shared experts (one SwiGLU of ``n_shared x`` the expert
  width) are added to every token;
- RMSNorm before attention, before the MLP and at the end; an untied head.

Departures from the published model, each deliberate:
- rope rotates the two halves of the rope dimensions, where DeepSeek-V2
  rotates interleaved pairs (its ``apply_rotary_pos_emb`` de-interleaves
  first).  With weights made rather than loaded this is a fixed permutation
  of the rope columns of ``W_q`` and ``W_kr``, and the program does the same;
- the expert share (``MoEConfig.experts_held`` experts from
  ``MoEConfig.expert_offset``) is a deployment's cut, not part of the model; with every expert held it is
  the whole layer;
- the vocabulary is padded to ``vocab_pad_to`` rows; the logits returned are
  the real ``vocab_size`` columns only.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs) -> np.ndarray:
    """The published YaRN inverse frequencies (numpy float64 -> float32)."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** pos
    if rs is None:
        return extra.astype(np.float32)
    inter = 1.0 / (rs.factor * base ** pos)

    def corr(rot):
        return (dim * math.log(rs.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs.beta_fast)), 0)
    high = min(math.ceil(corr(rs.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp                      # 1 = keep the extrapolated freq
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(cfg) -> float:
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_get_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _rope(x, pos, cfg):
    """x [T, ..., d]: rotate the two halves of the last axis by position."""
    d = x.shape[-1]
    rs = cfg.rope_scaling
    inv = jnp.asarray(yarn_inv_freq(d, cfg.rope_theta, rs))
    ang = pos.astype(jnp.float32)[:, None] * inv           # [T, d/2]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    if rs is not None:
        m = (yarn_get_mscale(rs.factor, rs.mscale)
             / yarn_get_mscale(rs.factor, rs.mscale_all_dim))
        c, s = c * m, s * m
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(p, cfg, h):
    """Expanded MLA over the sequence h [T, D]."""
    m = cfg.mla
    T = h.shape[0]
    pos = jnp.arange(T)
    q = jnp.einsum("td,dhk->thk", h, p["wq"], precision=HI)
    qn, qr = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    qr = _rope(qr, pos, cfg)
    c = _rms(_mm(h, p["wdkv"]), p["kv_norm"], cfg.norm_eps)   # [T, r]
    kpe = _rope(_mm(h, p["wkr"]), pos, cfg)                   # [T, dr]
    kn = jnp.einsum("tr,rhk->thk", c, p["wuk"], precision=HI)
    v = jnp.einsum("tr,rhv->thv", c, p["wuv"], precision=HI)
    s = (jnp.einsum("qhk,thk->hqt", qn, kn, precision=HI)
         + jnp.einsum("qhk,tk->hqt", qr, kpe, precision=HI))
    s = s * softmax_scale(cfg)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    prob = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqt,thv->qhv", prob, v, precision=HI)
    return jnp.einsum("qhv,hvd->qd", o, p["wo"], precision=HI)


def route(router, cfg, h):
    """(gates [T, K], expert ids [T, K]) of the published router."""
    mo = cfg.moe
    probs = jax.nn.softmax(_mm(h, router), axis=-1)
    gate, idx = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    return gate * mo.routed_scaling_factor, idx


def moe(p, cfg, h):
    """The routed experts this share holds, plus the shared experts."""
    mo = cfg.moe
    gate, idx = route(p["router"], cfg, h)
    y = jnp.zeros_like(h)
    for j in range(p["w_gate"].shape[0]):
        e = mo.expert_offset + j
        w = (gate * (idx == e)).sum(-1, keepdims=True)       # [T, 1]
        y = y + w * _swiglu(h, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    if mo.num_shared_experts:
        s = p["shared"]
        y = y + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"])
    return y


def logits(params, cfg, tokens):
    """Logits [T, vocab_size] of the whole sequence ``tokens`` [T]."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embedding"])[jnp.asarray(tokens)]
        fk = cfg.moe.first_k_dense
        stacks = [("seg0", fk, False), ("seg1", cfg.num_layers - fk, True)]
        if not fk:
            stacks = [("seg0", cfg.num_layers, True)]
        for seg, n, is_moe in stacks:
            stack = f32(params[seg]["b0"])
            for i in range(n):
                p = jax.tree.map(lambda a: a[i], stack)
                h = _rms(x, p["ln1"]["scale"], cfg.norm_eps)
                x = x + attention(p["attn"], cfg, h)
                h = _rms(x, p["ln2"]["scale"], cfg.norm_eps)
                if is_moe:
                    x = x + moe(p["mlp"], cfg, h)
                else:
                    f = p["mlp"]
                    x = x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
        x = _rms(x, f32(params["final_norm"]["scale"]), cfg.norm_eps)
        out = _mm(x, f32(params["unembed"]))
    return out[:, : cfg.vocab_size]
