"""Plain float32 forward of DeepSeek-V2 (arXiv:2405.04434) at one chip's share
of its experts, and its low-precision control.

Straight ``jax.numpy`` from the published equations: RMSNorm; multi-head
latent attention computed *expanded* (``c = RMSNorm(x W_dkv)``, per-head
``k = [c W_uk | k_pe]`` with ``k_pe = rope(x W_kr)`` shared by the heads,
``v = c W_uv``, ``q = [q_nope | rope(q_pe)]``, causal softmax at
``192^-0.5 * mscale^2``); YaRN rope (frequencies blended by the linear ramp
between the correction dimensions of ``beta_fast`` and ``beta_slow``); the
leading dense SwiGLU layer; the router (float32 softmax over every routed
expert, greedy top-k, not renormalised where ``norm_topk_prob`` is false,
times ``routed_scaling_factor``), each pick on a held expert adding its gate
times that expert's SwiGLU, picks on the other chips' experts adding nothing;
the shared experts; an untied head.  No kernel, no cache, no batching: one
sequence at a time, its whole prefix.  Matmuls run at ``precision="highest"``.

Departures, each deliberate: rope rotates the two halves of the rope
dimensions where DeepSeek-V2 rotates interleaved pairs (with made weights a
fixed permutation of the rope columns of ``W_q`` and ``W_kr``; the program
does the same); the experts are this chip's share, as in the program.

It imports nothing of the program and reads only the weights the benchmark
made (the program's parameter tree).  Those stay bfloat16 on the device: the
layers run under ``lax.scan`` and each is cast to float32 inside the scan,
since 3.1 B float32 parameters would not fit beside anything else.
Attention runs in blocks of queries, the routed experts one held expert at a
time over every token (a gate of zero where the token did not pick it), the
head in blocks of rows; what leaves the device is, per position, the
largest logit and the logits of the tokens asked about.

The control (``fp8=True``) is the same forward with the inputs of every
linear layer rounded to float8 e4m3 (per-row scales for activations, per
output channel for weights).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.latent_moe_model import LatentMoE

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    """x [T, D] @ w [D, N...] (float32)."""
    w2 = w.reshape(w.shape[0], -1)
    if fp8:
        x = _fp8(x, -1)
        w2 = _fp8(w2, 0)
    return jnp.dot(x, w2, precision=HI).reshape(x.shape[0], *w.shape[1:])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m: LatentMoE) -> np.ndarray:
    dim, base = m.qk_rope, m.rope_theta
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** pos
    inter = extra / m.yarn_factor

    def corr(rot):
        return (dim * math.log(m.yarn_original / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(m.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(m.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(m: LatentMoE) -> float:
    return ((m.qk_nope + m.qk_rope) ** -0.5
            * _mscale(m.yarn_factor, m.yarn_mscale_all_dim) ** 2)


def _rope(x, pos, m: LatentMoE):
    """x [T, (H,) dr]: rotate the two halves by position (YaRN)."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(m))
    if x.ndim == 3:
        ang = ang[:, None, :]
    k = (_mscale(m.yarn_factor, m.yarn_mscale)
         / _mscale(m.yarn_factor, m.yarn_mscale_all_dim))
    c, s = jnp.cos(ang) * k, jnp.sin(ang) * k
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _swiglu(x, wg, wu, wd, fp8):
    return _linear(jax.nn.silu(_linear(x, wg, fp8)) * _linear(x, wu, fp8),
                   wd, fp8)


def _attention(a, x, m: LatentMoE, fp8: bool, q_block: int):
    T = x.shape[0]
    pos = jnp.arange(T)
    q = _linear(x, a["wq"], fp8)                                # [T, H, dq]
    qn, qr = q[..., : m.qk_nope], _rope(q[..., m.qk_nope:], pos, m)
    c = _rms(_linear(x, a["wdkv"], fp8), a["kv_norm"], m.norm_eps)
    kpe = _rope(_linear(x, a["wkr"], fp8), pos, m)              # [T, dr]
    kn = _linear(c, a["wuk"], fp8)                              # [T, H, dn]
    v = _linear(c, a["wuv"], fp8)                               # [T, H, dv]
    scale = softmax_scale(m)

    def block(i):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, i * q_block, q_block, 0)  # noqa: E731
        s = (jnp.einsum("qhk,thk->hqt", sl(qn), kn, precision=HI)
             + jnp.einsum("qhk,tk->hqt", sl(qr), kpe, precision=HI)) * scale
        qi = i * q_block + jnp.arange(q_block)
        s = jnp.where(qi[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqt,thv->qhv", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(T // q_block)).reshape(T, m.n_heads, -1)
    return _linear(o.reshape(T, -1), a["wo"].reshape(-1, m.d_model), fp8)


def _moe(f, x, m: LatentMoE, fp8: bool):
    probs = jax.nn.softmax(jnp.dot(x, f["router"], precision=HI), axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * m.routed_scaling_factor

    def expert(y, j):
        w = (gate * (idx == m.expert_offset + j)).sum(-1, keepdims=True)
        out = _swiglu(x, f["w_gate"][j], f["w_up"][j], f["w_down"][j], fp8)
        return y + w * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m.experts_held))
    s = f["shared"]
    return y + _swiglu(x, s["w_gate"], s["w_up"], s["w_down"], fp8)


@partial(jax.jit, static_argnames=("m", "fp8", "q_block", "row_block"))
def _stats(w, tokens, lookups, *, m: LatentMoE, fp8: bool, q_block: int,
           row_block: int):
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    T = tokens.shape[0]
    emb = w["embedding"]
    x = emb[tokens].astype(jnp.float32)

    def layer(moe: bool):
        def body(x, p):
            p = f32(p)
            h = _rms(x, p["ln1"]["scale"], m.norm_eps)
            x = x + _attention(p["attn"], h, m, fp8, q_block)
            h = _rms(x, p["ln2"]["scale"], m.norm_eps)
            f = p["mlp"]
            if moe:
                return x + _moe(f, h, m, fp8), None
            return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], fp8), None
        return body

    x, _ = jax.lax.scan(layer(False), x, w["seg0"]["b0"])
    x, _ = jax.lax.scan(layer(True), x, w["seg1"]["b0"])
    x = _rms(x, w["final_norm"]["scale"].astype(jnp.float32), m.norm_eps)
    head = w["unembed"][:, : m.vocab]                         # [D, V] bf16

    def rows(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * row_block, row_block, 0)
        lg = _linear(xb, head.astype(jnp.float32), fp8)
        lk = jax.lax.dynamic_slice_in_dim(lookups, i * row_block, row_block, 1)
        got = jnp.take_along_axis(lg[None], lk[..., None], axis=-1)[..., 0]
        return lg.max(-1), lg.argmax(-1).astype(jnp.int32), got

    mx, am, got = jax.lax.map(rows, jnp.arange(T // row_block))
    return (mx.reshape(T), am.reshape(T),
            jnp.moveaxis(got, 0, 1).reshape(lookups.shape[0], T))


def bucket(n: int, quantum: int = 2048) -> int:
    return -(-n // quantum) * quantum


def logit_stats(w, m: LatentMoE, tokens, lookups, *, fp8: bool = False,
                quantum: int = 2048):
    """Per position of ``tokens`` (the logits that predict position + 1): the
    largest logit, its token, and the logit of each row of ``lookups``.
    ``tokens`` is padded to a multiple of ``quantum`` (causal: padding after
    the end changes nothing before it), so at most five programs (about half
    a minute each to compile at these widths) serve the cell's lengths.
    Numpy arrays cut back to ``len(tokens)``."""
    n = len(tokens)
    T = bucket(n, quantum)
    tk = np.zeros(T, np.int32)
    tk[:n] = tokens
    lk = np.zeros((len(lookups), T), np.int32)
    for i, row in enumerate(lookups):
        lk[i, : len(row)] = row
    with jax.default_matmul_precision("highest"):
        mx, am, got = _stats(w, jnp.asarray(tk), jnp.asarray(lk), m=m, fp8=fp8,
                             q_block=min(256, T), row_block=min(256, T))
    return np.asarray(mx)[:n], np.asarray(am)[:n], np.asarray(got)[:, :n]


def served_gaps(w, m: LatentMoE, prompt: list[int], served: list[int], *,
                control: bool = False):
    """How far below the reference's best logit each served token lies (and,
    with ``control``, the gaps of the tokens the float8 control puts first
    at the same positions): as ``benchlib.reference.served_gaps``."""
    seq = list(prompt) + list(served[:-1])
    start = len(prompt) - 1
    want = [np.zeros(len(seq), np.int32)]
    want[0][start:] = served
    if control:
        _, am8, _ = logit_stats(w, m, seq, want, fp8=True)
        want.append(np.zeros(len(seq), np.int32))
        want[1][start:] = am8[start:]
    mx, _, got = logit_stats(w, m, seq, want)
    gaps = [(mx - g)[start:] for g in got]
    return (gaps[0], gaps[1]) if control else gaps[0]
