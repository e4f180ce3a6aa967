"""Plain float32 forward of a dense decoder, and its low-precision control.

Straight ``jax.numpy`` from the published equations (Qwen2 / MiniCPM /
Llama-style): RMSNorm, rotary embeddings on the two halves of each head,
grouped-query causal softmax attention (query head h reads key head
h // (H / K)), SwiGLU MLP, residuals scaled by scale_depth / sqrt(L) where the
config sets scale_depth, embedding scale, logits divided by d_model /
dim_model_base where set, and the output head tied to the embedding.  No
kernel, no cache, no batching: one sequence at a time, its whole prefix.
Matmuls run at ``precision="highest"``, since a TPU would otherwise take
float32 matmuls in bfloat16.

It imports nothing of the program.  It is blocked to fit: the layers run
under ``lax.scan``, attention in blocks of queries, the output head in blocks
of rows, and what leaves the device is, per position, the largest logit and
the logits of the tokens asked about.

The control (``fp8=True``) is the same forward with the inputs of every
linear layer rounded to float8 e4m3 (per-row scales for activations, per
output channel for weights), the precision a serving change would reach for
below the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.flops import Dense

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` through float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    """x [T, D] @ w [D, N...] (weights already float32)."""
    w2 = w.reshape(w.shape[0], -1)
    if fp8:
        x = _fp8(x, -1)
        w2 = _fp8(w2, 0)
    y = jnp.dot(x, w2, precision=HI)
    return y.reshape(x.shape[0], *w.shape[1:])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [T, H, dh]: rotate the two halves of each head by position."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _attention(q, k, v, q_block: int):
    """Causal GQA attention, q [T, H, dh], k/v [T, K, dh], in query blocks."""
    T, H, dh = q.shape
    K = k.shape[1]
    G = H // K
    kk = jnp.repeat(k, G, axis=1)              # [T, H, dh]; head h -> h // G
    vv = jnp.repeat(v, G, axis=1)
    nb = T // q_block

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI) / jnp.sqrt(
            jnp.float32(dh))
        qi = i * q_block + jnp.arange(q_block)
        s = jnp.where(qi[None, :, None] >= jnp.arange(T)[None, None, :],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    out = jax.lax.map(block, jnp.arange(nb))     # [nb, q_block, H, dh]
    return out.reshape(T, H, dh)


@partial(jax.jit, static_argnames=("m", "fp8", "q_block", "row_block"))
def _stats(w, tokens, lookups, *, m: Dense, fp8: bool, q_block: int,
           row_block: int):
    f32 = lambda a: a.astype(jnp.float32)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    emb = f32(w["embedding"])
    x = emb[tokens] * m.scale_emb
    res = m.scale_depth / np.sqrt(m.n_layers) if m.scale_depth else 1.0

    def layer(x, p):
        p = jax.tree.map(f32, p)
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], m.norm_eps)
        q, k, v = (_linear(h, a["wq"], fp8), _linear(h, a["wk"], fp8),
                   _linear(h, a["wv"], fp8))
        if m.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
        o = _attention(q, k, v, q_block)
        x = x + res * _linear(o.reshape(T, -1), a["wo"].reshape(-1, m.d_model),
                              fp8)
        h = _rms(x, p["ln2"]["scale"], m.norm_eps)
        f = p["mlp"]
        g = _linear(h, f["w_gate"], fp8)
        u = _linear(h, f["w_up"], fp8)
        x = x + res * _linear(jax.nn.silu(g) * u, f["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["seg0"]["b0"])
    x = _rms(x, f32(w["final_norm"]["scale"]), m.norm_eps)
    if m.dim_model_base:
        x = x / (m.d_model / m.dim_model_base)
    head = emb[: m.vocab].T                      # [D, V] (tied)

    def rows(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * row_block, row_block, 0)
        lg = _linear(xb, head, fp8)              # [rows, V]
        lk = jax.lax.dynamic_slice_in_dim(lookups, i * row_block, row_block, 1)
        got = jnp.take_along_axis(lg[None], lk[..., None], axis=-1)[..., 0]
        return lg.max(-1), lg.argmax(-1).astype(jnp.int32), got

    mx, am, got = jax.lax.map(rows, jnp.arange(T // row_block))
    return (mx.reshape(T), am.reshape(T),
            jnp.moveaxis(got, 0, 1).reshape(lookups.shape[0], T))


def bucket(n: int, quantum: int = 1024) -> int:
    return -(-n // quantum) * quantum


def logit_stats(w, m: Dense, tokens, lookups, *, fp8: bool = False,
                quantum: int = 1024):
    """Per position of ``tokens`` (the logits that predict position + 1): the
    largest logit, its token, and the logit of each row of ``lookups``.

    ``tokens`` is padded to a multiple of ``quantum`` (causal: padding after
    the end changes nothing before it) so a few programs serve every length.
    Returns numpy arrays cut back to ``len(tokens)``.
    """
    n = len(tokens)
    T = bucket(n, quantum)
    tk = np.zeros(T, np.int32)
    tk[:n] = tokens
    lk = np.zeros((len(lookups), T), np.int32)
    for i, row in enumerate(lookups):
        lk[i, : len(row)] = row
    with jax.default_matmul_precision("highest"):
        mx, am, got = _stats(w, jnp.asarray(tk), jnp.asarray(lk), m=m, fp8=fp8,
                             q_block=min(512, T), row_block=min(256, T))
    return np.asarray(mx)[:n], np.asarray(am)[:n], np.asarray(got)[:, :n]


def served_gaps(w, m: Dense, prompt: list[int], served: list[int], *,
                control: bool = False):
    """How far below the reference's best logit each served token lies.

    The sequence is the prompt and the served tokens; the logits at position
    len(prompt) - 1 + j predict served token j.  With ``control`` it also
    returns, at each of those positions, the gap of the token that the
    float8 control puts first on the same prefix: ``(served, control)``.
    """
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
