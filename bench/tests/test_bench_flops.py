"""The benchmark's operation and byte counts against hand counts."""

from __future__ import annotations

import json

import pytest

from conftest import BENCH

from benchlib.flops import (Dense, KernelWork, decode_flops, flash_prefill_call,
                            n_params, paged_decode_call, prefill_flops,
                            train_step_flops)

TOY = Dense(d_model=8, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=2,
            d_ff=16, vocab=10, vocab_pad_to=4, qkv_bias=True, tied=True)


def qwen2() -> Dense:
    return Dense.from_config(json.loads((BENCH / "configs/qwen2-0.5b.json").read_text()))


def test_qwen2_train_step_matches_the_published_count():
    m = qwen2()
    assert m.vocab_padded == 152064
    assert n_params(m) == 494_147_456
    # 6N + 12 L H dh S per token at 2048 x 8 (the chip_smoke.py count)
    assert train_step_flops(m, 2048, 8) == 57_235_325_583_360
    assert train_step_flops(m, 2048, 8) == pytest.approx(5.7235e13, rel=1e-4)


def test_toy_params_by_hand():
    # per layer: q 8*4*2 + k,v 2*8*2*2 + o 4*2*8 = 64+64+64, biases (4+2+2)*2,
    # mlp 3*8*16, two norms 2*8
    layer = 64 + 64 + 64 + 16 + 384 + 16
    assert n_params(TOY) == 12 * 8 + 2 * layer + 8     # padded 12-row embedding


def test_serving_counts_by_hand():
    N = n_params(TOY)
    per_key = 4 * 2 * 4 * 2                           # 4 L H dh
    assert decode_flops(TOY, 5) == 2 * N + per_key * 5
    assert prefill_flops(TOY, 3) == 2 * N * 3 + per_key * (1 + 2 + 3)
    f, b = paged_decode_call(TOY, [3, 5])
    assert f == 4 * 4 * 2 * 8                          # 4 H dh per live key
    assert b == 2 * 2 * 2 * 8 * 2 + 2 * 4 * 2 * (2 + 4)  # K,V bf16; q in, f32 out
    f, b = flash_prefill_call(TOY, 4)
    assert f == 4 * 4 * 2 * 10                         # 10 causal pairs
    assert b == 4 * 4 * 2 * (2 + 4) + 2 * 4 * 2 * 2 * 2


def test_kernel_work_takes_the_larger_bound():
    w = KernelWork()
    w.add(100.0, 10.0, peak_flops=10.0, peak_bw=10.0, calls=2)   # compute: 10 s
    w.add(1.0, 30.0, peak_flops=10.0, peak_bw=10.0)              # bytes: 3 s
    assert w.least_s == pytest.approx(23.0)
    assert w.calls == 3 and w.bound == "compute"
