"""The DeepSeek-V2-Lite cell: its configuration file against the catalog, the
runner's mapping, weights and counts against the program, and a CPU
rehearsal of the runner at smoke size (the chip's kernels off, as on any
CPU run)."""

from __future__ import annotations

import json
import math
import re
import shutil

import pytest

from conftest import BENCH, DATA, ROOT, load_run

from benchlib.device import Peak
from benchlib.latent_moe_model import LatentMoE, n_params

CPU_PEAK = Peak("cpu-test", 1e12, 1e11, 1e10, "test value, not a device")
CELL = "serve-deepseek-v2-lite-ep8-chat"
FILE = BENCH / "configs" / "deepseek-v2-lite-ep8.json"

# the catalog's ``config`` of DeepSeek-V2-Lite (source_url
# https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json),
# letter for letter
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "greedy", "v_head_dim": 128, "vocab_size": 102400,
}
# keys that name a width, which no cut may change
WIDTH = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|head|"
                   r"expansion|experts_per_tok")


def _file():
    return json.loads(FILE.read_text())


def test_config_file_holds_the_catalog_config():
    """Every key and value of the catalog's config, nested ``rope_scaling``
    included, both at the file's top level and in its ``config``; only the
    keys in ``reduced`` differ, none of them a width, each with its cause."""
    c = _file()
    assert c["reduced"] == ["n_routed_experts", "kv_pool_blocks"]
    assert not [k for k in c["reduced"] if WIDTH.search(k)]
    assert set(c["cuts"]) == set(c["reduced"])
    for where in (c, c["config"]):
        for k, v in CATALOG.items():
            if k in c["reduced"]:
                continue
            assert k in where and where[k] == v and type(where[k]) is type(v), k
    assert set(c["config"]) == set(CATALOG)
    assert c["config"]["n_routed_experts"] == c["n_routed_experts"] == 8
    assert c["deployment"]["n_routed_experts_published"] == 64
    assert c["deployment"]["experts_held"] == 8
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [e for e in bench["configs"] if e["name"] == "deepseek-v2-lite-ep8"]
    assert entry[0]["reduced"] == c["reduced"]
    assert entry[0]["source"] == c["source"]


def test_mapping_uses_every_key():
    """``LatentMoE.from_config`` consumes every key of ``config``: an unknown
    key, a missing one (``first_k_dense_replace``) or a variant the program
    does not implement is refused, not dropped."""
    c = _file()
    m = LatentMoE.from_config(c)
    assert (m.first_k_dense, m.n_experts, m.experts_held, m.top_k) == (1, 64, 8, 6)
    assert (m.yarn_factor, m.yarn_original, m.norm_topk_prob) == (40.0, 4096, False)
    for bad, err in (
        (dict(c["config"], extra_key=1), KeyError),
        ({k: v for k, v in c["config"].items()
          if k != "first_k_dense_replace"}, KeyError),
        (dict(c["config"], hidden_act="gelu"), ValueError),
        (dict(c["config"], q_lora_rank=1536), ValueError),
        (dict(c["config"], rope_scaling=dict(c["config"]["rope_scaling"],
                                             extra=1)), KeyError),
    ):
        with pytest.raises(err):
            LatentMoE.from_config(dict(c, config=bad))


def test_weights_and_counts_follow_the_program():
    """The runner's weight tree has the program's leaves and shapes at the
    cell's sizes, and the parameter count behind the FLOP counts is the
    program's (3.11 B held here: 6.22 GB in bfloat16)."""
    import jax

    from benchlib.serve_latent_moe import _leaves, model_config
    from repro.models import lm

    m = LatentMoE.from_config(_file())
    cfg = model_config(m, "deepseek-v2-lite-16b")
    assert cfg.moe.first_k_dense == 1 and cfg.moe.held == 8
    shapes = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    flat = {tuple(str(getattr(k, "key", k)) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert flat == {path: shape for path, shape, _, _ in _leaves(m)}
    total = sum(math.prod(s) for s in flat.values())
    assert n_params(m) == total == 3_110_989_312


def test_work_counts():
    """The counts behind the roofline shares, from the shapes: a latent
    decode call reads each live row (576 wide, bf16) once for all 16 heads;
    an expert call reads each picked held expert's weights once; a prefill
    touches the active parameters (6 of 64 experts, 8 of them held here)
    per token plus causal expanded attention."""
    from benchlib.latent_moe_model import (active_params, expert_gmm_call,
                                           expert_params, latent_decode_call,
                                           prefill_flops)

    m = LatentMoE.from_config(_file())
    f, b = latent_decode_call(m, [100, 28])
    assert f == 2 * 16 * (2 * 512 + 64) * 128
    assert b == 576 * 128 * 2 + 2 * 16 * (576 * 2 + 512 * 4)
    f, b = expert_gmm_call(m, [3, 0, 5, 0, 0, 0, 0, 1])
    assert expert_params(m) == 3 * 2048 * 1408
    assert f == 2 * expert_params(m) * 9
    assert b == 3 * expert_params(m) * 2 + 2 * 9 * 2048 * 2
    per_key = 2 * 27 * 16 * (128 + 64 + 128)
    assert prefill_flops(m, 4) == 2 * active_params(m) * 4 + per_key * 10


@pytest.fixture
def smoke_root(tmp_path):
    """A checkout root whose ``BENCHMARK.json`` holds the cell at smoke size
    (``data/deepseek-smoke.json``: 3 layers, 4 of 8 experts held from offset
    4) under the real cell's name and metric entries."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = lambda ms: [m for m in ms if CELL in m.get("workloads", [CELL])]  # noqa: E731
    smoke = dict(bench, run_seconds=2,
                 configs=[{"name": "deepseek-smoke", "source": "test",
                           "file": "bench/configs/deepseek-smoke.json",
                           "reduced": [], "why": "test"}],
                 workloads=[{"name": CELL, "config": "deepseek-smoke",
                             "traffic": "chat-moe-smoke", "chips": 1,
                             "why": "test"}],
                 end_to_end=mine(bench["end_to_end"]),
                 per_layer=mine(bench["per_layer"]))
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(smoke))
    shutil.copy(DATA / "deepseek-smoke.json",
                tmp_path / "bench" / "configs" / "deepseek-smoke.json")
    shutil.copy(DATA / "chat-moe-smoke.json",
                tmp_path / "bench" / "traffic" / "chat-moe-smoke.json")
    return tmp_path


def _run(root, trace: int, seed: int = 2**33 + 17) -> dict:
    run = load_run()
    args = run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "2", "--trace", str(trace)])
    return run.run(args, root=root, require_chip=False, peak_override=CPU_PEAK)


def test_smoke_run_end_to_end(smoke_root):
    out = _run(smoke_root, 0)
    json.dumps(out)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 24
    assert set(out["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_smoke_run_traced(smoke_root):
    """On the CPU no kernel event exists: the trace readers find nothing and
    are left out; the expert load comes from the program's histograms."""
    out = _run(smoke_root, 1)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"expert_load_max_over_mean.serve"}
    assert out["metrics"]["expert_load_max_over_mean.serve"]["value"] >= 1.0


def test_float8_control_fails_the_limit():
    """At smoke size the float8 control's mean logit gap lies above the
    file's limit, where the program's float32-faithful path lies below it
    (the smoke runs above)."""
    import jax
    import numpy as np

    from benchlib.latent_moe_reference import served_gaps
    from benchlib.serve_latent_moe import make_weights

    c = json.loads((DATA / "deepseek-smoke.json").read_text())
    m = LatentMoE.from_config(c)
    w = make_weights(m, 5, "bfloat16")
    rng = np.random.default_rng(5)
    gaps = []
    for _ in range(4):
        prompt = rng.integers(2, m.vocab, 40).tolist()
        served = rng.integers(2, m.vocab, 20).tolist()
        _, ctl = served_gaps(w, m, prompt, served, control=True)
        gaps.append(ctl)
    jax.block_until_ready(w)
    assert float(np.concatenate(gaps).mean()) > c["correct"]["mean_logit_gap"]


def test_expert_kernel_time_is_read_inside_decode_programs():
    """``expert_gmm_roofline`` counts the kernel's events that start inside
    an execution of ``jit_serve_decode`` on their chip, not the prefills'."""
    import importlib.util

    from benchlib.megascan import Spans
    from benchlib.xplane import Op, Trace

    spec = importlib.util.spec_from_file_location(
        "egr", BENCH / "metrics" / "expert_gmm_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = Trace(devices={0: [Op("expert_gmm_pallas.1", 10, 20),
                            Op("fusion.3", 20, 40),
                            Op("expert_gmm_pallas.1", 110, 150),
                            Op("expert_gmm_pallas.2", 210, 215)]})
    sp = Spans(modules={0: [Op("jit_serve_decode(7)", 5, 60),
                            Op("jit_serve_prefill(3)", 100, 160),
                            Op("jit_serve_decode(7)", 200, 230)]})
    assert mod.decode_kernel_seconds(tr, sp, mod.NAME) == pytest.approx(15e-9)
