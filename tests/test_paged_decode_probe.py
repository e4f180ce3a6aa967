"""``tools/paged_decode_probe.py`` at a tiny size on the CPU (Pallas interpret
mode): one JSON line per (active, draw), the kernel's chunks walked, and the
kernel's output beside the XLA reference's."""

import importlib.util
import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _probe():
    spec = importlib.util.spec_from_file_location(
        "paged_decode_probe", ROOT / "tools" / "paged_decode_probe.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paged_decode_probe_smoke(capsys):
    probe = _probe()
    assert probe.main([
        "--slots", "4", "--heads", "4", "--kv-heads", "2", "--head-dim", "16",
        "--block-size", "8", "--pool-blocks", "64", "--max-blocks", "16",
        "--layers", "2", "--active", "1,3", "--draws", "1", "--reps", "1",
        "--prompt-median", "20", "--prompt-min", "4", "--prompt-max", "60",
        "--output-median", "8", "--output-min", "2", "--output-max", "16",
        "--interpret",
    ]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["active"] for r in rows] == [1, 3]
    for r in rows:
        # every slot walks at least one chunk; a table of at most 16 entries
        # is at most one chunk of 32 (256 rows of 8)
        assert r["chunks_per_call"] == 4
        assert r["live_tokens"] >= r["active"] * 5 + (4 - r["active"])
        assert r["us_per_call"] > 0 and r["live_gb_per_s"] > 0
        # bfloat16 outputs of the same float32 arithmetic
        assert r["max_abs_gap"] < 0.05


def test_paged_decode_probe_lengths_fit_the_pool():
    probe = _probe()
    args = probe.argparse.Namespace(
        prompt_median=512, prompt_sigma=1.0, prompt_min=32, prompt_max=4096,
        output_median=128, output_sigma=0.8, output_min=16, output_max=512,
        block_size=16, pool_blocks=2049, max_blocks=288)
    rng = np.random.default_rng(3)
    lens = probe.fit(rng, 40, args)
    assert len(lens) == 40 and min(lens) >= 33
    assert sum(-(-n // 16) for n in lens) < 2049
    tables, kv_len = probe.build_tables(lens, 64, 16, 2049, 288, rng)
    assert tables.shape[1] <= 288 and (kv_len[40:] == 1).all()
    live = tables[tables > 0]
    assert len(live) == len(set(live.tolist()))      # distinct blocks
