"""Cells, configurations, traffic mixes and metrics are found by name from
files alone; the peak table and the refusal to run without a chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, load_run

from benchlib.device import NoAccelerator, peak, require_tpu
from benchlib.spec import load_benchmark, load_cell, metric_reader
from benchlib.xplane import Trace


def write(path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def test_new_config_cell_and_metric_from_files_alone(smoke_root):
    bench = load_benchmark(smoke_root)
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "serve-toy", "config": "toy",
                               "traffic": "toy-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "toy_share", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "device", "moves": "ttft_p95_ms",
                               "workloads": ["serve-toy"]})
    write(smoke_root / "BENCHMARK.json", bench)
    write(smoke_root / "bench/configs/toy.json", {"name": "toy", "size": 3})
    write(smoke_root / "bench/traffic/toy-mix.json", {"kind": "serve", "rate_per_s": 1})
    write(smoke_root / "bench/metrics/toy_share.py",
          "def read(rec):\n    return 42.0 if rec.get('seen') else None\n")

    cell = load_cell("serve-toy", smoke_root)
    assert cell.config == {"name": "toy", "size": 3}
    assert cell.traffic["rate_per_s"] == 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "ttft_p95_ms"}
    assert "toy_share" in {m["name"] for m in cell.per_layer}
    # a metric limited to other cells is not this cell's
    assert "toy_share" not in {m["name"] for m in load_cell("serve-smoke", smoke_root).per_layer}
    read = metric_reader("toy_share", smoke_root)
    assert read({"seen": True}) == 42.0 and read({}) is None

    # the harness assembles the new metric, and leaves out one that reads nothing
    run = load_run()
    res = {"correct": True, "attempted": 1, "failed": 0, "device": {},
           "per_layer": {"seen": True, "requests": [], "hist": {},
                         "trace": Trace(), "window_s": 0.0, "decode_kv": [],
                         "prefill_n": []},
           "checks": {"gap": {"value": 0.1, "limit": 1.0}}}
    out = run.assemble(cell, res, trace=True, root=smoke_root)
    assert out["metrics"] == {"toy_share": {"value": 42.0, "unit": "%"}}
    assert list(out)[-1] == "checks"


def test_unknown_workload_is_refused(smoke_root):
    with pytest.raises(KeyError):
        load_cell("no-such-cell", smoke_root)


def test_committed_benchmark_resolves():
    bench = load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = load_cell(w["name"], ROOT, bench)
        assert cell.traffic and cell.config
        for m in cell.per_layer:
            assert callable(metric_reader(m["name"], ROOT))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert {m["moves"] for m in cell.per_layer} <= names


def test_peak_table():
    v5e = peak("TPU v5 lite")
    assert v5e.flops_per_s == 197e12 and v5e.bytes_per_s == 819e9
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="not in the peak table"):
        peak("TPU v99 imaginary")


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(NoAccelerator):
        require_tpu(1)


def _run_cli(cwd):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve-qwen2-0.5b-chat",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    # a directory that holds only BENCHMARK.json and the files under paths
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
