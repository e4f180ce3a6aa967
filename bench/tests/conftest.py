"""Shared set-up of the benchmark's CPU tests: import paths, and a checkout
root that holds a smoke-size cell (``data/``) beside the real metric readers."""

from __future__ import annotations

import importlib.util
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load_run():
    """``bench/run.py`` as a module."""
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke_root(tmp_path) -> Path:
    """A checkout root whose ``BENCHMARK.json`` names the smoke cell
    ``serve-smoke`` (tiny widths, the real readers in ``bench/metrics``)."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copy(DATA / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copy(DATA / "qwen2-smoke.json",
                tmp_path / "bench" / "configs" / "qwen2-smoke.json")
    shutil.copy(DATA / "chat-smoke.json",
                tmp_path / "bench" / "traffic" / "chat-smoke.json")
    return tmp_path
