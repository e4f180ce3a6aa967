"""CompileCache: key sensitivity, hit/miss/put accounting, corrupt-entry
fail-open, layout-version isolation, ``aot_compile`` composition, and the
restart story itself — a second *process* reusing the first one's entries."""

import os
import subprocess
import sys
from dataclasses import dataclass

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.compile_cache import (
    _MAGIC,
    CompileCache,
    aot_compile,
    mesh_descriptor,
)

AV = (
    jax.ShapeDtypeStruct((4,), jnp.float32),
    jax.ShapeDtypeStruct((4,), jnp.float32),
)


def _jitted():
    return jax.jit(lambda a, b: a * 2.0 + b)


# ---------------------------------------------------------------- keys ---


def test_key_stable_and_sensitive(tmp_path):
    cc = CompileCache(tmp_path)
    k1 = cc.key(bucket=("decode", 8), donate=[1], mesh="nomesh/cpux1")
    k2 = cc.key(mesh="nomesh/cpux1", donate=[1], bucket=("decode", 8))
    assert k1 == k2, "key must not depend on kwarg order"
    assert cc.key(bucket=("decode", 16), donate=[1],
                  mesh="nomesh/cpux1") != k1
    assert cc.key(bucket=("decode", 8), donate=[],
                  mesh="nomesh/cpux1") != k1


def test_key_canonicalizes_dataclasses(tmp_path):
    @dataclass
    class Cfg:
        n: int = 4
        name: str = "x"

    cc = CompileCache(tmp_path)
    assert cc.key(model=Cfg()) == cc.key(model={"n": 4, "name": "x"})
    assert cc.key(model=Cfg(n=5)) != cc.key(model=Cfg(n=4))


def test_mesh_descriptor_nomesh():
    d = mesh_descriptor(None)
    assert d.startswith("nomesh/") and jax.default_backend() in d


# ------------------------------------------------------- load/put/compile ---


def test_compile_miss_then_hit_roundtrip(tmp_path):
    jf = _jitted()
    cc = CompileCache(tmp_path)
    key = cc.key(bucket="t1")
    exe, hit = cc.compile(key, jf.lower(*AV))
    assert not hit and cc.stats.puts == 1 and cc.stats.misses == 1

    cc2 = CompileCache(tmp_path)  # fresh instance, same directory
    exe2, hit2 = cc2.compile(key, jf.lower(*AV))
    assert hit2 and cc2.stats.hits == 1 and cc2.stats.puts == 0
    a = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(exe2(a, a)), np.asarray(a * 3.0))


def test_corrupt_entry_fails_open_and_unlinks(tmp_path):
    jf = _jitted()
    cc = CompileCache(tmp_path)
    key = cc.key(bucket="t2")
    cc.compile(key, jf.lower(*AV))
    path = cc._path(key)
    assert path.exists()

    # truncate mid-payload: magic is intact, pickle is not
    blob = path.read_bytes()
    path.write_bytes(blob[: len(blob) // 2])
    assert cc.load(key) is None
    assert cc.stats.errors == 1
    assert not path.exists(), "corrupt entry must be dropped"

    # bad magic: an alien file in the cache dir
    path.write_bytes(b"XXXX" + blob[len(_MAGIC):])
    assert cc.load(key) is None and cc.stats.errors == 2

    # after both failures a plain recompile repopulates the slot
    exe, hit = cc.compile(key, jf.lower(*AV))
    assert not hit and path.exists()


def test_version_bump_misses_old_entries(tmp_path):
    jf = _jitted()
    cc = CompileCache(tmp_path)
    key = cc.key(bucket="t3")
    cc.compile(key, jf.lower(*AV))

    class V2(CompileCache):
        VERSION = 2

    cc2 = V2(tmp_path)
    # same parts hash differently *and* live in a different directory —
    # a layout bump can never deserialize a v1 entry
    assert cc2.key(bucket="t3") != key
    assert cc2.load(cc2.key(bucket="t3")) is None
    assert "v1" in str(cc._path(key)) and "v2" in str(cc2._path(key))


# ----------------------------------------------------------- aot_compile ---


def test_aot_compile_without_cache(tmp_path):
    exe, hit = aot_compile(_jitted(), AV, cache=None, key_parts={})
    assert not hit
    a = jnp.ones(4, jnp.float32)
    np.testing.assert_allclose(np.asarray(exe(a, a)), 3.0)


def test_aot_compile_hit_skips_lowering(tmp_path):
    cc = CompileCache(tmp_path)
    parts = {"bucket": ("decode", 4), "donate": []}
    exe1, hit1 = aot_compile(_jitted(), AV, cache=cc, key_parts=parts)
    assert not hit1 and cc.stats.puts == 1

    class Boom:
        def lower(self, *a):  # a hit must never trace/lower
            raise AssertionError("lowered on a hit")

    exe2, hit2 = aot_compile(Boom(), AV, cache=cc, key_parts=parts)
    assert hit2
    a = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(exe2(a, a)), np.asarray(exe1(a, a)))


# -------------------------------------------------------- cross-process ---

_CHILD = """
import sys
import jax, jax.numpy as jnp
from repro.core.compile_cache import CompileCache, aot_compile

cc = CompileCache(sys.argv[1])
av = (jax.ShapeDtypeStruct((4,), jnp.float32),) * 2
exe, hit = aot_compile(jax.jit(lambda a, b: a * 2.0 + b), av,
                       cache=cc, key_parts={"bucket": "xproc"})
out = exe(jnp.arange(4, dtype=jnp.float32), jnp.ones(4, jnp.float32))
print("HIT" if hit else "MISS", [float(x) for x in out])
"""


@pytest.mark.parametrize("n_dev", [1, 4])
def test_cross_process_reuse(tmp_path, n_dev):
    """The actual restart scenario: process 2 must hit entries process 1
    wrote, and the deserialized executable must compute the same thing —
    also in a process with more local devices than the executable uses."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.abspath("src"), env.get("PYTHONPATH", "")])
    )
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"

    def run():
        r = subprocess.run(
            [sys.executable, "-c", _CHILD, str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert r.returncode == 0, r.stderr
        return r.stdout.strip()

    first, second = run(), run()
    assert first.startswith("MISS") and second.startswith("HIT")
    assert first.split(" ", 1)[1] == second.split(" ", 1)[1]


# ------------------------------------------------- JAX persistent cache ---


def test_use_jax_cache_leaves_env_dir_alone(monkeypatch, tmp_path):
    from repro.core.compile_cache import use_jax_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_jax_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_use_jax_cache_defaults_to_fixed_checkout_path(monkeypatch):
    from pathlib import Path

    from repro.core.compile_cache import use_jax_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    root = Path(__file__).resolve().parents[1]
    try:
        first, second = use_jax_cache(), use_jax_cache()
        assert first == second == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (root / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
