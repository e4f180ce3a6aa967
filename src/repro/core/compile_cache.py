"""Persistent compilation cache for AOT-compiled executables.

Restart/warmup as a measured product surface: ``MegaServe.precompile()`` and
the train loop ahead-of-time compile their bucketed step variants
(``jit(...).lower().compile()``), and this module persists the resulting
executables so the *next* process start skips XLA entirely — cold-start-to-
first-token drops from "compile the world" to "mmap + deserialize".

Modeled on jax's experimental compilation cache, with the same two defenses:

* a **versioned on-disk layout** — entries live under
  ``root/v<VERSION>/<backend>-jax<version>/<keyhash>.bin``, so a layout bump,
  a jax upgrade, or a backend switch simply *misses* (stale executables are
  never deserialized into an incompatible runtime);
* **keys over everything that shapes the executable** — the model config,
  the mesh descriptor, the bucket identity (step kind + static widths), and
  the donation signature all hash into the entry name, because two programs
  differing in any of them compile to different XLA modules.

Entries are whole pickled ``jax.experimental.serialize_executable`` triples
``(payload, in_tree, out_tree)``, plus the ids of the devices the executable
was compiled for (a process with more local devices would otherwise load it
onto all of them and reject single-device arguments), behind a small magic
header, written atomically (tmp + rename) so concurrent processes can share
one cache directory.  Every read path fails *open*: a missing, truncated, corrupt, or
version-skewed entry returns ``None`` (counted in ``stats.errors`` and
unlinked when possible) and the caller falls back to a normal compile — the
cache can only ever make startup faster, never wrong or fatal.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

_MAGIC = b"RPCC"  # repro compile cache
# the checkout this module runs from (src/repro/core/ -> three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def use_jax_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and this sets nothing.  Otherwise the cache goes to the fixed path
    ``<checkout>/.jax_cache`` (never a temp name), so every run of this
    checkout finds what the last one compiled.  Entry points call this —
    ``python -m repro`` and ``chip_smoke.py`` — never a module import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    puts: int = 0
    errors: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _jsonable(x: Any) -> Any:
    """Best-effort canonical form for key parts: dataclasses flatten to
    sorted dicts, tuples to lists, everything else through ``str`` if json
    refuses it."""
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return {k: _jsonable(v) for k, v in sorted(
            dataclasses.asdict(x).items()
        )}
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in sorted(x.items())}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return str(x)


def mesh_descriptor(mesh: Any | None) -> str:
    """Stable string for the compilation mesh: axis names x sizes + device
    kinds (a 2x4 cpu mesh and a 2x4 tpu mesh are different programs)."""
    import jax

    if mesh is None or getattr(mesh, "empty", False):
        return f"nomesh/{jax.default_backend()}x{jax.device_count()}"
    shape = dict(getattr(mesh, "shape", {}))
    kinds = sorted({d.platform for d in mesh.devices.flat})
    return f"{shape}/{'+'.join(kinds)}"


class CompileCache:
    """Directory-backed executable store (see module docstring).

    ``key(...)`` hashes arbitrary jsonable parts — callers pass the model
    config, mesh descriptor, bucket identity, and donation signature;
    ``load``/``put`` move serialized executables; ``compile(key, lowered)``
    is the one-liner the warmup paths use: hit -> deserialize, miss ->
    ``lowered.compile()`` + persist.
    """

    VERSION = 1

    def __init__(self, root: str | os.PathLike):
        self.root = Path(root)
        self.stats = CacheStats()

    # ------------------------------------------------------------- layout
    def _dir(self) -> Path:
        import jax

        return (
            self.root
            / f"v{self.VERSION}"
            / f"{jax.default_backend()}-jax{jax.__version__}"
        )

    def _path(self, key: str) -> Path:
        return self._dir() / f"{key}.bin"

    # --------------------------------------------------------------- keys
    def key(self, **parts: Any) -> str:
        """Hash the parts that shape the executable into an entry name.

        Conventional parts: ``config`` (model config dataclass), ``mesh``
        (:func:`mesh_descriptor`), ``bucket`` (step kind + every static
        width baked into the trace), ``donate`` (donated argnums).  The
        layout version and jax version/backend ride the directory, but are
        hashed in too so a relocated entry can never alias."""
        import jax

        body = json.dumps(
            {
                "v": self.VERSION,
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                **{k: _jsonable(v) for k, v in sorted(parts.items())},
            },
            sort_keys=True,
            separators=(",", ":"),
        )
        return hashlib.sha256(body.encode()).hexdigest()[:32]

    # ----------------------------------------------------------------- io
    def load(self, key: str) -> Callable | None:
        """Deserialize the cached executable for ``key``; ``None`` on miss
        *or any failure* (corrupt/truncated/alien entries are dropped)."""
        import jax
        from jax.experimental import serialize_executable as se

        path = self._path(key)
        try:
            blob = path.read_bytes()
        except OSError:
            self.stats.misses += 1
            return None
        try:
            if blob[: len(_MAGIC)] != _MAGIC:
                raise ValueError("bad magic")
            payload, in_tree, out_tree, ids = pickle.loads(blob[len(_MAGIC):])
            by_id = {d.id: d for d in jax.devices()}
            fn = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in ids],
            )
        except Exception:
            # fail open: a corrupt entry must cost one recompile, not a crash
            self.stats.errors += 1
            try:
                path.unlink()
            except OSError:
                pass
            return None
        self.stats.hits += 1
        return fn

    def put(self, key: str, compiled: Any) -> bool:
        """Serialize ``compiled`` (a ``jax`` Compiled/Loaded executable)
        under ``key``; atomic rename so concurrent writers race benignly."""
        import jax
        from jax.experimental import serialize_executable as se

        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            sharding = jax.tree.leaves(
                (compiled.input_shardings, compiled.output_shardings)
            )[0]
            ids = [d.id for d in sharding._device_assignment]
            blob = _MAGIC + pickle.dumps((payload, in_tree, out_tree, ids))
            d = self._dir()
            d.mkdir(parents=True, exist_ok=True)
            tmp = d / f".{key}.{os.getpid()}.tmp"
            tmp.write_bytes(blob)
            os.replace(tmp, self._path(key))
        except Exception:
            self.stats.errors += 1
            return False
        self.stats.puts += 1
        return True

    # ---------------------------------------------------------- composite
    def compile(self, key: str, lowered: Any) -> tuple[Callable, bool]:
        """Load-or-compile: returns ``(executable, was_hit)``.  On a miss
        the freshly compiled executable is persisted before returning."""
        fn = self.load(key)
        if fn is not None:
            return fn, True
        compiled = lowered.compile()
        self.put(key, compiled)
        return compiled, False


def aot_compile(
    jitted: Any,
    avatars: tuple,
    *,
    cache: CompileCache | None,
    key_parts: dict[str, Any],
) -> tuple[Callable, bool]:
    """AOT-compile ``jitted`` against ``avatars`` (ShapeDtypeStructs or real
    arrays), consulting ``cache`` when given.  Returns ``(exe, was_hit)``.
    On a hit the trace/lower/XLA-compile pipeline is skipped entirely; on a
    miss the executable is compiled and persisted for the next process.
    """
    if cache is None:
        lowered = jitted.lower(*avatars)
        return lowered.compile(), False
    key = cache.key(**key_parts)
    fn = cache.load(key)
    if fn is not None:
        return fn, True
    compiled = jitted.lower(*avatars).compile()
    cache.put(key, compiled)
    return compiled, False
