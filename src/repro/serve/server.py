"""MegaServe front-end: ``submit() / step() / drain()`` over the paged engine.

One ``step()`` is one scheduler tick: admit + prefill newly-arrived requests,
grow block tables (preempting if the pool is dry), run one fused decode step
for every active slot, evict finished slots so their space refills next tick.

Observability is first-class, mirroring the four-module philosophy:

* every prefill / decode step is bracketed by a MegaScan ``Tracer`` scope, so
  serving timelines flow through the same ``TraceEvent`` pipeline (chrome
  export, analytics, straggler detection) as training traces; the tick's
  host work (``tick``, ``admit``, ``upload``, ``pull``, ``publish``) has
  ``host`` scopes of its own, and under the JAX profiler every scope is a
  ``megascan.<name>`` span beside the device ops (``core.tracing.tracer``);
* an optional MegaScope ``ScopeCollector`` threads through the model; probe
  captures surface per-slot (the vmapped decode stacks them over the slot
  axis) and are attached to each request's stream records.

The static-batch baseline (`run_static`) drives the pre-existing lockstep
path for benchmarking and equivalence tests.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.compile_cache import CompileCache, aot_compile, mesh_descriptor
from repro.core.tracing.tracer import Tracer
from repro.kernels.paged_attention.kernel import walk_chunks
from repro.models import get_model
from repro.models.hooks import Collector, NULL_COLLECTOR
from repro.serve.engine import (
    make_chunk_prefill_step,
    make_decode_step,
    make_flash_prefill_step,
    make_paged_decode_step,
    make_prefill_step,
    make_seg_prefill,
    make_slot_decode_step,
    make_slot_prefill,
    make_spec_verify_step,
)
from repro.serve.paged_cache import (
    PagedKVCache,
    PoolSpec,
    blocks_for,
    pow2_bucket,
    pow2_segments,
)
from repro.serve.request import Request, RequestStatus, aggregate_metrics
from repro.serve.sampler import greedy_verify, sample
from repro.serve.scheduler import Scheduler, ServeConfig
from repro.serve.spec import Drafter, NGramDrafter


@dataclass
class StreamItem:
    """One generated token of one request, with optional probe captures.

    Capture shapes differ by phase: the admission item's captures come from
    the B=1 prefill over the whole prompt (leaves keep their batch=1/time
    axes), later items are per-slot slices of the vmapped single-token
    decode.  Consumers should branch on which phase an item came from (the
    admission item is the first of a stream / of a recompute segment).
    """
    step: int
    token: int
    captures: dict = field(default_factory=dict)


class MegaServe:
    """Continuous-batching serving front-end: ``submit() / step() / drain()``.

    One ``step()`` is one scheduler tick — admit + prefill arrivals, grow
    block tables (preempting by recompute when the pool runs dry), run one
    fused decode (or speculative verify) step over every active slot, evict
    finished requests.  Greedy decoding is deterministic across all engine
    paths: paged vs gathered, speculative vs plain, and preemption round
    trips all produce token-identical streams.

    Construction keyword knobs:

    * ``collector`` — a MegaScope ``Collector``; probe captures attach to
      each generated token's ``StreamItem`` (deep per-slot probing prefers
      ``decode_path="gathered"``, which ``"auto"`` selects for you);
    * ``tracer`` — a MegaScan ``Tracer``; every phase (``prefill``,
      ``decode``, speculative ``draft``/``verify``/``accept``) emits
      ``TraceEvent``s consumable by the chrome exporter and analytics;
    * ``drafter`` — speculative-decoding proposer (``serve.spec.Drafter``);
      defaults to the n-gram prompt-lookup drafter when
      ``serve_cfg.spec_decode`` is set;
    * ``clock`` — injectable time source for deterministic tests/replays;
    * ``use_jit`` — disable jit for step-through debugging.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        serve_cfg: ServeConfig = ServeConfig(),
        *,
        collector: Collector = NULL_COLLECTOR,
        tracer: Tracer | None = None,
        clock: Callable[[], float] | None = None,
        drafter: Drafter | None = None,
        use_jit: bool = True,
        wrap_step: Callable[[Callable], Callable] | None = None,
        registry=None,
        metrics_prefix: str = "serve.",
        prefill_only: bool = False,
        compile_cache: CompileCache | None = None,
    ):
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.params = params
        # live telemetry (a repro.obs.MetricsRegistry, or None): TTFT and
        # decode/prefill latency histograms, queue-depth / KV-occupancy
        # gauges, preemption + spec-acceptance counters publish per tick.
        # ``metrics_prefix`` namespaces the series (the router runs replica
        # i under "serve.r{i}." so per-replica load is attributable).
        self.registry = registry
        self._mpfx = metrics_prefix
        # disaggregation: a prefill-only replica admits + prefills (emitting
        # each request's first token) but never decodes — the router harvests
        # its filled slots via ``export_request`` and hands them to a decode
        # replica's ``adopt_request``
        self.prefill_only = prefill_only
        # decorator applied to every jitted engine step (prefill / decode /
        # spec-verify) — the ModulePlugin.wrap_step attach point
        self._wrap = wrap_step if wrap_step is not None else (lambda f: f)
        # persistent compilation cache (core.compile_cache): precompile()
        # consults it so a restarted process deserializes yesterday's
        # executables instead of re-running XLA
        self.compile_cache = compile_cache
        # AOT-compiled executables by (step kind, *static widths); the tick
        # paths dispatch through ``_aot_exec.get(key, jitted_fallback)`` so a
        # precompiled bucket skips the jit call cache entirely and an
        # unseen width still traces on demand
        self._aot_exec: dict[tuple, Callable] = {}
        # set once precompile() has built the ladders: from then on a step
        # key off them compiles inside a tick (see _note_fallback)
        self._precompiled = False
        self._fell_back: set[tuple] = set()
        self.sched = Scheduler(serve_cfg)
        self.tracer = tracer or Tracer(rank=0, enabled=True)
        self.collector = collector
        self._capture = collector is not NULL_COLLECTOR
        self.streams: dict[int, list[StreamItem]] = {}
        self.step_idx = 0
        self._next_rid = 0
        # offset-based clock: t=0 at construction (or last reset()), for
        # injected clocks too, so reset() re-times warmed-up runs correctly
        self._raw_clock = clock or time.perf_counter
        self._base = self._raw_clock()
        self._clock = lambda: self._raw_clock() - self._base

        # decode-path selection: deep MegaScope probing wants the vmapped
        # per-slot capture semantics of the gathered oracle; otherwise the
        # paged kernels (GQA, or the latent kernel for MLA) serve
        path = serve_cfg.decode_path
        if path == "auto":
            # speculative verification exists only on the paged path, so a
            # spec_decode request overrides the collector's gathered bias
            if serve_cfg.spec_decode:
                path = "paged"
            else:
                path = "paged" if not self._capture else "gathered"
        elif path not in ("paged", "gathered"):
            raise ValueError(f"unknown decode_path {serve_cfg.decode_path!r}")
        if cfg.use_mla and prefill_only:
            raise ValueError(
                f"{cfg.name}: disaggregated serving hands a prefilled slot to "
                "a decode replica; the latent (MLA) pool's hand-off is not "
                "built (decode and whole-prompt prefill are)")
        if serve_cfg.spec_decode and path != "paged":
            raise ValueError(
                "spec_decode requires the paged decode path "
                f"(got decode_path={serve_cfg.decode_path!r})"
            )
        self.decode_path = path

        self.kv = PagedKVCache(
            cfg,
            PoolSpec(
                num_slots=serve_cfg.num_slots,
                num_blocks=serve_cfg.num_blocks,
                block_size=serve_cfg.block_size,
                max_blocks=serve_cfg.max_blocks_per_slot,
            ),
            # XLA CPU cannot alias bf16 scatters: the paged path's in-place
            # pool writes would silently degrade to full-pool copies
            promote_store=(
                path == "paged" and jax.default_backend() == "cpu"
            ),
        )
        # take ownership of the pool buffers; keeping them referenced from
        # self.kv too would pin a second full KV pool in device memory
        self.pool, self.kv.pool = self.kv.pool, None

        # the GQA decode kernel's live-block walk: the chunks it walks per
        # layer call (``serve.paged_decode_chunks``), from the host's
        # positions; None where decode runs no paged GQA attention
        self._walk = None
        if path == "paged" and not cfg.use_mla and any(
                jax.tree.leaves(self.kv.paged)):
            self._walk = functools.partial(
                walk_chunks, block_size=serve_cfg.block_size,
                window=cfg.griffin.window if cfg.family == "griffin" else None)

        if path == "paged":
            paged_step = make_paged_decode_step(
                cfg, collector, block_size=serve_cfg.block_size,
                paged_flags=self.kv.paged, impl=serve_cfg.paged_attn_impl,
            )

            def serve_decode(params, pool, tables, tokens, pos):
                pool, logits, caps = paged_step(params, pool, tables, tokens, pos)
                return pool, jnp.argmax(logits, -1), caps
        else:
            slot_step = make_slot_decode_step(cfg, collector)

            def serve_decode(params, pool, tables, tokens, pos):
                dense = self.kv.gather(pool, tables)
                new_dense, logits, caps = slot_step(params, dense, tokens, pos)
                pool = self.kv.scatter_decode(pool, new_dense, tables, pos)
                return pool, jnp.argmax(logits, -1), caps

        # donate the pool: it is the largest buffer in the program and every
        # step rewrites it, so double-buffering it would waste a full KV pool.
        # The unwrapped jit is kept separate so precompile() can .lower() it
        # (wrap_step decorators do not preserve the AOT surface).  Each jitted
        # step's function name is its program's name on the device trace's
        # ``XLA Modules`` line: ``jit_serve_decode``, ``jit_serve_prefill``,
        # ``jit_serve_chunk``, ``jit_serve_verify``.
        self._decode_jit = (
            jax.jit(serve_decode, donate_argnums=(1,))
            if use_jit else serve_decode
        )
        self._decode = self._wrap(self._decode_jit)

        # speculative decoding: draft proposer + batched verification step.
        # Recurrent slot-state (rwkv / griffin rec blocks) integrates every
        # token into an O(1) state that cannot be rewound to the accepted
        # prefix, so speculation is limited to attention-only cache families.
        self._spec_step = self._spec_jit = None
        self.drafter = drafter
        if serve_cfg.spec_decode:
            leaves = jax.tree.leaves(self.kv.paged)
            if not (leaves and all(leaves)):
                raise ValueError(
                    f"{cfg.name}: spec_decode needs an attention-only KV "
                    "cache (recurrent slot-state cannot roll back rejected "
                    "drafts)"
                )
            if serve_cfg.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {serve_cfg.spec_k}")
            if self.drafter is None:
                self.drafter = NGramDrafter(
                    max_ngram=serve_cfg.spec_ngram_max,
                    min_ngram=serve_cfg.spec_ngram_min,
                )
            spec_fn = make_spec_verify_step(
                cfg, collector, block_size=serve_cfg.block_size,
                paged_flags=self.kv.paged, impl=serve_cfg.paged_attn_impl,
            )

            def serve_verify(params, pool, tables, tokens, pos):
                return spec_fn(params, pool, tables, tokens, pos)

            self._spec_jit = (
                jax.jit(serve_verify, donate_argnums=(1,))
                if use_jit else serve_verify
            )
            self._spec_step = self._wrap(self._spec_jit)

        self._slot_prefill = make_slot_prefill(cfg, collector)
        self._prefill_cache: dict[int, Callable] = {}
        self._use_jit = use_jit
        # right-pad prompts to power-of-two block buckets when every cache
        # leaf is attention-paged (causal masking keeps pad positions
        # invisible); recurrent-state families integrate every position, so
        # they compile per exact prompt length instead
        leaves = jax.tree.leaves(self.kv.paged)
        self._pad_prefill = bool(leaves) and all(leaves)

        # prefill-path selection: the flash kernel streams the whole padded
        # prompt straight into the slot's pool blocks (banded causal
        # attention, no dense-cache materialization or scatter copy), which
        # needs the paged decode path's pool/table plumbing and an
        # attention-only cache family
        flash_ok = path == "paged" and self._pad_prefill
        ppath = serve_cfg.prefill_path
        if ppath == "auto":
            # the flash kernel is a Pallas/TPU program; off-TPU the dispatch
            # falls back to the banded jnp oracle, which is a correctness
            # harness, not a win — one-shot dense prefill beats it there.
            # "auto" therefore only picks flash where the kernel is real (or
            # explicitly requested via paged_attn_impl); forcing
            # prefill_path="flash" still works everywhere for parity tests.
            impl = serve_cfg.paged_attn_impl
            kernel_real = impl in ("pallas", "pallas_interpret") or (
                impl == "auto" and jax.default_backend() == "tpu")
            ppath = "flash" if (flash_ok and kernel_real) else "dense"
        elif ppath not in ("flash", "dense"):
            raise ValueError(
                f"unknown prefill_path {serve_cfg.prefill_path!r}")
        if ppath == "flash" and not flash_ok:
            raise ValueError(
                f"{cfg.name}: prefill_path='flash' needs the paged decode "
                "path and an attention-only KV cache (got "
                f"decode_path={path!r})"
            )
        self.prefill_path = ppath
        self._flash_fn = None
        if ppath == "flash":
            self._flash_fn = make_flash_prefill_step(
                cfg, collector, block_size=serve_cfg.block_size,
                paged_flags=self.kv.paged, impl=serve_cfg.paged_attn_impl,
            )

        # recurrent-state families (rwkv / griffin) prefill through the
        # binary segment driver: exact pow2-width segments integrate into a
        # block-bucketed dense cache, bounding the compile set at
        # O(log^2 max_len) instead of one executable per exact prompt
        # length.  A live collector keeps the one-shot exact path so probe
        # captures still cover the whole prompt in a single forward.
        self._seg_ok = not self._pad_prefill and not self._capture
        self._seg_jit = self._seg_step = self._seg_finish = None
        if self._seg_ok:
            seg_fn = make_seg_prefill(cfg, collector)

            def seg_finish(cache, logits, pool, slot, phys):
                pool = self.kv.scatter_prefill(pool, cache, slot, phys)
                return pool, jnp.argmax(logits, -1)

            self._seg_jit = (
                jax.jit(seg_fn, donate_argnums=(1,)) if use_jit else seg_fn
            )
            self._seg_step = self._wrap(self._seg_jit)
            # donate only the pool: the dense cache's [n, 1, ...] leaves
            # cannot alias the pool's [n, slots|blocks, ...] outputs anyway
            self._seg_finish = (
                jax.jit(seg_finish, donate_argnums=(2,))
                if use_jit else seg_finish
            )

        # chunked prefill: prompts longer than chunk_len stream block-aligned
        # chunks through the q_len>1 paged path, one chunk per tick, so
        # decode ticks for other slots interleave between them
        self._chunking: dict[int, dict] = {}
        self._chunk_step = self._chunk_jit = None
        if serve_cfg.chunked_prefill:
            if path != "paged" or not self._pad_prefill:
                raise ValueError(
                    f"{cfg.name}: chunked_prefill needs the paged decode path "
                    "and an attention-only KV cache (recurrent slot-state "
                    "must integrate every position in one pass); got "
                    f"decode_path={path!r}"
                )
            chunk_fn = make_chunk_prefill_step(
                cfg, collector, block_size=serve_cfg.block_size,
                paged_flags=self.kv.paged, impl=serve_cfg.paged_attn_impl,
            )

            def serve_chunk(params, pool, tables, tokens, pos, n_last):
                pool, logits, caps = chunk_fn(
                    params, pool, tables, tokens, pos, n_last
                )
                return pool, jnp.argmax(logits, -1), caps

            self._chunk_jit = (
                jax.jit(serve_chunk, donate_argnums=(1,))
                if use_jit else serve_chunk
            )
            self._chunk_step = self._wrap(self._chunk_jit)

        # slot migration (disaggregated prefill -> decode hand-off): pure
        # gather/scatter over the pool, retraced per pow2 block-bucket width.
        # Export reads the pool (no donation); import rewrites it (donated).
        self._export_step = (
            jax.jit(self.kv.export_slot) if use_jit else self.kv.export_slot
        )
        self._import_step = (
            jax.jit(self.kv.import_slot, donate_argnums=(0,))
            if use_jit else self.kv.import_slot
        )

    @classmethod
    def from_session(cls, session, params: Any, serve_cfg: ServeConfig, **kw):
        """Construct a server wired to a ``repro.app.Session``: the session's
        MegaScan tracer and MegaScope collector (claimed by whichever module
        plugins are enabled) become this server's, and every jitted engine
        step runs through the plugins' ``wrap_step`` chain — so serving
        emits through the same observability spine as every workload."""
        kw.setdefault("registry", getattr(session, "metrics_registry", None))
        kw.setdefault("compile_cache", getattr(session, "compile_cache", None))
        return cls(
            session.model_cfg, params, serve_cfg,
            collector=session.collector, tracer=session.tracer,
            wrap_step=session.wrap_step, **kw,
        )

    # -------------------------------------------------------------- intake
    def submit(
        self,
        prompt: list[int],
        max_new: int,
        *,
        arrival: float | None = None,
        eos_id: int | None = None,
        rid: int | None = None,
    ) -> int:
        """Queue a prompt; returns its rid.  ``rid`` lets a router supply
        globally-unique ids across replicas (local auto-ids stay ahead)."""
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            self._next_rid = max(self._next_rid, rid + 1)
        req = Request(
            rid=rid, prompt=list(prompt), max_new=max_new,
            arrival=self._clock() if arrival is None else arrival,
            eos_id=eos_id,
            draft_len=self.serve_cfg.spec_k if self._spec_step else 0,
        )
        self.sched.submit(req)
        self.streams[rid] = []
        return rid

    # ------------------------------------------------------------ prefill
    def _prefill_blocks(self, n_tokens: int) -> int:
        """Block count the prefill executable for ``n_tokens`` covers: a
        power-of-two bucket (capped at the table width) for every family —
        bounding the compile cache at O(log max_len) entries even under
        preemption-recompute prompts of arbitrary length.  The one exception
        is a state family under a live MegaScope collector, which keeps the
        exact count (its one-shot exact prefill carries the whole-prompt
        capture semantics)."""
        n_blk = blocks_for(n_tokens, self.serve_cfg.block_size)
        if not self._pad_prefill and not self._seg_ok:
            return n_blk
        return min(pow2_bucket(n_blk), self.serve_cfg.max_blocks_per_slot)

    def _build_prefill_jit(self, n_blk: int) -> Callable:
        """The one-shot prefill jit for a ``n_blk``-block bucket (flash or
        dense), unwrapped so precompile() can ``.lower()`` it.  Signature:
        ``(params, tokens [1,P], n_real, pool, slot, phys [n_blk])``."""
        bs = self.serve_cfg.block_size
        cache_len = n_blk * bs

        if self._flash_fn is not None:
            flash = self._flash_fn

            def serve_prefill(params, tokens, n_real, pool, slot, phys):
                # the padded block list *is* the slot's table row: the
                # kernel writes K/V straight into those pool blocks
                pool, logits, caps = flash(
                    params, pool, phys[None, :], tokens, n_real
                )
                return pool, jnp.argmax(logits, -1), caps
        else:

            def serve_prefill(params, tokens, n_real, pool, slot, phys):
                filled, logits, caps = self._slot_prefill(
                    params, tokens, n_real, cache_len
                )
                pool = self.kv.scatter_prefill(pool, filled, slot, phys)
                return pool, jnp.argmax(logits, -1), caps

        return (
            jax.jit(serve_prefill, donate_argnums=(3,))
            if self._use_jit else serve_prefill
        )

    def _make_seg_driver(self, n_tokens: int) -> Callable:
        """Prefill driver for recurrent-state families: runs the descending
        binary decomposition of ``n_tokens`` as exact pow2 segments through
        one shared jitted segment step (shape-keyed on (width, cache_len)),
        then scatters the filled dense cache into the slot's pool blocks.
        Matches the one-shot prefill signature so ``step()`` is agnostic."""
        from repro.models import lm

        bs = self.serve_cfg.block_size
        n_blk = self._prefill_blocks(n_tokens)
        cache_len = n_blk * bs
        segs = pow2_segments(n_tokens)

        def driver(params, tokens, n_real, pool, slot, phys):
            cache = lm.init_cache(self.cfg, 1, cache_len)
            off, logits, caps = 0, None, {}
            for w in segs:
                exe = self._exec(("seg", w, cache_len), self._seg_step)
                cache, logits, caps = exe(
                    params, cache, tokens[:, off:off + w], jnp.int32(off)
                )
                off += w
            fin = self._exec(("seg_fin", n_blk), self._seg_finish)
            pool, tok = fin(cache, logits, pool, slot, phys)
            return pool, tok, caps

        return driver

    def _prefill_for(self, n_tokens: int) -> Callable:
        n_blk = self._prefill_blocks(n_tokens)
        key = n_blk if self._pad_prefill else n_tokens
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        if self._seg_ok:
            fn = self._make_seg_driver(n_tokens)
        else:
            self._note_fallback(("prefill", key))
            fn = self._wrap(self._build_prefill_jit(n_blk))
        self._prefill_cache[key] = fn
        return fn

    def _exec(self, key: tuple, fallback: Callable) -> Callable:
        """The executable ``precompile()`` built for ``key``, else the jitted
        ``fallback``, which compiles on its first call."""
        exe = self._aot_exec.get(key)
        if exe is None:
            self._note_fallback(key)
            return fallback
        return exe

    def _note_fallback(self, key: tuple) -> None:
        """A tick dispatches a step ``precompile()`` did not build, so it
        compiles mid-serving: the first such dispatch of each key counts in
        ``serve.step_fallbacks.<path>`` and leaves a ``step_fallback``
        marker (its ``path`` and ``width``) in the trace.  Before
        ``precompile()`` every step compiles on first use and nothing is
        counted."""
        if not self._precompiled or key in self._fell_back:
            return
        self._fell_back.add(key)
        path, width = key[0], key[1]
        if self.registry is not None:
            self.registry.counter(self._m(f"step_fallbacks.{path}")).inc()
        self.tracer.instant("step_fallback", path=path, width=width)

    def _m(self, name: str) -> str:
        return self._mpfx + name

    # --------------------------------------------------------------- step
    def step(self) -> dict:
        """One scheduler tick; returns what happened for observability.  A
        tick with no live slot and no arrived request has nothing to do: it
        returns at once and traces nothing, so a server polling for
        arrivals leaves no spans."""
        now = self._clock()
        nxt = self.sched.next_arrival()
        if not self.sched.active_slots() and (nxt is None or nxt > now):
            return {"admitted": [], "preempted": [], "finished": [],
                    "active": 0, "tokens": 0}
        with self.tracer.scope("tick", kind="host", step=self.step_idx):
            return self._tick(now)

    def _tick(self, now: float) -> dict:
        admitted, tokens_out = [], 0
        chunk_min = (
            self.serve_cfg.resolved_chunk_len
            if self._chunk_step is not None else None
        )

        with self.tracer.scope("admit", kind="host", step=self.step_idx):
            admissions = self.sched.admit(now)
        for adm in admissions:
            admitted.append(adm.rid)
            n_real = len(adm.tokens)
            with self.tracer.scope("admit", kind="host", rid=adm.rid,
                                   slot=adm.slot, step=self.step_idx):
                if self.registry is not None and not adm.is_recompute:
                    wait = self.sched.requests[adm.rid].queue_wait
                    if wait is not None:
                        self.registry.histogram(
                            self._m("queue_wait_s")).observe(wait)
                if chunk_min is not None and n_real > chunk_min:
                    # long prompt: don't stall this tick on a monolithic
                    # prefill — stream it chunk-by-chunk (first chunk runs
                    # just below), with decode ticks interleaving until the
                    # last chunk lands
                    self._chunking[adm.slot] = {
                        "rid": adm.rid, "toks": list(adm.tokens),
                        "written": 0, "t0": now,
                    }
                    continue
                fn = self._prefill_for(n_real)
                toks, phys = list(adm.tokens), list(adm.phys)
                n_blk = self._prefill_blocks(n_real)
                if self._pad_prefill:
                    # right-pad tokens to the bucketed cache length and the
                    # block list to the bucket width with null-block entries
                    # (their garbage K/V land in block 0, which every read
                    # masks out)
                    toks += [0] * (n_blk * self.serve_cfg.block_size - n_real)
                    phys += [0] * (n_blk - len(phys))
                elif self._seg_ok:
                    # segment driver: tokens stay exact (recurrent state must
                    # integrate every real position, none invented), but the
                    # block list pads to the bucketed scatter width
                    phys += [0] * (n_blk - len(phys))
                tokens = jnp.asarray(toks, jnp.int32)[None, :]
                n_arr, slot_arr = jnp.int32(n_real), jnp.int32(adm.slot)
                phys_arr = jnp.asarray(phys, jnp.int32)
            t_pre = self._clock()
            with self.tracer.scope(
                "prefill", kind="compute", rid=adm.rid, slot=adm.slot,
                tokens=n_real, recompute=adm.is_recompute,
                step=self.step_idx,
            ):
                self.pool, tok, caps = fn(
                    self.params, tokens, n_arr, self.pool, slot_arr, phys_arr,
                )
                tok = jax.block_until_ready(tok)
            now = self._clock()
            self._emit(adm.slot, int(tok), caps, slot_axis=False)
            self.sched.record_token(adm.slot, int(tok), now)
            if self.registry is not None:
                self.registry.histogram(self._m("prefill_s")).observe(now - t_pre)
                if not adm.is_recompute:  # recomputes kept their first TTFT
                    ttft = self.sched.requests[adm.rid].ttft
                    if ttft is not None:
                        self.registry.histogram(self._m("ttft_s")).observe(ttft)
            tokens_out += 1

        # one chunk per chunking slot per tick; a completed last chunk
        # emits that request's first token
        if self._chunking:
            tokens_out += self._chunk_tick()
            now = self._clock()

        # a prefill token can complete a request (max_new=1, or eos emitted
        # right away): evict before decode or the slot runs one step past
        # its budget and buries the eos
        finished = self.sched.evict_finished(now)

        preempted: list[int] = []
        active = self.sched.active_slots()
        # mid-chunking slots hold blocks but cannot decode yet: their ride
        # through the batched step would be wasted work, so they are excluded
        # from drafting/decoding (their garbage write at pos lands where the
        # first real decode write overwrites it before it could become live)
        runnable = [s for s in active if s not in self._chunking]
        if not self.prefill_only:
            # speculative drafts are gathered before capacity planning: a
            # slot about to verify k drafts needs 1 + k write positions
            drafts: dict[int, list[int]] = {}
            if self._spec_step is not None and runnable:
                drafts = self._collect_drafts()
            preempted = self.sched.ensure_capacity(
                {s: 1 + len(d) for s, d in drafts.items()} if drafts else None
            )
            active = self.sched.active_slots()
            runnable = [s for s in active if s not in self._chunking]
            drafts = {s: d for s, d in drafts.items() if s in set(runnable)}
            if runnable:
                if drafts:
                    tokens_out += self._spec_tick(runnable, drafts)
                else:
                    tokens_out += self._decode_tick(runnable)
                now = self._clock()

        finished += self.sched.evict_finished(now)
        if admitted or active:
            self.step_idx += 1  # idle ticks don't count as engine steps
        # preempted alone still publishes: ensure_capacity can evict every
        # slot (pool too tight for even one), and that count must not vanish
        if self.registry is not None and (admitted or active or preempted):
            with self.tracer.scope("publish", kind="host"):
                self._publish_tick(active, preempted, tokens_out)
        return {
            "admitted": admitted,
            "preempted": preempted,
            "finished": finished,
            "active": len(active),
            "tokens": tokens_out,
        }

    def _chunk_tick(self) -> int:
        """Advance every mid-chunking slot by one prompt chunk; returns the
        number of first tokens emitted (chunking runs that finished).  A slot
        whose rid no longer matches was preempted mid-chunking — its entry is
        dropped and the re-admission restarts chunking from scratch, so
        greedy streams stay token-identical under preemption."""
        scfg = self.serve_cfg
        C, bs = scfg.resolved_chunk_len, scfg.block_size
        out = 0
        for slot in sorted(self._chunking):
            st = self._chunking[slot]
            if self.sched.slots[slot] != st["rid"]:
                del self._chunking[slot]
                continue
            toks, w = st["toks"], st["written"]
            n_real = len(toks)
            chunk = toks[w : w + C]
            final = w + C >= n_real
            n_last = (n_real - 1 - w) if final else (len(chunk) - 1)
            chunk = chunk + [0] * (C - len(chunk))
            # table width: pow2 bucket over the blocks this chunk can touch,
            # so the compile cache stays O(log max_blocks) like live tables
            width = min(
                pow2_bucket(blocks_for(w + C, bs)), scfg.max_blocks_per_slot
            )
            tables = jnp.asarray(self.sched.tables[slot : slot + 1, :width])
            fn = self._exec(("chunk", width), self._chunk_step)
            t0 = self._clock()
            with self.tracer.scope(
                "prefill_chunk", kind="compute", rid=st["rid"], slot=slot,
                offset=w, tokens=min(C, n_real - w), step=self.step_idx,
            ):
                self.pool, tok, caps = fn(
                    self.params, self.pool, tables,
                    jnp.asarray(chunk, jnp.int32)[None, :],
                    jnp.asarray([w], jnp.int32), jnp.int32(n_last),
                )
                tok = jax.block_until_ready(tok)
            now = self._clock()
            if self.registry is not None:
                self.registry.histogram(self._m("chunk_s")).observe(now - t0)
            st["written"] = w + C
            if not final:
                continue
            del self._chunking[slot]
            self._emit(slot, int(tok), caps, slot_axis=False)
            self.sched.record_token(slot, int(tok), now)
            req = self.sched.requests[st["rid"]]
            if self.registry is not None:
                self.registry.histogram(
                    self._m("prefill_s")).observe(now - st["t0"])
                if req.n_preemptions == 0 and req.ttft is not None:
                    self.registry.histogram(self._m("ttft_s")).observe(req.ttft)
            out += 1
        return out

    def _publish_tick(
        self, active: list[int], preempted: list[int], tokens_out: int
    ) -> None:
        """Per-tick serve series into the registry (host bookkeeping only)."""
        reg, alloc = self.registry, self.sched.allocator
        reg.counter(self._m("tokens")).inc(tokens_out)
        if preempted:
            reg.counter(self._m("preemptions")).inc(len(preempted))
        reg.gauge(self._m("queue_depth")).set(len(self.sched.waiting))
        reg.gauge(self._m("active_slots")).set(len(active))
        used = alloc.num_blocks - alloc.reserved - alloc.num_free
        reg.gauge(self._m("kv_occupancy")).set(
            used / max(self.serve_cfg.usable_blocks, 1)
        )

    def _publish_picks(self, picks: np.ndarray) -> None:
        """Expert load of one decode call, from its ``[n_moe_layers,
        experts_held]`` pick counts: ``moe.held_picks``, the picks on held
        experts per MoE layer, and ``moe.max_expert_picks``, the most that one
        held expert got in any layer."""
        reg = self.registry
        reg.histogram(self._m("moe.held_picks")).observe(
            float(picks.sum()) / picks.shape[0])
        reg.histogram(self._m("moe.max_expert_picks")).observe(
            float(picks.max()))

    def _live_tables(self, active: list[int]) -> jax.Array:
        """Block tables for the decode/verify step.  On the paged path they
        are sliced to the live-block high-water mark (next power of two): the
        decode kernel walks live blocks only at any width, but the tables'
        upload and the XLA fallback's gather then cost O(max live kv_len),
        not O(pool max_len); bucketing keeps the compile cache at
        O(log max_blocks)."""
        if self.decode_path != "paged":
            return jnp.asarray(self.sched.tables)
        live = max((len(self.sched.blocks[s]) for s in active), default=1)
        hb = min(pow2_bucket(live), self.serve_cfg.max_blocks_per_slot)
        return jnp.asarray(self.sched.tables[:, :hb])

    def _decode_tick(self, active: list[int]) -> int:
        """One plain fused decode step over every active slot (1 token each)."""
        with self.tracer.scope("upload", kind="host"):
            toks = jnp.asarray(self.sched.last_tok, jnp.int32)
            pos = jnp.asarray(self.sched.pos, jnp.int32)
            tables = self._live_tables(active)
        fn = self._exec(("decode", tables.shape[1]), self._decode)
        t_dec = self._clock()
        with self.tracer.scope(
            "decode", kind="compute", step=self.step_idx,
            active=len(active), tokens=len(active),
        ):
            self.pool, next_tok, caps = fn(
                self.params, self.pool, tables, toks, pos
            )
            next_tok = jax.block_until_ready(next_tok)
        now = self._clock()
        if self.registry is not None:
            self.registry.histogram(self._m("decode_step_s")).observe(now - t_dec)
            if self._walk is not None:
                kvl = np.asarray(self.sched.pos)[active] + 1
                self.registry.histogram(self._m("paged_decode_chunks")).observe(
                    float(self._walk(kvl, n_entries=tables.shape[1]).sum()))
        with self.tracer.scope("pull", kind="host", active=len(active)):
            next_tok = np.asarray(next_tok)
            picks = caps.pop("moe_held_picks", None)
            if picks is not None and self.registry is not None:
                self._publish_picks(np.asarray(picks))
            for s in active:
                self.sched.advance(s)
                self._emit(s, int(next_tok[s]), caps,
                           slot_axis=(self.decode_path == "gathered"))
                self.sched.record_token(s, int(next_tok[s]), now)
        return len(active)

    # --------------------------------------------------------- speculation
    def _collect_drafts(self) -> dict[int, list[int]]:
        """Ask the drafter for proposals, one per active slot.

        Each request's draft budget is its adapted ``draft_len`` capped so
        the verify writes stay inside the slot's table reach and the
        request's remaining token budget (drafting past either is pure
        waste).  Requests whose budget has adapted to 0 re-probe with a
        1-token draft every ``spec_retry`` steps."""
        t0 = self._clock()
        drafts: dict[int, list[int]] = {}
        proposed = 0
        for s in self.sched.active_slots():
            if s in self._chunking:   # no committed tokens to draft from yet
                continue
            req = self.sched.requests[self.sched.slots[s]]
            if req.draft_len == 0:
                # exponential re-probe backoff: a request that keeps failing
                # its probes gets probed less and less often, so a hostile
                # workload converges to plain decode throughput
                req.spec_idle += 1
                if req.spec_idle >= self.serve_cfg.spec_retry * req.spec_backoff:
                    req.spec_idle = 0
                    req.draft_len = 1
                continue
            k = min(
                req.draft_len,
                self.serve_cfg.spec_k,
                req.remaining - 1,
                self.serve_cfg.max_len - self.sched.pos[s] - 1,
            )
            if k <= 0:
                continue
            # clamp: the Drafter protocol is a user plug point, and a
            # proposal longer than k would overflow the verify row / the
            # slot's grown table reach
            d = list(self.drafter.propose(req.prompt + req.generated, k))[:k]
            if d:
                drafts[s] = d
                proposed += len(d)
        self.tracer.record(
            "draft", t0, self._clock() - t0, kind="host",
            step=self.step_idx, proposed=proposed, slots=len(drafts),
        )
        return drafts

    def _spec_tick(self, active: list[int], drafts: dict[int, list[int]]) -> int:
        """One batched draft-verification step.

        Every active slot rides the same ``Q = spec_k + 1``-token forward:
        row 0 is its last committed token, rows 1..k its draft, the rest
        padding (causally invisible to the rows that matter).  Greedy
        acceptance (``sampler.greedy_verify``) commits the agreeing prefix
        plus one correction/bonus token per slot — between 1 and ``k + 1``
        tokens — then the block tables are rewound past the committed
        high-water mark (``Scheduler.trim_blocks``)."""
        scfg = self.serve_cfg
        Q = scfg.spec_k + 1
        with self.tracer.scope("upload", kind="host"):
            rows = np.zeros((scfg.num_slots, Q), np.int32)
            for s in active:
                row = [self.sched.last_tok[s]] + drafts.get(s, [])
                rows[s, : len(row)] = row
            toks = jnp.asarray(rows)
            pos = jnp.asarray(self.sched.pos, jnp.int32)
            tables = self._live_tables(active)
        fn = self._exec(("verify", tables.shape[1]), self._spec_step)
        drafted = sum(len(d) for d in drafts.values())
        v0 = self._clock()
        # the verify TraceEvent is recorded below, once acceptance knows its
        # token count; the profiler span brackets the device call itself
        with self.tracer.annotate("verify", step=self.step_idx,
                                  active=len(active), drafted=drafted):
            self.pool, greedy, _logits, caps = fn(
                self.params, self.pool, tables, toks, pos
            )
            greedy = np.asarray(jax.block_until_ready(greedy))
        now = self._clock()
        v_dur = now - v0
        t0 = now
        emitted_total = accepted_total = 0
        for s in active:
            d = drafts.get(s, [])
            n_acc, emitted = greedy_verify(greedy[s], d)
            req = self.sched.requests[self.sched.slots[s]]
            if d:
                req.spec_proposed += len(d)
                req.spec_accepted += n_acc
                accepted_total += n_acc
                # acceptance-rate adaptation: the verify forward costs the
                # same whatever the draft length (Q is padded), so any
                # acceptance at all restores the full budget.  An *isolated*
                # miss is the signature of a continuation shift — the next
                # lookup either proposes the new pattern or nothing at all —
                # so only consecutive zero-acceptance verifies (a drafter
                # that is systematically wrong) shut speculation off for
                # this request, with exponentially backed-off re-probes:
                # each wasted verify is a plain decode step at multi-token
                # price, so a hostile workload must degrade to plain decode
                if n_acc > 0:
                    req.draft_len = scfg.spec_k
                    req.spec_miss = 0
                    req.spec_backoff = 1
                else:
                    req.spec_miss += 1
                    if req.spec_miss >= 3:
                        req.draft_len = 0
                        req.spec_backoff = min(req.spec_backoff * 2, 16)
            n_commit = 0
            for t in emitted[: req.remaining]:
                n_commit += 1
                self._emit(s, int(t), caps, slot_axis=False)
                self.sched.record_token(s, int(t), now)
                if req.eos_id is not None and int(t) == req.eos_id:
                    break
            self.sched.advance(s, n_commit)
            emitted_total += n_commit
        self.sched.trim_blocks()
        # the verify event is recorded after acceptance so it can carry the
        # realized token count (the scope context manager freezes args at
        # entry); ts/dur still bracket exactly the jitted verification
        self.tracer.record(
            "verify", v0, v_dur, kind="compute", step=self.step_idx,
            active=len(active), tokens=emitted_total, drafted=drafted,
        )
        self.tracer.record(
            "accept", t0, self._clock() - t0, kind="host",
            step=self.step_idx, accepted=accepted_total, emitted=emitted_total,
        )
        if self.registry is not None:
            reg = self.registry
            reg.histogram(self._m("verify_step_s")).observe(v_dur)
            if drafted:
                reg.counter(self._m("spec_proposed")).inc(drafted)
                reg.counter(self._m("spec_accepted")).inc(accepted_total)
                reg.gauge(self._m("spec_accept_rate")).set(
                    reg.counter(self._m("spec_accepted")).value
                    / reg.counter(self._m("spec_proposed")).value
                )
        return emitted_total

    def _emit(self, slot: int, tok: int, caps: Any, *, slot_axis: bool) -> None:
        rid = self.sched.slots[slot]
        captures = {}
        if self._capture and caps:
            # slot_axis is only set on the gathered path, where vmap stacks
            # *every* capture leaf over the slot axis, so slicing is exact.
            # The batched paged step offers no such guarantee (probe
            # reductions may collapse the axis entirely), so its captures
            # attach whole — deep per-slot probing should use
            # decode_path="gathered" (what "auto" picks under a collector).
            take = (lambda a: np.asarray(a[slot])) if slot_axis else np.asarray
            captures = jax.tree.map(take, caps)
        self.streams[rid].append(StreamItem(self.step_idx, tok, captures))

    # ---------------------------------------------------------- migration
    def exportable(self) -> list[int]:
        """Rids whose prefill has completed here but whose decode has not
        begun — on a prefill-only replica these are ready for hand-off (a
        colocated replica never exports; it decodes its own prefills)."""
        if not self.prefill_only:
            return []
        out = []
        for s in self.sched.active_slots():
            if s in self._chunking:
                continue
            req = self.sched.requests[self.sched.slots[s]]
            if req.t_first_token is not None and not req.done:
                out.append(req.rid)
        return out

    def export_request(self, rid: int) -> dict:
        """Pull a prefilled request out of this replica: its KV blocks leave
        the pool as an ``export_slot`` bundle (padded to the pow2 block
        bucket with null-block entries), its slot/blocks are freed, and the
        ``Request`` object + token stream ride the package so timing fields
        and emitted tokens survive the migration."""
        slot = next(
            (s for s, r in enumerate(self.sched.slots) if r == rid), None)
        if slot is None:
            raise ValueError(f"rid {rid} not active (cannot export)")
        req = self.sched.requests[rid]
        phys = list(self.sched.blocks[slot])
        pos = self.sched.pos[slot]
        last_tok = self.sched.last_tok[slot]
        width = min(
            pow2_bucket(max(len(phys), 1)), self.serve_cfg.max_blocks_per_slot
        )
        padded = phys + [0] * (width - len(phys))
        with self.tracer.scope(
            "kv_export", kind="comm", rid=rid, slot=slot, blocks=len(phys),
            step=self.step_idx,
        ):
            bundle = self._export_step(
                self.pool, jnp.asarray(padded, jnp.int32), jnp.int32(slot)
            )
            bundle = jax.block_until_ready(bundle)
        stream = self.streams.pop(rid)
        self.sched.release_request(rid)
        return {
            "req": req, "stream": stream, "bundle": bundle,
            "n_blocks": len(phys), "width": width,
            "pos": pos, "last_tok": last_tok,
        }

    def adopt_request(self, package: dict) -> bool:
        """Install an ``export_request`` package into this replica: claim a
        slot + blocks, scatter the bundle's KV into them, and resume decode
        from the migrated cursor.  Returns False (package untouched) when no
        slot/blocks are free — the router retries next tick.  Bit-identical
        KV import means the greedy continuation is token-identical to the
        colocated engine's."""
        req = package["req"]
        got = self.sched.adopt(req, package["pos"], package["last_tok"])
        if got is None:
            return False
        slot, phys = got
        padded = phys + [0] * (package["width"] - len(phys))
        with self.tracer.scope(
            "kv_import", kind="comm", rid=req.rid, slot=slot,
            blocks=package["n_blocks"], step=self.step_idx,
        ):
            self.pool = self._import_step(
                self.pool, package["bundle"],
                jnp.asarray(padded, jnp.int32), jnp.int32(slot),
            )
        self.streams[req.rid] = package["stream"]
        self._next_rid = max(self._next_rid, req.rid + 1)
        return True

    # --------------------------------------------------------- precompile
    def _avatar(self, tree: Any) -> Any:
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
        )

    def _width_ladder(self, hi: int) -> list[int]:
        """The pow2 bucket ladder 1, 2, 4, ... capped at ``hi`` — exactly
        the static widths the tick paths can request."""
        ws, w = [], 1
        while True:
            ws.append(w)
            if w >= hi:
                return ws
            w = min(2 * w, hi)

    def _aot(
        self, jitted: Any, avatars: tuple, *, kind: str,
        donate: tuple, extra: dict,
    ) -> tuple[Callable, bool]:
        """AOT-compile one bucketed step variant through the persistent
        compile cache (or plain lower+compile when none is attached)."""
        params_sig = [
            f"{l.shape}/{l.dtype}" for l in jax.tree.leaves(self._avatar(self.params))
        ]
        return aot_compile(
            jitted, avatars, cache=self.compile_cache,
            key_parts={
                "model": self.cfg,
                "serve": self.serve_cfg,
                "mesh": mesh_descriptor(None),
                "capture": self._capture,
                "params": params_sig,
                "step": kind,
                "donate": list(donate),
                **extra,
            },
        )

    def precompile(self) -> dict:
        """Ahead-of-time compile every bucketed step variant before serving.

        Each engine step retraces per pow2 static-shape bucket (decode /
        verify table widths, chunk widths, prefill prompt buckets, recurrent
        segment widths x cache buckets), and which bucket occurs first is
        timing-dependent — a shape reached mid-run pays its XLA compile
        inside the serving loop (hundreds of ms), exactly the jitter a
        latency SLO or cold-start benchmark cannot absorb.  This walks every
        ladder through ``jit(...).lower().compile()`` (consulting the
        persistent ``compile_cache`` when attached, so a restarted process
        deserializes instead of recompiling) and parks the executables where
        the tick paths dispatch first (``_aot_exec`` / ``_prefill_cache``).

        Returns per-path counts and wall-clock milliseconds::

            {"decode": {"count", "ms"}, "prefill": {...}, "chunk": {...},
             "verify": {...}, "total": n[, "cache": CacheStats dict]}

        and publishes each path's ms as a ``precompile_ms.<path>`` gauge.
        """
        out: dict[str, Any] = {
            p: {"count": 0, "ms": 0.0}
            for p in ("decode", "prefill", "chunk", "verify")
        }
        out["total"] = 0
        if not self._use_jit:
            return out
        scfg = self.serve_cfg
        n_slots, max_w, bs = scfg.num_slots, scfg.max_blocks_per_slot, scfg.block_size
        i32 = jnp.int32
        pa = self._avatar(self.params)
        pool_a = self._avatar(self.pool)
        scalar = jax.ShapeDtypeStruct((), i32)

        def run(path, jitted, avatars, *, donate, **extra):
            t0 = self._raw_clock()
            exe, _hit = self._aot(
                jitted, avatars, kind=path, donate=donate, extra=extra
            )
            out[path]["count"] += 1
            out[path]["ms"] += (self._raw_clock() - t0) * 1e3
            return exe

        # ---- decode: one executable per live-table width bucket
        widths = (
            self._width_ladder(max_w)
            if self.decode_path == "paged" else [max_w]
        )
        toks_a = jax.ShapeDtypeStruct((n_slots,), i32)
        pos_a = jax.ShapeDtypeStruct((n_slots,), i32)
        for w in widths:
            tb = jax.ShapeDtypeStruct((n_slots, w), i32)
            exe = run("decode", self._decode_jit,
                      (pa, pool_a, tb, toks_a, pos_a),
                      donate=(1,), width=w)
            self._aot_exec[("decode", w)] = self._wrap(exe)

        # ---- speculative verify: same width ladder, Q = spec_k + 1 rows
        if self._spec_jit is not None:
            vt = jax.ShapeDtypeStruct((n_slots, scfg.spec_k + 1), i32)
            for w in widths:
                tb = jax.ShapeDtypeStruct((n_slots, w), i32)
                exe = run("verify", self._spec_jit,
                          (pa, pool_a, tb, vt, pos_a),
                          donate=(1,), width=w)
                self._aot_exec[("verify", w)] = self._wrap(exe)

        # ---- chunked prefill: the chunk-tick table widths actually reachable
        if self._chunk_jit is not None:
            C = scfg.resolved_chunk_len
            cws = sorted({
                min(pow2_bucket(blocks_for(off + C, bs)), max_w)
                for off in range(0, scfg.max_len, C)
            })
            ct = jax.ShapeDtypeStruct((1, C), i32)
            cp = jax.ShapeDtypeStruct((1,), i32)
            for w in cws:
                tb = jax.ShapeDtypeStruct((1, w), i32)
                exe = run("chunk", self._chunk_jit,
                          (pa, pool_a, tb, ct, cp, scalar),
                          donate=(1,), width=w)
                self._aot_exec[("chunk", w)] = self._wrap(exe)

        # ---- prefill: prompt block-bucket ladder (padded families) or the
        # pow2 segment-width x cache-bucket grid (recurrent families)
        if self._pad_prefill:
            for n_blk in self._width_ladder(max_w):
                tok_a = jax.ShapeDtypeStruct((1, n_blk * bs), i32)
                phys_a = jax.ShapeDtypeStruct((n_blk,), i32)
                exe = run("prefill", self._build_prefill_jit(n_blk),
                          (pa, tok_a, scalar, pool_a, scalar, phys_a),
                          donate=(3,), n_blk=n_blk,
                          prefill_impl=self.prefill_path)
                self._prefill_cache[n_blk] = self._wrap(exe)
        elif self._seg_ok:
            from repro.models import lm

            for n_blk in self._width_ladder(max_w):
                cache_len = n_blk * bs
                cache_a = jax.eval_shape(
                    lambda L=cache_len: lm.init_cache(self.cfg, 1, L)
                )
                seg_out = None
                for w in self._width_ladder(cache_len):
                    tok_a = jax.ShapeDtypeStruct((1, w), i32)
                    exe = run("prefill", self._seg_jit,
                              (pa, cache_a, tok_a, scalar),
                              donate=(1,), seg_w=w, cache_len=cache_len)
                    self._aot_exec[("seg", w, cache_len)] = self._wrap(exe)
                    if seg_out is None:
                        seg_out = jax.eval_shape(
                            self._seg_jit, pa, cache_a, tok_a, scalar
                        )
                phys_a = jax.ShapeDtypeStruct((n_blk,), i32)
                exe = run("prefill", self._seg_finish,
                          (self._avatar(seg_out[0]),
                           self._avatar(seg_out[1]),
                           pool_a, scalar, phys_a),
                          donate=(2,), fin_blk=n_blk)
                self._aot_exec[("seg_fin", n_blk)] = exe

        out["total"] = sum(
            v["count"] for k, v in out.items() if isinstance(v, dict)
        )
        if self.compile_cache is not None:
            out["cache"] = self.compile_cache.stats.as_dict()
        if self.registry is not None:
            for p in ("decode", "prefill", "chunk", "verify"):
                self.registry.gauge(
                    self._m(f"precompile_ms.{p}")).set(out[p]["ms"])
        self._precompiled = True
        return out

    # -------------------------------------------------------------- drain
    def drain(
        self,
        max_steps: int = 100_000,
        *,
        on_step: Callable[[list, dict], None] | None = None,
    ) -> dict[int, list[int]]:
        """Run until every submitted request finishes; returns token streams.

        ``max_steps`` bounds productive engine steps and (separately) idle
        ticks spent waiting for future arrivals; with an injected clock that
        never reaches the next arrival this raises instead of spinning.
        ``on_step(events, report)`` observes each tick — the TraceEvents it
        emitted and the scheduler report — which is how Session plugins
        attach to the serving loop."""
        work = idle = 0
        while not self.sched.all_done:
            n_ev = len(self.tracer.events)
            out = self.step()
            if on_step is not None:
                on_step(self.tracer.events[n_ev:], out)
            if out["admitted"] or out["active"]:
                work += 1
                idle = 0
                if work > max_steps:
                    raise RuntimeError(f"drain: not done after {work} steps")
                continue
            idle += 1
            if idle > max_steps:
                raise RuntimeError(
                    f"drain: stalled waiting for arrival at "
                    f"t={self.sched.next_arrival()} (now={self._clock():.3f})"
                )
            nxt = self.sched.next_arrival()
            if nxt is not None:
                # real sleep is harmless for injected clocks too: either the
                # clock advances elsewhere or the idle guard above fires
                time.sleep(max(0.0, min(nxt - self._clock(), 1e-3)))
        return {rid: [it.token for it in s] for rid, s in self.streams.items()}

    # ------------------------------------------------------------ metrics
    def metrics(self) -> dict:
        """Fleet metrics: tokens/s, TTFT/latency percentiles, preemptions,
        engine steps, and (when speculation is on) draft acceptance."""
        reqs = list(self.sched.requests.values())
        out = {
            **aggregate_metrics(reqs, wall=self._clock()),
            "steps": self.step_idx,
        }
        if self._spec_step is not None:
            proposed = sum(r.spec_proposed for r in reqs)
            accepted = sum(r.spec_accepted for r in reqs)
            out["spec_proposed"] = proposed
            out["spec_accepted"] = accepted
            out["spec_accept_rate"] = accepted / proposed if proposed else 0.0
        return out

    def trace_events(self):
        return self.tracer.events

    def reset(self) -> None:
        """Drop finished requests/streams/traces and restart the clock —
        lets a warmed-up server (compiled steps) time a fresh workload."""
        if not self.sched.all_done:
            raise RuntimeError("reset() with requests still in flight")
        self.sched.requests.clear()
        self.streams.clear()
        self.tracer.clear()
        self._chunking.clear()
        self.step_idx = 0
        self._base = self._raw_clock()


# ---------------------------------------------------------------------------
# Static-batch baseline (the pre-existing lockstep path)
# ---------------------------------------------------------------------------


class StaticRunner:
    """Length-bucketed static batching: requests sharing one prompt length
    batch together in arrival order (the static prefill/decode steps require
    a single prompt length and one shared position), the whole batch decodes
    in lockstep to the slowest member's budget, and a batch only launches
    once its last member has arrived.  Holds its jitted steps so repeat runs
    (benchmark warmup) reuse compilations."""

    def __init__(self, cfg: ModelConfig, params: Any):
        self.cfg = cfg
        self.params = params
        self.model = get_model(cfg)
        self.prefill = jax.jit(make_prefill_step(cfg))
        self.decode = jax.jit(make_decode_step(cfg))

    def run(
        self,
        requests: list[tuple[list[int], int, float]],  # (prompt, max_new, arrival)
        *,
        batch_size: int,
        tracer: Tracer | None = None,
        clock: Callable[[], float] | None = None,
    ) -> tuple[dict[int, list[int]], dict]:
        """Returns (rid -> tokens, metrics); rids index ``requests``."""
        cfg, params = self.cfg, self.params
        tracer = tracer or Tracer(rank=0, enabled=True)
        t0 = time.perf_counter()
        clock = clock or (lambda: time.perf_counter() - t0)
        model, prefill, decode = self.model, self.prefill, self.decode

        reqs = [Request(rid=i, prompt=list(p), max_new=m, arrival=a)
                for i, (p, m, a) in enumerate(requests)]
        buckets: dict[int, list[Request]] = {}
        for r in reqs:
            buckets.setdefault(r.prompt_len, []).append(r)

        outputs: dict[int, list[int]] = {}
        for P in sorted(buckets):
            group = buckets[P]
            for i in range(0, len(group), batch_size):
                members = group[i : i + batch_size]
                B = len(members)
                steps = max(r.max_new for r in members)
                launch = max(r.arrival for r in members)
                stalls = 0
                while clock() < launch:
                    before = clock()
                    time.sleep(min(launch - before, 1e-3))
                    # injected clocks may be simulated: bail out instead of
                    # spinning forever if time never advances (~10 s real)
                    stalls = stalls + 1 if clock() <= before else 0
                    if stalls > 10_000:
                        raise RuntimeError(
                            f"static: stalled waiting for batch launch at "
                            f"t={launch} (now={clock():.3f})"
                        )
                cache = model.init_cache(cfg, B, P + steps)
                prompts = jnp.asarray([r.prompt for r in members], jnp.int32)
                with tracer.scope("prefill", kind="compute", tokens=B * P, batch=B):
                    cache, logits = prefill(params, {"tokens": prompts}, cache)
                    jax.block_until_ready(logits)
                tok = sample(logits, temperature=0.0)
                now = clock()
                for b, r in enumerate(members):
                    r.t_admitted = launch
                    r.t_first_token = now
                    r.generated.append(int(tok[b]))
                    if len(r.generated) == r.max_new:
                        r.t_finished = now
                for s in range(steps - 1):
                    with tracer.scope("decode", kind="compute", step=s, active=B,
                                      tokens=B):
                        cache, logits, tok = decode(params, cache, tok, jnp.int32(P + s))
                        tok = jax.block_until_ready(tok)
                    now = clock()
                    for b, r in enumerate(members):
                        if len(r.generated) < r.max_new:
                            r.generated.append(int(tok[b]))
                            if len(r.generated) == r.max_new:
                                r.t_finished = now
                for r in members:
                    if r.t_finished is None:
                        r.t_finished = now
                    r.status = RequestStatus.FINISHED
                    outputs[r.rid] = list(r.generated)

        metrics = aggregate_metrics(reqs, wall=clock())
        return outputs, metrics

def run_static(
    cfg: ModelConfig,
    params: Any,
    requests: list[tuple[list[int], int, float]],
    *,
    batch_size: int,
    tracer: Tracer | None = None,
    clock: Callable[[], float] | None = None,
) -> tuple[dict[int, list[int]], dict]:
    """One-shot convenience wrapper over ``StaticRunner``."""
    return StaticRunner(cfg, params).run(
        requests, batch_size=batch_size, tracer=tracer, clock=clock
    )


def make_poisson_workload(
    cfg: ModelConfig,
    *,
    n: int,
    rate: float,
    prompt_lens: tuple[int, ...],
    max_new_range: tuple[int, int],
    num_slots: int,
    block_size: int = 16,
    num_blocks: int = 0,
    seed: int = 0,
    traffic: str = "poisson",
):
    """Shared CLI workload builder (launcher + benchmark): arrival specs
    (``traffic`` picks the process — ``poisson`` / ``bursty`` MMPP /
    ``diurnal`` sinusoidal), random token prompts, and a ``ServeConfig``
    sized so the worst request fits one slot — ``num_blocks=0`` sizes the
    pool for zero preemption (every slot can hold its worst case
    simultaneously, plus the reserved null block).  The sizing also covers
    speculative decoding: draft budgets are capped so every real verify
    write stays inside the worst-case footprint (``_collect_drafts``).
    Returns (specs, prompts by rid, serve_cfg)."""
    from repro.core.simkit.workload import (
        bursty_requests,
        diurnal_requests,
        poisson_requests,
    )

    gens = {
        "poisson": poisson_requests,
        "bursty": bursty_requests,
        "diurnal": diurnal_requests,
    }
    if traffic not in gens:
        raise ValueError(
            f"unknown traffic {traffic!r}; one of {sorted(gens)}"
        )
    specs = gens[traffic](
        n, rate, prompt_lens=prompt_lens, max_new_range=max_new_range,
        seed=seed,
    )
    rng = np.random.default_rng(seed)
    prompts = {
        s.rid: rng.integers(2, cfg.vocab_size, size=s.prompt_len).tolist()
        for s in specs
    }
    worst = max(blocks_for(s.prompt_len + s.max_new, block_size) for s in specs)
    serve_cfg = ServeConfig(
        num_slots=num_slots, block_size=block_size,
        num_blocks=num_blocks or (num_slots * worst + 1),
        max_blocks_per_slot=worst,
    )
    return specs, prompts, serve_cfg
