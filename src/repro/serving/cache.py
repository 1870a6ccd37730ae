"""Artifact/compile cache for the serving engine.

Two maps, both keyed on the engine identity ``(arch, fingerprint)`` — the
architecture name and the serving plan's *content fingerprint*
(`repro.serving.fleet.comp_fingerprint`, hashing codebook values, masks and
``msr_bits``). The fingerprint replaced the old bare ``compress_k`` integer:
two plans with equal k but different codebooks or MSR settings used to
collide and silently share executables and exported artifacts built from the
*first* plan's weights.

* ``(arch, fingerprint, shape-key)`` -> compiled executables. Wave/oneshot
  modes key on a `BucketSpec` and get a `CompiledStep` (prefill + lockstep
  decode); the slot-level engine keys on ``("group", batch, total_len)`` for
  its active-masked group decode (`GroupStep`) and on
  ``("chunk", rows, chunk, batch, total_len)`` for each chunked-prefill
  executable (`ChunkStep`) — a small *fixed* set determined by the config's
  chunk buckets, never by request shapes. Compilation happens exactly once
  per key, through `jax.jit(...).lower(...).compile()`; the resulting
  executables *reject* any differently-shaped call with a ``TypeError``
  instead of silently recompiling, so "compiles once per shape, never per
  request" is enforced structurally, not just measured.
* ``(arch, fingerprint)`` -> exported `ServeArtifact` tree + summary for the
  packed 4-bit deployment form (`repro.core.lm_compress.export_lm_matmuls`),
  used for footprint reporting and parity checks.

``compile_count`` increments on every executable build; the serving benchmark
gates on it staying flat after warmup.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.layers import QuantConfig
from repro.serving.bucketing import BucketSpec, EngineConfig


@dataclasses.dataclass(frozen=True)
class CompiledStep:
    """AOT executables for one bucket: ``prefill(params, prompts)`` ->
    (logits, cache); ``decode(params, cache, tok)`` -> (logits, cache)."""

    bucket: BucketSpec
    prefill: Callable
    decode: Callable


@dataclasses.dataclass(frozen=True)
class GroupStep:
    """AOT decode for one slot group: ``decode(params, cache, tok, active)``
    -> (logits, cache). Rows where ``active`` is False keep their cache and
    position; their logits are garbage. ``make_cache()`` returns a fresh
    zeroed group cache (every slot's positions start invalid)."""

    batch: int
    total_len: int
    decode: Callable
    make_cache: Callable


@dataclasses.dataclass(frozen=True)
class ChunkStep:
    """AOT chunked-prefill step:
    ``fn(params, cache, tokens, rows, start, active)`` -> (logits, cache).

    Gathers ``rows`` (int32 (rows,)) out of the group cache, runs one
    prefill chunk per gathered row starting at ``start`` (int32 (rows,)),
    and scatters the updated rows back (``active`` masks padding rows).
    Logits are (rows, V) — each row's *last* chunk position only, which is
    all decode needs: a row's final chunk seeds its first sampled token.
    Compiled per (row-width, chunk) pair from the config's fixed
    ``chunk_row_buckets`` x chunk-size grid, so refilling one freed slot
    dispatches a 1-row chunk instead of a full-width one."""

    rows: int
    chunk: int
    fn: Callable


class ServeCompileCache:
    """Per-(arch, plan-fingerprint) compile + artifact cache. Engine and
    oneshot serving apply the same discipline; the oneshot fallback warms
    batch-1 buckets (its wave width), so the two modes' bucket keys are
    disjoint."""

    def __init__(self, model, *, arch: str, fingerprint: str = "",
                 compress_k: int = 0, qcfg: Optional[QuantConfig] = None,
                 comp=None, config: EngineConfig = EngineConfig(),
                 place_prompts: Optional[Callable] = None,
                 place_replicated: Optional[Callable] = None):
        self.model = model
        self.arch = arch
        self.compress_k = int(compress_k)
        if not fingerprint:
            # direct construction without an explicit plan identity: derive
            # it from the comp content so distinct comps never share keys
            from repro.serving.fleet import comp_fingerprint

            fingerprint = comp_fingerprint(comp)
        self.fingerprint = fingerprint
        self.qcfg = qcfg if qcfg is not None else QuantConfig.off()
        self.comp = comp
        self.config = config
        self._place = place_prompts if place_prompts is not None else (lambda x: x)
        # slot-group state is placed replicated under an optional mesh (the
        # 'requests' sharding speedup applies to the wave/oneshot paths)
        self._rep = place_replicated if place_replicated is not None \
            else (lambda x: x)
        self._steps: Dict[Tuple, object] = {}
        self._artifacts: Dict[Tuple, Tuple[dict, dict]] = {}
        self.compile_count = 0

    # ------------------------------------------------------------ step fns

    def _compile(self, fn, params, *args, donate: bool = False):
        """AOT-compile ``fn(params, comp, *args)``; return it as
        ``(params, *args) -> out``.

        The comp tree is an argument of the executable, not a constant
        closed over: a packed plan at published widths holds hundreds of MB
        that would otherwise be copied into every executable. ``donate``
        donates the first of ``args`` (the decode cache)."""
        compiled = jax.jit(fn, donate_argnums=(2,) if donate else ()).lower(
            params, self.comp, *args).compile()
        self.compile_count += 1
        comp = self.comp
        return lambda p, *a: compiled(p, comp, *a)

    def _key(self, bucket: BucketSpec) -> Tuple:
        return (self.arch, self.fingerprint, bucket.key())

    def fns(self, bucket: BucketSpec, params) -> CompiledStep:
        """Compiled (prefill, decode) for the bucket; compiles on first use."""
        key = self._key(bucket)
        if key in self._steps:
            return self._steps[key]

        model, cfg = self.model, self.config
        qcfg = self.qcfg
        cache_dtype = jnp.dtype(cfg.cache_dtype)

        def prefill_fn(p, c, prompts):
            return model.prefill(p, prompts, max_len=bucket.total_len,
                                 qcfg=qcfg, comp=c, cache_dtype=cache_dtype,
                                 q_block=cfg.q_block, kv_block=cfg.kv_block)

        def decode_fn(p, c, cache, tok):
            return model.decode_step(p, cache, tok, qcfg=qcfg, comp=c)

        prompts0 = self._place(
            jnp.zeros((bucket.batch, bucket.prompt_len), jnp.int32))
        prefill_c = self._compile(prefill_fn, params, prompts0)
        # lower decode from a *concrete* prefill output so avals (and, under
        # an optional serving mesh, shardings) match the runtime cache exactly
        _, cache0 = prefill_c(params, prompts0)
        tok0 = self._place(jnp.zeros((bucket.batch, 1), jnp.int32))
        decode_c = self._compile(decode_fn, params, cache0, tok0, donate=True)

        step = CompiledStep(bucket=bucket, prefill=prefill_c, decode=decode_c)
        self._steps[key] = step
        return step

    # --------------------------------------------------- slot-group step fns

    def _group_shape(self) -> Tuple[int, int]:
        cfg = self.config
        return cfg.max_batch, cfg.group_total_len

    def _group_cache_zero(self):
        batch, total_len = self._group_shape()
        spec = self.model.cache_spec(batch, total_len,
                                     jnp.dtype(self.config.cache_dtype))
        zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        # fresh slots must look *unwritten*: per-row positions of 0 with an
        # all-zero cache are harmless (chunk prefill overwrites from pos 0
        # before any decode touches the row), so zeros are the right init
        return jax.tree.map(self._rep, zero)

    def group_fns(self, params) -> GroupStep:
        """Compiled active-masked decode for the slot group shape."""
        batch, total_len = self._group_shape()
        key = (self.arch, self.fingerprint, ("group", batch, total_len))
        if key in self._steps:
            return self._steps[key]

        model, qcfg = self.model, self.qcfg

        def decode_fn(p, c, cache, tok, active):
            return model.decode_step(p, cache, tok, qcfg=qcfg, comp=c,
                                     active=active)

        cache0 = self._group_cache_zero()
        tok0 = self._rep(jnp.zeros((batch, 1), jnp.int32))
        act0 = self._rep(jnp.zeros((batch,), bool))
        decode_c = self._compile(decode_fn, params, cache0, tok0, act0,
                                 donate=True)
        step = GroupStep(batch=batch, total_len=total_len, decode=decode_c,
                         make_cache=self._group_cache_zero)
        self._steps[key] = step
        return step

    def chunk_fns(self, chunk: int, rows: int, params) -> ChunkStep:
        """Compiled chunked-prefill step for one (chunk size, row width)
        pair, operating on gathered group rows."""
        cfg = self.config
        batch, total_len = self._group_shape()
        rows = int(rows)
        key = (self.arch, self.fingerprint,
               ("chunk", rows, int(chunk), batch, total_len))
        if key in self._steps:
            return self._steps[key]

        model, qcfg = self.model, self.qcfg

        def chunk_fn(p, c, cache, tokens, row_ids, start, active):
            row_cache = model.gather_cache_rows(cache, row_ids)
            logits, new_rows = model.prefill_chunk(
                p, row_cache, tokens, start=start, qcfg=qcfg, comp=c,
                q_block=cfg.q_block, kv_block=cfg.kv_block)
            new_cache = model.scatter_cache_rows(cache, row_ids, new_rows,
                                                 active)
            return logits[:, -1, :], new_cache

        cache0 = self._group_cache_zero()
        tokens0 = self._rep(jnp.zeros((rows, int(chunk)), jnp.int32))
        rows0 = self._rep(jnp.zeros((rows,), jnp.int32))
        start0 = self._rep(jnp.zeros((rows,), jnp.int32))
        act0 = self._rep(jnp.zeros((rows,), bool))
        fn_c = self._compile(chunk_fn, params, cache0, tokens0, rows0,
                             start0, act0, donate=True)
        step = ChunkStep(rows=rows, chunk=int(chunk), fn=fn_c)
        self._steps[key] = step
        return step

    # ----------------------------------------------------------- artifacts

    def artifacts(self, params) -> Tuple[dict, dict]:
        """Packed `ServeArtifact` tree + footprint summary for
        (arch, fingerprint).

        Empty when the engine is uncompressed — there is nothing to pack
        without a codebook restriction.
        """
        key = (self.arch, self.fingerprint)
        if key in self._artifacts:
            return self._artifacts[key]
        if self.comp is None:
            arts: dict = {}
            summary = {"layers": 0, "weight_bytes_packed": 0}
        else:
            from repro.core.export import export_summary
            from repro.core.lm_compress import export_lm_matmuls

            arts, _skips = export_lm_matmuls(self.model, params, self.comp)
            summary = export_summary(arts)
        self._artifacts[key] = (arts, summary)
        return self._artifacts[key]

    # ------------------------------------------------------------- reports

    def stats(self) -> dict:
        return {
            "arch": self.arch,
            "compress_k": self.compress_k,
            "fingerprint": self.fingerprint,
            "buckets_compiled": len(self._steps),
            "compile_count": self.compile_count,
        }
