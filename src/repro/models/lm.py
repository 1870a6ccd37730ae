"""LM assembly: ArchConfig -> spec tree + forward / prefill / decode.

Layers are grouped by the config's repeating block `pattern`; each pattern
position's parameters are stacked over the repeat count and iterated with
`jax.lax.scan` (keeps HLO size O(pattern) instead of O(n_layers), which is
what makes 512-device SPMD lowering of 26-48 layer models tractable).
Remainder layers (n_layers % len(pattern)) are unrolled as "tail" blocks.

Whisper-style encoder-decoder stacks an extra (non-causal, no-RoPE) encoder
scan and gives decoder blocks cross-attention; VLM (internvl2) prepends stub
patch embeddings to the token embeddings (the frontend is an input, per the
assignment).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.nn import transformer as T
from repro.nn.layers import QuantConfig
from repro.nn.spec import ParamSpec, normal_init, stack_specs
from repro.nn.transformer import (
    apply_block,
    apply_block_chunk,
    apply_block_decode,
    block_cache_spec,
    make_block_spec,
)

NEG_INF = -1e30


@jax.custom_vjp
def _carry_barrier(h):
    """`optimization_barrier` with a differentiation rule.

    `jax.lax.optimization_barrier` has no VJP, so placing it on the scan
    carry broke every grad step. The barrier semantics (don't let XLA hoist
    dtype converts of the remat-saved carry stack out of the backward loop)
    matter in both directions, so forward and cotangent each get their own
    barrier while the math stays identity."""
    return jax.lax.optimization_barrier(h)


def _carry_barrier_fwd(h):
    return jax.lax.optimization_barrier(h), None


def _carry_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_carry_barrier.defvjp(_carry_barrier_fwd, _carry_barrier_bwd)


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """(B, S) int positions -> (B, S, d) sinusoidal embeddings (whisper)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


@dataclasses.dataclass
class LMModel:
    cfg: ArchConfig
    spec: dict

    # ------------------------------------------------------------ structure

    @property
    def n_pattern(self) -> int:
        return len(self.cfg.pattern)

    @property
    def n_rep(self) -> int:
        return self.cfg.n_layers // self.n_pattern

    @property
    def n_tail(self) -> int:
        return self.cfg.n_layers % self.n_pattern

    # ------------------------------------------------------------- encoder

    def _encode(self, params, enc_embeds, *, qcfg, comp, remat, q_block, kv_block,
                shard=None):
        cfg = self.cfg
        x = enc_embeds.astype(cfg.cdtype)
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = x + _sinusoid(pos, cfg.d_model).astype(x.dtype)
        if shard is not None:
            x = shard(x)
        enc_comp = None if comp is None else comp.get("enc_blocks")

        def body(carry, xs):
            layer_params, layer_comp = xs if enc_comp is not None else (xs, None)
            h, _ = apply_block(layer_params, carry, cfg, "attn", positions=pos,
                               qcfg=qcfg, comp=layer_comp, q_block=q_block,
                               kv_block=kv_block, encoder=True)
            if shard is not None:
                h = shard(h)
            return h, None

        if remat:
            body = jax.checkpoint(body)
        xs = (params["enc_blocks"], enc_comp) if enc_comp is not None \
            else params["enc_blocks"]
        x, _ = jax.lax.scan(body, x, xs)
        return T.apply_norm(params["enc_norm"], x, cfg)

    # ------------------------------------------------------------- forward

    def forward(
        self,
        params,
        tokens: jax.Array,                      # (B, S) int32
        *,
        prefix_embeds: Optional[jax.Array] = None,   # (B, P, d) stub frontend
        enc_embeds: Optional[jax.Array] = None,      # (B, S_enc, d) whisper frames
        qcfg: QuantConfig = QuantConfig.off(),
        comp=None,
        remat: bool = False,
        q_block: int = 512,
        kv_block: int = 512,
        shard: Optional[Callable] = None,
        shard_logits: Optional[Callable] = None,
        use_flash: bool = False,
        remat_policy: Optional[str] = None,   # None | "save_qat"
    ) -> Tuple[jax.Array, dict]:
        """Returns (logits (B, S_total, padded_vocab), aux)."""
        cfg = self.cfg
        b, s_tok = tokens.shape
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.cdtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(cfg.cdtype), x], axis=1)
        if cfg.encoder_decoder:
            pos_ids = jnp.broadcast_to(
                jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
            x = x + _sinusoid(pos_ids, cfg.d_model).astype(x.dtype)
        if shard is not None:
            x = shard(x)
        s = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        enc_out = None
        if cfg.encoder_decoder:
            assert enc_embeds is not None
            enc_out = self._encode(params, enc_embeds, qcfg=qcfg, comp=comp,
                                   remat=remat, q_block=q_block,
                                   kv_block=kv_block, shard=shard)

        aux0 = {"lb_loss": jnp.zeros((), jnp.float32),
                "z_loss": jnp.zeros((), jnp.float32)}
        blocks_comp = None if comp is None else comp.get("blocks")
        tail_comp = None if comp is None else comp.get("tail")

        def macro_body(carry, xs):
            layer_params, layer_comp = xs if blocks_comp is not None else (xs, None)
            h, aux_c = carry
            # Barrier: stops XLA from hoisting the bf16->f32 convert of the
            # rematerialization-saved carry *stack* out of the backward loop
            # (which would materialize an O(L*B*S*D) f32 buffer).
            h = _carry_barrier(h)
            aux_new = dict(aux_c)
            for i, bt in enumerate(cfg.pattern):
                ci = None if layer_comp is None else layer_comp.get(f"g{i}")
                h, aux = apply_block(
                    layer_params[f"g{i}"], h, cfg, bt, positions=positions,
                    qcfg=qcfg, comp=ci, enc_out=enc_out,
                    q_block=q_block, kv_block=kv_block, use_flash=use_flash)
                aux_new = {k: aux_new[k] + aux[k] for k in aux_new}
            if shard is not None:
                h = shard(h)
            return (h, aux_new), None

        if remat and remat_policy == "save_qat":
            policy = jax.checkpoint_policies.save_only_these_names("qat_weights")
            body = jax.checkpoint(macro_body, policy=policy)
        elif remat:
            body = jax.checkpoint(macro_body)
        else:
            body = macro_body
        if self.n_rep > 0:
            xs = (params["blocks"], blocks_comp) if blocks_comp is not None \
                else params["blocks"]
            (x, aux), _ = jax.lax.scan(body, (x, aux0), xs)
        else:
            aux = aux0
        for j in range(self.n_tail):
            bt = cfg.pattern[j]
            cj = None if tail_comp is None else tail_comp.get(f"t{j}")
            x, a = apply_block(params["tail"][f"t{j}"], x, cfg, bt,
                               positions=positions, qcfg=qcfg, comp=cj,
                               enc_out=enc_out, q_block=q_block,
                               kv_block=kv_block, use_flash=use_flash)
            aux = {k: aux[k] + a[k] for k in aux}

        x = T.apply_norm(params["final_norm"], x, cfg)
        logits = self._unembed(params, x, shard_logits)
        return logits, aux

    def _unembed(self, params, x, shard_logits=None):
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"]["table"].astype(x.dtype))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"]["w"].astype(x.dtype))
        # mask the vocab padding
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, NEG_INF, logits.astype(jnp.float32))
        if shard_logits is not None:
            logits = shard_logits(logits)
        return logits

    # ---------------------------------------------------------------- loss

    def loss(self, params, batch: Dict[str, jax.Array], **fwd_kwargs):
        """Causal LM loss. batch: tokens, labels (+prefix/enc embeds)."""
        logits, aux = self.forward(
            params, batch["tokens"],
            prefix_embeds=batch.get("prefix_embeds"),
            enc_embeds=batch.get("enc_embeds"),
            **fwd_kwargs)
        labels = batch["labels"]
        # with a prefix, loss applies to the trailing token positions only
        logits_tok = logits[:, logits.shape[1] - labels.shape[1]:]
        logp = jax.nn.log_softmax(logits_tok, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            loss = jnp.mean(nll)
        total = loss + 0.01 * aux["lb_loss"] + 1e-3 * aux["z_loss"]
        metrics = {"ce": loss, "lb_loss": aux["lb_loss"], "z_loss": aux["z_loss"]}
        return total, metrics

    # --------------------------------------------------------------- caches

    def cache_spec(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   cross_len: int = 0) -> dict:
        cfg = self.cfg
        spec: Dict[str, Any] = {"groups": {}, "tail": {}}
        for i, bt in enumerate(cfg.pattern):
            one = block_cache_spec(cfg, bt, batch, max_len, dtype,
                                   cross_len=cross_len)
            spec["groups"][f"g{i}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((self.n_rep, *s.shape), s.dtype),
                one)
        for j in range(self.n_tail):
            bt = cfg.pattern[j]
            spec["tail"][f"t{j}"] = block_cache_spec(
                cfg, bt, batch, max_len, dtype, cross_len=cross_len)
        # per-sequence positions: rows of one batch may sit at different
        # depths (slot-level continuous batching refills rows mid-flight)
        spec["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
        return spec

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   cross_len: int = 0) -> dict:
        spec = self.cache_spec(batch, max_len, dtype, cross_len)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)

    # --------------------------------------------------------------- decode

    def decode_step(
        self,
        params,
        cache: dict,
        tokens: jax.Array,          # (B, 1) int32
        *,
        qcfg: QuantConfig = QuantConfig.off(),
        comp=None,
        shard: Optional[Callable] = None,
        shard_logits: Optional[Callable] = None,
        active: Optional[jax.Array] = None,   # (B,) bool; None = all rows
    ) -> Tuple[jax.Array, dict]:
        """One token for every sequence in the batch. Returns (logits, cache).

        ``cache["pos"]`` is per-sequence (B,). The layer scan only reads the
        cache: attention blocks emit the new token's K/V rows, and after the
        scan one scatter per leaf writes them at ``[row, pos mod Smax]``, so
        a donated cache is updated in place instead of rewritten. Recurrent
        blocks' states are replaced whole; cross-attention K/V are kept.

        With ``active`` given, rows where it is False keep their cache and
        position untouched (their logits are garbage and must be ignored):
        their K/V writes are dropped and ``_merge_active`` keeps their state
        and position. This is what lets a slot group decode while some
        slots are empty or mid-prefill.
        """
        cfg = self.cfg
        pos = cache["pos"]
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.cdtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
        if cfg.encoder_decoder:
            pos_ids = (pos.astype(jnp.int32)[:, None] if jnp.ndim(pos)
                       else jnp.broadcast_to(pos.astype(jnp.int32), x.shape[:2]))
            x = x + _sinusoid(pos_ids, cfg.d_model).astype(x.dtype)
        if shard is not None:
            x = shard(x)

        blocks_comp = None if comp is None else comp.get("blocks")
        tail_comp = None if comp is None else comp.get("tail")

        def macro_body(carry, xs):
            h = carry
            if blocks_comp is not None:
                layer_params, layer_cache, layer_comp = xs
            else:
                (layer_params, layer_cache), layer_comp = xs, None
            outs = {}
            for i, bt in enumerate(cfg.pattern):
                ci = None if layer_comp is None else layer_comp.get(f"g{i}")
                h, outs[f"g{i}"] = apply_block_decode(
                    layer_params[f"g{i}"], h, layer_cache[f"g{i}"], pos, cfg,
                    bt, qcfg=qcfg, comp=ci)
            return h, outs

        outs = {"groups": {}, "tail": {}}
        if self.n_rep > 0:
            xs = (params["blocks"], cache["groups"])
            if blocks_comp is not None:
                xs = (params["blocks"], cache["groups"], blocks_comp)
            x, outs["groups"] = jax.lax.scan(macro_body, x, xs)
        for j in range(self.n_tail):
            bt = cfg.pattern[j]
            cj = None if tail_comp is None else tail_comp.get(f"t{j}")
            x, outs["tail"][f"t{j}"] = apply_block_decode(
                params["tail"][f"t{j}"], x, cache["tail"][f"t{j}"], pos, cfg,
                bt, qcfg=qcfg, comp=cj)

        b = tokens.shape[0]
        rows = jnp.arange(b, dtype=jnp.int32)
        if active is not None:
            # out of range (and still distinct): the scatter drops the write
            rows = jnp.where(active.astype(bool), rows, rows + b)
        new_cache = {"groups": dict(cache["groups"]),
                     "tail": dict(cache["tail"]), "pos": pos + 1}
        for sect, key, bt, axis in self._cache_blocks():
            old, out = cache[sect][key], outs[sect][key]
            if bt in ("attn", "local"):
                slots = jnp.mod(pos, old["k"].shape[axis + 1])
                at = (slice(None),) * axis + (rows, slots)
                new_cache[sect][key] = {**old, **{
                    n: old[n].at[at].set(out[n], mode="drop",
                                         unique_indices=True)
                    for n in ("k", "v")}}
            else:
                new_cache[sect][key] = out
        if active is not None:
            new_cache = self._merge_active(cache, new_cache, active)

        x = T.apply_norm(params["final_norm"], x, cfg)
        logits = self._unembed(params, x, shard_logits)
        return logits, new_cache

    def _cache_blocks(self):
        """(section, key, block type, batch axis) of each block's cache:
        `groups` leaves carry a leading layer-stack axis, `tail` leaves
        have batch leading."""
        if self.n_rep > 0:
            for i, bt in enumerate(self.cfg.pattern):
                yield "groups", f"g{i}", bt, 1
        for j in range(self.n_tail):
            yield "tail", f"t{j}", self.cfg.pattern[j], 0

    def _merge_active(self, old_cache: dict, new_cache: dict, active) -> dict:
        """Keep inactive rows' position and recurrent state. Requires per-row
        pos (B,).

        Only what a decode step replaces whole goes through this masked
        select: ``pos`` and the rglru/ssm state leaves, which are (B, d)
        sized. Attention K/V leaves pass through: the step's scatter already
        dropped inactive rows' writes.
        """
        act = active.astype(bool)

        def merge(axis):
            def f(new, old):
                shape = [1] * new.ndim
                shape[axis] = act.shape[0]
                return jnp.where(act.reshape(shape), new, old)
            return f

        merged = {"groups": dict(new_cache["groups"]),
                  "tail": dict(new_cache["tail"]),
                  "pos": jnp.where(act, new_cache["pos"], old_cache["pos"])}
        for sect, key, bt, axis in self._cache_blocks():
            if bt not in ("attn", "local"):
                merged[sect][key] = jax.tree.map(
                    merge(axis), new_cache[sect][key], old_cache[sect][key])
        return merged

    # --------------------------------------------------------------- prefill

    def prefill(
        self,
        params,
        tokens: jax.Array,
        max_len: int,
        *,
        prefix_embeds: Optional[jax.Array] = None,
        enc_embeds: Optional[jax.Array] = None,
        qcfg: QuantConfig = QuantConfig.off(),
        comp=None,
        cache_dtype=jnp.bfloat16,
        q_block: int = 512,
        kv_block: int = 512,
    ) -> Tuple[jax.Array, dict]:
        """Forward over the prompt, capturing per-layer state into a decode
        cache. Returns (logits (B, S, V), cache ready at pos = S)."""
        cfg = self.cfg
        b, s_tok = tokens.shape
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.cdtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(cfg.cdtype), x], axis=1)
        if cfg.encoder_decoder:
            pos_ids = jnp.broadcast_to(
                jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
            x = x + _sinusoid(pos_ids, cfg.d_model).astype(x.dtype)
        s = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        enc_out = None
        if cfg.encoder_decoder:
            assert enc_embeds is not None
            enc_out = self._encode(params, enc_embeds, qcfg=qcfg, comp=comp,
                                   remat=False, q_block=q_block,
                                   kv_block=kv_block)
        cross_len = enc_out.shape[1] if enc_out is not None else 0

        # Blocks run unrolled for prefill (state capture per layer); prefill
        # happens once per request and serve-time models ship a fixed cfg, so
        # the larger HLO is acceptable. (The dry-run decode path uses the
        # scanned decode_step.)
        cache = {"groups": {}, "tail": {},
                 "pos": jnp.full((b,), s, jnp.int32)}
        group_states: Dict[str, list] = {f"g{i}": [] for i in range(self.n_pattern)}
        blocks_comp = None if comp is None else comp.get("blocks")
        tail_comp = None if comp is None else comp.get("tail")

        def run_block(block_params, h, bt, block_comp):
            return apply_block(block_params, h, cfg, bt, positions=positions,
                               qcfg=qcfg, comp=block_comp, enc_out=enc_out,
                               q_block=q_block, kv_block=kv_block,
                               return_state=True)

        for r in range(self.n_rep):
            layer_params = jax.tree.map(lambda p: p[r], params["blocks"])
            layer_comp = None if blocks_comp is None else jax.tree.map(
                lambda c: c[r], blocks_comp)
            for i, bt in enumerate(cfg.pattern):
                ci = None if layer_comp is None else layer_comp.get(f"g{i}")
                (x, _), st = run_block(layer_params[f"g{i}"], x, bt, ci)
                group_states[f"g{i}"].append(
                    self._state_to_cache(st, bt, max_len, cache_dtype, enc_out,
                                         layer_params[f"g{i}"], qcfg, ci))
        for key, sts in group_states.items():
            if sts:
                cache["groups"][key] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *sts)
        for j in range(self.n_tail):
            bt = cfg.pattern[j]
            cj = None if tail_comp is None else tail_comp.get(f"t{j}")
            (x, _), st = run_block(params["tail"][f"t{j}"], x, bt, cj)
            cache["tail"][f"t{j}"] = self._state_to_cache(
                st, bt, max_len, cache_dtype, enc_out,
                params["tail"][f"t{j}"], qcfg, cj)

        x = T.apply_norm(params["final_norm"], x, cfg)
        logits = self._unembed(params, x)
        return logits, cache

    # ------------------------------------------------------- chunked prefill

    def prefill_chunk(
        self,
        params,
        cache: dict,
        tokens: jax.Array,          # (B, C) int32 — one prompt chunk per row
        *,
        start: jax.Array,           # (B,) int32 — first absolute position
        qcfg: QuantConfig = QuantConfig.off(),
        comp=None,
        q_block: int = 8,
        kv_block: int = 8,
        shard: Optional[Callable] = None,
        shard_logits: Optional[Callable] = None,
    ) -> Tuple[jax.Array, dict]:
        """Run one prefill chunk per row against an existing decode cache.

        Row r processes positions ``start[r] .. start[r]+C-1``; the cache
        comes back with ``pos = start + C``. Logits are (B, C, V) — the last
        chunk's final real position seeds the first sampled token. Recurrent
        mixers only support a single chunk from position 0 (their state
        restarts from zero each call); encoder-decoder models have no chunk
        path at all.
        """
        cfg = self.cfg
        if cfg.encoder_decoder:
            raise ValueError("chunked prefill does not support "
                             "encoder-decoder models; use the oneshot path")
        b, c = tokens.shape
        start = jnp.asarray(start, jnp.int32)
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.cdtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.cdtype)
        if shard is not None:
            x = shard(x)

        blocks_comp = None if comp is None else comp.get("blocks")
        tail_comp = None if comp is None else comp.get("tail")

        def macro_body(carry, xs):
            h = carry
            if blocks_comp is not None:
                layer_params, layer_cache, layer_comp = xs
            else:
                (layer_params, layer_cache), layer_comp = xs, None
            new_caches = {}
            for i, bt in enumerate(cfg.pattern):
                ci = None if layer_comp is None else layer_comp.get(f"g{i}")
                h, c_new = apply_block_chunk(
                    layer_params[f"g{i}"], h, layer_cache[f"g{i}"], positions,
                    cfg, bt, qcfg=qcfg, comp=ci, q_block=q_block,
                    kv_block=kv_block)
                new_caches[f"g{i}"] = c_new
            return h, new_caches

        new_cache = {"groups": cache["groups"], "tail": {}, "pos": start + c}
        if self.n_rep > 0:
            xs = (params["blocks"], cache["groups"])
            if blocks_comp is not None:
                xs = (params["blocks"], cache["groups"], blocks_comp)
            x, group_caches = jax.lax.scan(macro_body, x, xs)
            new_cache["groups"] = group_caches
        for j in range(self.n_tail):
            bt = cfg.pattern[j]
            cj = None if tail_comp is None else tail_comp.get(f"t{j}")
            x, c_new = apply_block_chunk(
                params["tail"][f"t{j}"], x, cache["tail"][f"t{j}"], positions,
                cfg, bt, qcfg=qcfg, comp=cj, q_block=q_block,
                kv_block=kv_block)
            new_cache["tail"][f"t{j}"] = c_new

        x = T.apply_norm(params["final_norm"], x, cfg)
        logits = self._unembed(params, x, shard_logits)
        return logits, new_cache

    # ---------------------------------------------------- cache row shuffles

    def gather_cache_rows(self, cache: dict, rows: jax.Array) -> dict:
        """Extract rows (int32 (Bc,)) of a decode cache as a smaller cache.

        `groups` leaves carry a leading layer-stack axis (batch is axis 1);
        `tail` and `pos` leaves have batch leading.
        """
        return {
            "groups": jax.tree.map(lambda a: jnp.take(a, rows, axis=1),
                                   cache["groups"]),
            "tail": jax.tree.map(lambda a: jnp.take(a, rows, axis=0),
                                 cache["tail"]),
            "pos": jnp.take(cache["pos"], rows, axis=0),
        }

    def scatter_cache_rows(self, cache: dict, rows: jax.Array,
                           row_cache: dict, active: jax.Array) -> dict:
        """Write `row_cache` (batch Bc) back into `cache` at `rows`.

        `active` (Bc,) bool masks padding rows; active entries of `rows`
        must be distinct. Inactive/unlisted rows keep their old state.
        """
        b = cache["pos"].shape[0]
        sel = (jnp.arange(b, dtype=jnp.int32)[:, None] == rows[None, :]) \
            & active.astype(bool)[None, :]
        hit = jnp.any(sel, axis=1)                       # (B,)
        src = jnp.argmax(sel, axis=1).astype(jnp.int32)  # (B,) source column

        def put(axis):
            def f(old, new):
                gathered = jnp.take(new, src, axis=axis)
                shape = [1] * old.ndim
                shape[axis] = b
                return jnp.where(hit.reshape(shape), gathered, old)
            return f

        return {
            "groups": jax.tree.map(put(1), cache["groups"],
                                   row_cache["groups"]),
            "tail": jax.tree.map(put(0), cache["tail"], row_cache["tail"]),
            "pos": put(0)(cache["pos"], row_cache["pos"]),
        }

    def _state_to_cache(self, st, bt, max_len, dtype, enc_out, block_params,
                        qcfg, comp):
        cfg = self.cfg
        if bt in ("attn", "local"):
            dims = cfg.attn_dims(bt == "local")
            cache_len = min(max_len, dims.window) if dims.window else max_len
            k, v = st["k"], st["v"]
            s = k.shape[1]
            take = min(s, cache_len)
            pos_range = jnp.arange(s - take, s, dtype=jnp.int32)
            slots = jnp.mod(pos_range, cache_len)
            b = k.shape[0]
            kc = jnp.zeros((b, cache_len, *k.shape[2:]), dtype)
            vc = jnp.zeros((b, cache_len, *v.shape[2:]), dtype)
            kc = kc.at[:, slots].set(k[:, s - take:].astype(dtype))
            vc = vc.at[:, slots].set(v[:, s - take:].astype(dtype))
            out = {"k": kc, "v": vc}
            if enc_out is not None and "xattn" in block_params:
                xk, xv = T._cross_kv(block_params["xattn"], enc_out, cfg, qcfg,
                                     comp)
                out["xk"] = xk.astype(dtype)
                out["xv"] = xv.astype(dtype)
            return out
        return st  # rglru / ssm states already in cache layout


def build_lm(cfg: ArchConfig) -> LMModel:
    spec: Dict[str, Any] = {
        "embed": {
            "table": ParamSpec((cfg.padded_vocab, cfg.d_model), cfg.pdtype,
                               ("vocab", "embed"), normal_init(0.02)),
        },
        "final_norm": T.make_norm_spec(cfg),
    }
    n_pat = len(cfg.pattern)
    n_rep = cfg.n_layers // n_pat
    n_tail = cfg.n_layers % n_pat
    cross = cfg.encoder_decoder

    if n_rep > 0:
        group = {
            f"g{i}": make_block_spec(cfg, bt, cross_attn=cross)
            for i, bt in enumerate(cfg.pattern)
        }
        spec["blocks"] = stack_specs(group, n_rep, "layers")
    if n_tail:
        spec["tail"] = {
            f"t{j}": make_block_spec(cfg, cfg.pattern[j], cross_attn=cross)
            for j in range(n_tail)
        }
    if cfg.encoder_decoder:
        enc_block = make_block_spec(cfg, "attn", cross_attn=False)
        spec["enc_blocks"] = stack_specs(enc_block, cfg.n_enc_layers, "layers")
        spec["enc_norm"] = T.make_norm_spec(cfg)
    if not cfg.tie_embeddings:
        spec["lm_head"] = {
            "w": ParamSpec((cfg.d_model, cfg.padded_vocab), cfg.pdtype,
                           ("embed", "vocab"), normal_init(0.02)),
        }
    return LMModel(cfg, spec)
