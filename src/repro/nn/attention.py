"""Attention substrate: GQA/MQA/MHA + RoPE + sliding window + KV cache.

Training/prefill uses a double-blocked, online-softmax attention (pure-JAX
flash-attention schedule: outer scan over query blocks, inner scan over
key/value blocks) so activation memory is O(B * qblk * H * kblk) regardless
of sequence length — this is what lets 32k prefill lower/compile within HBM
on the production mesh. Decode is a single-query gather over the cache.

All attention projections are *compressible units*: they accept the same
optional (qcfg, comp) pair as Dense layers (see `repro.core.qat`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qat
from repro.nn.layers import QuantConfig
from repro.nn.spec import ParamSpec, fan_in_init, zeros_init

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: int = 0          # 0 => full attention; > 0 => sliding window
    causal: bool = True
    softcap: float = 0.0     # attention logit softcap (gemma-style), 0 = off


def make_attention_spec(dims: AttnDims, dtype=jnp.float32) -> dict:
    d, hq, hkv, hd = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    spec = {
        "wq": ParamSpec((d, hq, hd), dtype, ("embed", "heads", None), fan_in_init(in_axis=0)),
        "wk": ParamSpec((d, hkv, hd), dtype, ("embed", "kv_heads", None), fan_in_init(in_axis=0)),
        "wv": ParamSpec((d, hkv, hd), dtype, ("embed", "kv_heads", None), fan_in_init(in_axis=0)),
        "wo": ParamSpec((hq, hd, d), dtype, ("heads", None, "embed"), fan_in_init(in_axis=0)),
    }
    if dims.qkv_bias:
        spec["bq"] = ParamSpec((hq, hd), dtype, ("heads", None), zeros_init)
        spec["bk"] = ParamSpec((hkv, hd), dtype, ("kv_heads", None), zeros_init)
        spec["bv"] = ParamSpec((hkv, hd), dtype, ("kv_heads", None), zeros_init)
    return spec


# ----------------------------------------------------------------------- rope


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D), positions: (B, S) int32. Rotates first/second half pairs."""
    freqs = rope_frequencies(x.shape[-1], theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ projections


def _project(params, x, qcfg: QuantConfig, comp, name: str, key: str,
             bias_key: Optional[str] = None):
    w = params[key]  # (d, H, hd) or (H, hd, d)
    c = None if comp is None else comp.get(f"{name}/{key}")
    if qcfg.enabled and qcfg.act_quant:
        x = qat.fake_quant_act(x)
    art = None if c is None else c.get("serve")
    if qcfg.enabled and qcfg.comp_mode == "serve" and art is not None:
        # packed 4-bit LUT path (bias fused into the kernel epilogue):
        # wq/wk/wv are exported in_first as (d, H*hd), wo out_last as (H*hd, d).
        # The kernel returns f32 for bf16 inputs; cast back like the dense path
        from repro.core.export import serve_dense

        if key == "wo":
            xin = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
            return serve_dense(xin, art,
                               use_ref=qcfg.use_ref_kernel).astype(x.dtype)
        bias = params[bias_key] if bias_key and bias_key in params else None
        y = serve_dense(x, art,
                        bias=None if bias is None else bias.reshape(-1),
                        use_ref=qcfg.use_ref_kernel).astype(x.dtype)
        return y.reshape(*x.shape[:-1], w.shape[1], w.shape[2])
    if qcfg.enabled:
        w = qat.fake_quant_weight(w, c)
    if key == "wo":
        y = jnp.einsum("bshd,hdm->bsm", x, w.astype(x.dtype))
    else:
        y = jnp.einsum("bsm,mhd->bshd", x, w.astype(x.dtype))
    if bias_key and bias_key in params:
        y = y + params[bias_key].astype(y.dtype)
    return y


# ------------------------------------------------------------ blocked attention


def _block_mask(q_pos, k_pos, dims: AttnDims):
    """Boolean mask for one (q-block, k-block) pair.

    Positions are ``(Sq,)``/``(Sk,)`` (shared across the batch) or
    ``(B, Sq)``/``(B, Sk)`` (per-sequence, e.g. chunked prefill rows at
    different offsets); the mask is ``(Sq, Sk)`` or ``(B, Sq, Sk)``.
    """
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if dims.causal:
        m &= kp <= qp
    if dims.window > 0:
        m &= kp > qp - dims.window
    return m


def blocked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, dims: AttnDims, *,
    q_offset: int = 0, q_block: int = 512, kv_block: int = 512,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    use_flash: bool = False,
) -> jax.Array:
    """Online-softmax attention. q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D).

    GQA handled by reshaping queries to (B, S, Hkv, G, D). Memory per step is
    one (B, q_block, Hkv, G, kv_block) score tile. Works for any Sq/Sk that
    are multiples of the block sizes (callers pad).

    ``q_positions``/``kv_positions`` may be per-sequence (``(B, S)``), which
    is what lets chunked-prefill rows sit at independent offsets in one
    fixed-shape call; fully masked key blocks contribute exactly zero to the
    online softmax, so adding padded/invalid keys never changes the result.
    """
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    assert sq % q_block == 0 and sk % kv_block == 0, (sq, sk, q_block, kv_block)
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(b, sq, hkv, g, hd)
    nq, nk = sq // q_block, sk // kv_block
    if q_positions is None:
        q_positions = q_offset + jnp.arange(sq, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = jnp.arange(sk, dtype=jnp.int32)
    batched_pos = q_positions.ndim > 1 or kv_positions.ndim > 1
    if use_flash and batched_pos:
        raise ValueError("flash attention does not support per-sequence "
                         "positions; use the blocked path")

    if use_flash and dims.softcap == 0:
        # FlashAttention-style custom VJP: O(S) residuals instead of the
        # O(S^2/blk) probability stacks autodiff saves (see nn/flash.py)
        from repro.nn.flash import flash_attention

        out = flash_attention(qg, k, v, q_positions, kv_positions,
                              dims.causal, dims.window, q_block, kv_block)
        return out.reshape(b, sq, hq, hd)

    def q_step(_, qi):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qi * q_block, q_block, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_positions, qi * q_block, q_block,
                                          axis=-1)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * kv_block, kv_block, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * kv_block, kv_block, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kv_positions, ki * kv_block,
                                              kv_block, axis=-1)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk).astype(jnp.float32)
            s = s * scale
            if dims.softcap > 0:
                s = dims.softcap * jnp.tanh(s / dims.softcap)
            mask = _block_mask(qp, kp, dims)  # (qblk, kblk) or (b, qblk, kblk)
            if mask.ndim == 2:
                mask = mask[None, None, None]
            else:
                mask = mask[:, None, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_block, hd), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk, dtype=jnp.int32))
        out = acc / jnp.maximum(l_f[..., None], 1e-20)  # (b, hkv, g, qblk, hd)
        out = jnp.transpose(out, (0, 3, 1, 2, 4))       # (b, qblk, hkv, g, hd)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq, dtype=jnp.int32))
    # blocks: (nq, b, q_block, hkv, g, hd)
    out = jnp.transpose(blocks, (1, 0, 2, 3, 4, 5)).reshape(b, sq, hq, hd)
    return out


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, dims: AttnDims, *,
    cur_pos: jax.Array, cache_positions: Optional[jax.Array] = None,
    kv_new: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> jax.Array:
    """Single-step attention over a cache.

    q: (B, 1, Hq, D); k_cache/v_cache: (B, Smax, Hkv, D); cur_pos: () or (B,)
    is the position of the new token. Cache entries at slot i hold position
    ``cache_positions[..., i]`` (default: identity, i.e. contiguous cache);
    ``cache_positions`` may be per-sequence (B, Smax) when rows sit at
    independent offsets (slot-level continuous batching).

    ``kv_new`` ((B, Hkv, D) each) is the new token's own key and value, not
    in the cache: it joins the softmax as one more term at ``cur_pos``.
    """
    b, _, hq, hd = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(b, hkv, g, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache).astype(jnp.float32) * scale
    if kv_new is not None:
        s_new = jnp.einsum("bhgd,bhd->bhg", qg, kv_new[0]).astype(jnp.float32)
        s = jnp.concatenate([s, s_new[..., None] * scale], axis=-1)
    if dims.softcap > 0:
        s = dims.softcap * jnp.tanh(s / dims.softcap)
    pos = cache_positions if cache_positions is not None else jnp.arange(smax)
    if pos.ndim == 1:
        pos = pos[None, :]                    # (1, Smax) -> broadcast over B
    cur = jnp.asarray(cur_pos)
    cur = cur[..., None] if cur.ndim else cur
    # slots that were never written carry negative positions -> invalid
    valid = (pos <= cur) & (pos >= 0)         # (B or 1, Smax)
    if dims.window > 0:
        valid &= pos > cur - dims.window
    if kv_new is not None:                    # the new token sees itself
        valid = jnp.concatenate(
            [jnp.broadcast_to(valid, (b, smax)), jnp.ones((b, 1), bool)],
            axis=-1)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p[..., :smax].astype(v_cache.dtype),
                     v_cache)
    if kv_new is not None:
        out = out + p[..., smax:].astype(v_cache.dtype) * kv_new[1][:, :, None]
    # a cache wider than the compute dtype (f32 cache, bf16 compute) must
    # not widen the residual stream: the layer scan carries it at q's dtype
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


# ----------------------------------------------------------------- full layer


def apply_attention(
    params,
    x: jax.Array,
    dims: AttnDims,
    *,
    positions: Optional[jax.Array] = None,
    qcfg: QuantConfig = QuantConfig.off(),
    comp=None,
    name: str = "attn",
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,   # cross-attention K/V source
    q_block: int = 512,
    kv_block: int = 512,
    return_kv: bool = False,
    use_flash: bool = False,
):
    """Training/prefill attention over (B, S, d_model).

    Returns the block output, or (output, (k, v)) with post-RoPE K/V when
    ``return_kv`` (prefill cache capture).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q = _project(params, x, qcfg, comp, name, "wq", "bq")
    if kv is None:
        k = _project(params, x, qcfg, comp, name, "wk", "bk")
        v = _project(params, x, qcfg, comp, name, "wv", "bv")
        kv_positions = None
        if dims.rope_theta > 0:
            q = apply_rope(q, positions, dims.rope_theta)
            k = apply_rope(k, positions, dims.rope_theta)
    else:
        k, v = kv
        kv_positions = jnp.arange(k.shape[1], dtype=jnp.int32)
    k_ret, v_ret = k, v

    # pad S to block multiples
    pad_q = (-s) % q_block
    pad_k = (-k.shape[1]) % kv_block
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if kv_positions is not None:
            kv_positions = jnp.pad(kv_positions, (0, pad_k),
                                   constant_values=jnp.int32(1 << 30))
    out = blocked_attention(q, k, v, dims, q_block=q_block, kv_block=kv_block,
                            kv_positions=kv_positions, use_flash=use_flash)
    if pad_q:
        out = out[:, :s]
    out = _project(params, out, qcfg, comp, name, "wo")
    if return_kv:
        return out, (k_ret, v_ret)
    return out


def init_kv_cache(batch: int, max_len: int, dims: AttnDims, dtype=jnp.bfloat16):
    shape = (batch, max_len, dims.n_kv_heads, dims.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def kv_cache_spec(batch: int, max_len: int, dims: AttnDims, dtype=jnp.bfloat16):
    shape = (batch, max_len, dims.n_kv_heads, dims.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
    }


def apply_attention_decode(
    params,
    x: jax.Array,              # (B, 1, d_model)
    cache: dict,               # {"k": (B, Smax, Hkv, D), "v": ...}
    pos: jax.Array,            # () or (B,) int32 current position(s)
    dims: AttnDims,
    *,
    qcfg: QuantConfig = QuantConfig.off(),
    comp=None,
    name: str = "attn",
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, dict]:
    """One decode step; returns (output (B, 1, d), the new token's K/V rows).

    The cache is only read. Each row attends over its cache with its own
    slot ``pos mod Smax`` masked out (it holds an older position, or
    nothing), plus the new token's own key and value as one more term of
    the same softmax: the positions attended are those of a cache with the
    new token written in. The returned ``{"k", "v"}`` rows are (B, Hkv, D)
    in the cache's dtype; the caller writes them at slot ``pos mod Smax``
    (``LMModel.decode_step``). With ``cross_kv`` the output attends over
    the encoder's K/V and the rows are empty.

    ``pos`` may be per-sequence (B,): each row masks against its own
    position, which is what slot-level continuous batching needs when rows
    of one batch sit at different depths.
    """
    b = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos_b[:, None]  # (B, 1)
    q = _project(params, x, qcfg, comp, name, "wq", "bq")

    if cross_kv is not None:
        out = decode_attention(
            q, cross_kv[0], cross_kv[1],
            dataclasses.replace(dims, causal=False, window=0),
            cur_pos=jnp.int32(1 << 30))
        return _project(params, out, qcfg, comp, name, "wo"), {}

    k_new = _project(params, x, qcfg, comp, name, "wk", "bk")
    v_new = _project(params, x, qcfg, comp, name, "wv", "bv")
    if dims.rope_theta > 0:
        q = apply_rope(q, positions, dims.rope_theta)
        k_new = apply_rope(k_new, positions, dims.rope_theta)
    k_row = k_new[:, 0].astype(cache["k"].dtype)
    v_row = v_new[:, 0].astype(cache["v"].dtype)

    smax = cache["k"].shape[1]
    idx = jnp.arange(smax, dtype=jnp.int32)
    # slot i holds the largest position congruent to i (mod smax) that is
    # <= pos (ring layout for windowed layers, linear otherwise); slots
    # never written yet resolve to negative positions, which the validity
    # mask in decode_attention rejects. The row's own slot (position pos)
    # still holds an older entry or nothing: it goes to -1, and the new
    # token's own term stands in for it.
    cache_positions = idx[None, :] + (
        (pos_b[:, None] - idx[None, :]) // smax) * smax  # (B, Smax)
    cache_positions = jnp.where(cache_positions == pos_b[:, None], -1,
                                cache_positions)
    out = decode_attention(q, cache["k"], cache["v"], dims, cur_pos=pos_b,
                           cache_positions=cache_positions,
                           kv_new=(k_row, v_row))
    out = _project(params, out, qcfg, comp, name, "wo")
    return out, {"k": k_row, "v": v_row}


def apply_attention_chunk(
    params,
    x: jax.Array,              # (B, C, d_model) one prefill chunk per row
    cache: dict,               # {"k": (B, Smax, Hkv, D), "v": ...}
    positions: jax.Array,      # (B, C) int32 absolute positions of the chunk
    dims: AttnDims,
    *,
    qcfg: QuantConfig = QuantConfig.off(),
    comp=None,
    name: str = "attn",
    q_block: int = 8,
    kv_block: int = 8,
) -> Tuple[jax.Array, dict]:
    """Chunked-prefill attention step; returns (output (B, C, d), new cache).

    Writes the chunk's post-RoPE K/V into each row's cache, then runs blocked
    online-softmax attention over the *whole* cache with per-row positions.
    Slots the row has not reached yet are masked via the same
    largest-position-congruent-to-slot formula as decode, so stale entries
    from a previous occupant of the slot are invisible. Masked key blocks
    contribute exactly zero, so with a float32 cache the chunked pass is
    bit-identical to one full prefill over the same tokens.

    Ring caches (windowed layers with Smax < total length) are not supported:
    a chunk write could evict keys still inside an earlier query's window.
    Callers gate on ``Smax >= max positions`` before using the chunk path.
    """
    b, c, _ = x.shape
    smax = cache["k"].shape[1]
    positions = positions.astype(jnp.int32)
    q = _project(params, x, qcfg, comp, name, "wq", "bq")
    k_new = _project(params, x, qcfg, comp, name, "wk", "bk")
    v_new = _project(params, x, qcfg, comp, name, "wv", "bv")
    if dims.rope_theta > 0:
        q = apply_rope(q, positions, dims.rope_theta)
        k_new = apply_rope(k_new, positions, dims.rope_theta)

    # Scatter the chunk into the cache, last-write-wins per slot (a chunk
    # never wraps — see the ring note above — so "last" is just in-order).
    idx = jnp.arange(smax, dtype=jnp.int32)
    hits = jnp.mod(positions, smax)[:, :, None] == idx[None, None, :]  # (B,C,S)
    order = jnp.where(hits, jnp.arange(c, dtype=jnp.int32)[None, :, None], -1)
    src = jnp.max(order, axis=1)          # (B, Smax); -1 = slot untouched
    written = (src >= 0)[..., None, None]

    def scatter(old, new):
        gathered = jnp.take_along_axis(
            new, jnp.maximum(src, 0)[..., None, None], axis=1)
        return jnp.where(written, gathered.astype(old.dtype), old)

    k_cache = scatter(cache["k"], k_new)
    v_cache = scatter(cache["v"], v_new)

    cur = positions[:, -1]                # (B,) last position in the chunk
    cache_positions = idx[None, :] + ((cur[:, None] - idx[None, :]) // smax) * smax
    kv_positions = jnp.where(cache_positions >= 0, cache_positions,
                             jnp.int32(1 << 30))  # unwritten -> fails causal

    pad_q = (-c) % q_block
    pad_k = (-smax) % kv_block
    q_pos = positions
    kf, vf = k_cache.astype(q.dtype), v_cache.astype(q.dtype)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), mode="edge")
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_k)),
                               constant_values=jnp.int32(1 << 30))
    out = blocked_attention(q, kf, vf, dims, q_block=q_block,
                            kv_block=kv_block, q_positions=q_pos,
                            kv_positions=kv_positions)
    if pad_q:
        out = out[:, :c]
    out = _project(params, out, qcfg, comp, name, "wo")
    return out, {"k": k_cache, "v": v_cache}
