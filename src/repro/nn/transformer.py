"""Transformer block assembly: norms + mixer (attn/local/rglru/ssm) + FFN/MoE.

A *block* is one residual layer of the network. `make_block_spec` /
`apply_block` / `apply_block_decode` dispatch on the block type string; the
LM assembler (repro.models.lm) stacks same-typed blocks and scans over them.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qat
from repro.models.config import ArchConfig
from repro.nn import attention as A
from repro.nn import moe as MOE
from repro.nn import rglru as RG
from repro.nn import ssm as SSM
from repro.nn.layers import ACTIVATIONS, QuantConfig, apply_layernorm, apply_rmsnorm
from repro.nn.spec import ParamSpec, fan_in_init

# ------------------------------------------------------------------- norms


def make_norm_spec(cfg: ArchConfig):
    if cfg.norm == "rmsnorm":
        return {"scale": ParamSpec((cfg.d_model,), cfg.pdtype, (None,),
                                   lambda k, s, t: jnp.ones(s, t))}
    if cfg.norm == "layernorm":
        return {
            "scale": ParamSpec((cfg.d_model,), cfg.pdtype, (None,),
                               lambda k, s, t: jnp.ones(s, t)),
            "bias": ParamSpec((cfg.d_model,), cfg.pdtype, (None,),
                              lambda k, s, t: jnp.zeros(s, t)),
        }
    if cfg.norm == "nonparam_ln":
        return {}
    raise ValueError(cfg.norm)


def apply_norm(params, x, cfg: ArchConfig):
    if cfg.norm == "rmsnorm":
        return apply_rmsnorm(params, x)
    return apply_layernorm(params, x)  # parametric or non-parametric LN


# ------------------------------------------------------------------- ffn


def make_ffn_spec(cfg: ArchConfig):
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.pdtype
    if cfg.ffn in ("swiglu", "geglu"):
        return {
            "w_gate": ParamSpec((d, f), dt, ("embed", "mlp"), fan_in_init(in_axis=0)),
            "w_up": ParamSpec((d, f), dt, ("embed", "mlp"), fan_in_init(in_axis=0)),
            "w_down": ParamSpec((f, d), dt, ("mlp", "embed"), fan_in_init(in_axis=0)),
        }
    return {
        "w_up": ParamSpec((d, f), dt, ("embed", "mlp"), fan_in_init(in_axis=0)),
        "w_down": ParamSpec((f, d), dt, ("mlp", "embed"), fan_in_init(in_axis=0)),
    }


def apply_ffn(params, x, cfg: ArchConfig, *, qcfg=QuantConfig.off(), comp=None,
              name: str = "mlp"):
    def mm(key, xin, activation="none"):
        """act(xin @ w[key]) — on the serve path the matmul runs on the
        packed LUT GEMM with the activation fused into the kernel epilogue."""
        c = None if comp is None else comp.get(f"{name}/{key}")
        art = None if c is None else c.get("serve")
        if qcfg.enabled and qcfg.comp_mode == "serve" and art is not None:
            from repro.core.export import serve_dense

            return serve_dense(xin, art, activation=activation,
                               use_ref=qcfg.use_ref_kernel).astype(x.dtype)
        w = params[key]
        w = qat.fake_quant_weight(w, c) if qcfg.enabled else w
        y = jnp.einsum("...k,kn->...n", xin, w.astype(x.dtype))
        return ACTIVATIONS[activation](y)

    xin = qat.fake_quant_act(x) if (qcfg.enabled and qcfg.act_quant) else x
    if cfg.ffn in ("swiglu", "geglu"):
        act = "silu" if cfg.ffn == "swiglu" else "gelu"
        h = mm("w_gate", xin, act) * mm("w_up", xin)
    else:
        h = mm("w_up", xin, "gelu")
    if qcfg.enabled and qcfg.act_quant:
        h = qat.fake_quant_act(h)
    return mm("w_down", h)


# ------------------------------------------------------------------- blocks


def make_block_spec(cfg: ArchConfig, block_type: str, *, cross_attn: bool = False):
    spec = {"ln1": make_norm_spec(cfg)}
    if block_type in ("attn", "local"):
        spec["attn"] = A.make_attention_spec(
            cfg.attn_dims(block_type == "local"), cfg.pdtype)
        spec["ln2"] = make_norm_spec(cfg)
        if cfg.is_moe:
            spec["moe"] = MOE.make_moe_spec(cfg.moe_dims(), cfg.pdtype)
        else:
            spec["mlp"] = make_ffn_spec(cfg)
    elif block_type == "rglru":
        spec["rglru"] = RG.make_rglru_spec(cfg.rglru_dims(), cfg.pdtype)
        spec["ln2"] = make_norm_spec(cfg)
        spec["mlp"] = make_ffn_spec(cfg)
    elif block_type == "ssm":
        spec["ssm"] = SSM.make_ssm_spec(cfg.ssm_dims(), cfg.pdtype)
    else:
        raise ValueError(block_type)
    if cross_attn:
        spec["ln_x"] = make_norm_spec(cfg)
        spec["xattn"] = A.make_attention_spec(cfg.enc_attn_dims(), cfg.pdtype)
    return spec


def apply_block(
    params,
    x: jax.Array,
    cfg: ArchConfig,
    block_type: str,
    *,
    positions: Optional[jax.Array] = None,
    qcfg: QuantConfig = QuantConfig.off(),
    comp=None,
    enc_out: Optional[jax.Array] = None,
    q_block: int = 512,
    kv_block: int = 512,
    encoder: bool = False,
    return_state: bool = False,
    use_flash: bool = False,
):
    """One residual block (train/prefill).

    Returns (x, aux), or ((x, aux), state) when ``return_state`` — the state
    is the mixer's contribution to a decode cache (K/V after RoPE, or the
    recurrent/SSM final state).
    """
    aux = {"lb_loss": jnp.zeros((), jnp.float32),
           "z_loss": jnp.zeros((), jnp.float32)}
    state = None
    h = apply_norm(params["ln1"], x, cfg)
    if block_type in ("attn", "local"):
        dims = cfg.enc_attn_dims() if encoder else cfg.attn_dims(block_type == "local")
        mix = A.apply_attention(params["attn"], h, dims, positions=positions,
                                qcfg=qcfg, comp=comp, name="attn",
                                q_block=q_block, kv_block=kv_block,
                                return_kv=return_state, use_flash=use_flash)
        if return_state:
            mix, (k_st, v_st) = mix
            state = {"k": k_st, "v": v_st}
    elif block_type == "rglru":
        mix = RG.apply_rglru(params["rglru"], h, cfg.rglru_dims(),
                             qcfg=qcfg, comp=comp, name="rglru",
                             return_state=return_state)
        if return_state:
            mix, state = mix
    elif block_type == "ssm":
        mix = SSM.apply_ssm(params["ssm"], h, cfg.ssm_dims(),
                            qcfg=qcfg, comp=comp, name="ssm",
                            return_state=return_state)
        if return_state:
            mix, state = mix
    else:
        raise ValueError(block_type)
    x = x + mix

    if "xattn" in params:
        h = apply_norm(params["ln_x"], x, cfg)
        assert enc_out is not None, "cross-attention block needs encoder output"
        xa = A.apply_attention(
            params["xattn"], h, cfg.enc_attn_dims(), qcfg=qcfg, comp=comp,
            name="xattn", kv=_cross_kv(params["xattn"], enc_out, cfg, qcfg, comp),
            q_block=q_block, kv_block=kv_block)
        x = x + xa

    if block_type == "ssm":
        return ((x, aux), state) if return_state else (x, aux)

    h = apply_norm(params["ln2"], x, cfg)
    if cfg.is_moe and block_type in ("attn", "local"):
        y, moe_aux = MOE.apply_moe(params["moe"], h, cfg.moe_dims(),
                                   qcfg=qcfg, comp=comp, name="moe")
        aux = {"lb_loss": moe_aux["lb_loss"], "z_loss": moe_aux["z_loss"]}
    else:
        y = apply_ffn(params["mlp"], h, cfg, qcfg=qcfg, comp=comp, name="mlp")
    x = x + y
    return ((x, aux), state) if return_state else (x, aux)


def _cross_kv(attn_params, enc_out, cfg: ArchConfig, qcfg, comp):
    """K/V from encoder output for cross-attention (no RoPE)."""
    from repro.nn.attention import _project

    k = _project(attn_params, enc_out, qcfg, comp, "xattn", "wk", "bk")
    v = _project(attn_params, enc_out, qcfg, comp, "xattn", "wv", "bv")
    return k, v


# ------------------------------------------------------------------- decode


def block_cache_spec(cfg: ArchConfig, block_type: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16, *, cross_len: int = 0):
    if block_type in ("attn", "local"):
        dims = cfg.attn_dims(block_type == "local")
        cache_len = min(max_len, dims.window) if dims.window else max_len
        spec = A.kv_cache_spec(batch, cache_len, dims, dtype)
        if cross_len:
            xdims = cfg.enc_attn_dims()
            spec["xk"] = jax.ShapeDtypeStruct(
                (batch, cross_len, xdims.n_kv_heads, xdims.head_dim), dtype)
            spec["xv"] = jax.ShapeDtypeStruct(
                (batch, cross_len, xdims.n_kv_heads, xdims.head_dim), dtype)
        return spec
    if block_type == "rglru":
        return RG.rglru_cache_spec(batch, cfg.rglru_dims(), jnp.float32)
    if block_type == "ssm":
        return SSM.ssm_cache_spec(batch, cfg.ssm_dims(), jnp.float32)
    raise ValueError(block_type)


def init_block_cache(cfg: ArchConfig, block_type: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16, *, cross_len: int = 0):
    spec = block_cache_spec(cfg, block_type, batch, max_len, dtype,
                            cross_len=cross_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def apply_block_decode(
    params,
    x: jax.Array,            # (B, 1, d)
    cache: dict,
    pos: jax.Array,          # () or (B,) int32
    cfg: ArchConfig,
    block_type: str,
    *,
    qcfg: QuantConfig = QuantConfig.off(),
    comp=None,
) -> Tuple[jax.Array, dict]:
    """One decode step through a block, reading its cache; returns (x, out).

    Attention blocks give the new token's K/V rows ``{"k", "v"}`` (B, Hkv,
    D) for the caller to write into the cache (cross-attention K/V are only
    read); recurrent blocks give their whole new state.
    """
    h = apply_norm(params["ln1"], x, cfg)
    if block_type in ("attn", "local"):
        dims = cfg.attn_dims(block_type == "local")
        mix, out = A.apply_attention_decode(
            params["attn"], h, cache, pos, dims, qcfg=qcfg, comp=comp,
            name="attn")
    elif block_type == "rglru":
        mix, out = RG.apply_rglru_decode(
            params["rglru"], h, cache, cfg.rglru_dims(), qcfg=qcfg, comp=comp,
            name="rglru")
    elif block_type == "ssm":
        mix, out = SSM.apply_ssm_decode(
            params["ssm"], h, cache, cfg.ssm_dims(), qcfg=qcfg, comp=comp,
            name="ssm")
    else:
        raise ValueError(block_type)
    x = x + mix

    if "xattn" in params:
        h = apply_norm(params["ln_x"], x, cfg)
        xa, _ = A.apply_attention_decode(
            params["xattn"], h, {}, pos, cfg.enc_attn_dims(), qcfg=qcfg,
            comp=comp, name="xattn", cross_kv=(cache["xk"], cache["xv"]))
        x = x + xa

    if block_type == "ssm":
        return x, out

    h = apply_norm(params["ln2"], x, cfg)
    if cfg.is_moe and block_type in ("attn", "local"):
        y, _ = MOE.apply_moe(params["moe"], h, cfg.moe_dims(), qcfg=qcfg,
                             comp=comp, name="moe")
    else:
        y = apply_ffn(params["mlp"], h, cfg, qcfg=qcfg, comp=comp, name="mlp")
    return x + y, out


def apply_block_chunk(
    params,
    x: jax.Array,            # (B, C, d) one prefill chunk per row
    cache: dict,
    positions: jax.Array,    # (B, C) int32 absolute positions
    cfg: ArchConfig,
    block_type: str,
    *,
    qcfg: QuantConfig = QuantConfig.off(),
    comp=None,
    q_block: int = 8,
    kv_block: int = 8,
) -> Tuple[jax.Array, dict]:
    """One chunked-prefill step through a block; returns (x, updated cache).

    Attention blocks scatter the chunk's K/V into the row's cache and attend
    over the whole cache with per-row positions (see
    `attention.apply_attention_chunk`). Recurrent mixers (rglru/ssm) have no
    mid-sequence state injection, so they only support a single chunk that
    covers the whole prompt from position 0 — the engine enforces this
    statically by giving recurrent archs chunk buckets equal to the prompt
    buckets. Cross-attention (encoder/decoder) has no chunk path.
    """
    if "xattn" in params:
        raise ValueError("chunked prefill does not support cross-attention "
                         "blocks; use the oneshot/wave path")
    h = apply_norm(params["ln1"], x, cfg)
    new_cache = dict(cache)
    if block_type in ("attn", "local"):
        dims = cfg.attn_dims(block_type == "local")
        kv_cache = {"k": cache["k"], "v": cache["v"]}
        mix, kv_new = A.apply_attention_chunk(
            params["attn"], h, kv_cache, positions, dims, qcfg=qcfg,
            comp=comp, name="attn", q_block=q_block, kv_block=kv_block)
        new_cache.update(kv_new)
    elif block_type == "rglru":
        # chunk == whole prompt: the recurrence runs from its zero state
        mix, state = RG.apply_rglru(params["rglru"], h, cfg.rglru_dims(),
                                    qcfg=qcfg, comp=comp, name="rglru",
                                    return_state=True)
        new_cache = state
    elif block_type == "ssm":
        mix, state = SSM.apply_ssm(params["ssm"], h, cfg.ssm_dims(),
                                   qcfg=qcfg, comp=comp, name="ssm",
                                   return_state=True)
        new_cache = state
    else:
        raise ValueError(block_type)
    x = x + mix

    if block_type == "ssm":
        return x, new_cache

    h = apply_norm(params["ln2"], x, cfg)
    if cfg.is_moe and block_type in ("attn", "local"):
        y, _ = MOE.apply_moe(params["moe"], h, cfg.moe_dims(), qcfg=qcfg,
                             comp=comp, name="moe")
    else:
        y = apply_ffn(params["mlp"], h, cfg, qcfg=qcfg, comp=comp, name="mlp")
    return x + y, new_cache
