"""LM substrate tests: forward/prefill/decode agreement across families."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig
from repro.models.lm import _sinusoid, build_lm
from repro.nn import attention as A
from repro.nn import transformer as T
from repro.nn.layers import QuantConfig
from repro.nn.spec import init_params


def _mk(name="t", **kw):
    base = dict(
        name=name, family="dense", n_layers=4, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=300, head_dim=16,
        compute_dtype="float32",
    )
    base.update(kw)
    return ArchConfig(**base)


def _roundtrip(cfg, s_prompt=16, s_total=22, batch=2, **fwd):
    """prefill + decode must reproduce the full forward logits."""
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, s_total), 0, cfg.vocab)
    kwargs = dict(q_block=8, kv_block=8)
    full, _ = m.forward(params, toks, **kwargs, **fwd)
    lg, cache = m.prefill(params, toks[:, :s_prompt], max_len=s_total + 8,
                          cache_dtype=jnp.float32, **kwargs, **fwd)
    pf_err = float(jnp.max(jnp.abs(lg[:, :s_prompt, :cfg.vocab]
                                   - full[:, :s_prompt, :cfg.vocab])))
    errs = []
    for t in range(s_prompt, s_total):
        lg_d, cache = m.decode_step(params, cache, toks[:, t:t + 1])
        errs.append(float(jnp.max(jnp.abs(
            lg_d[:, 0, :cfg.vocab] - full[:, t, :cfg.vocab]))))
    return pf_err, max(errs), full


def test_dense_gqa_roundtrip():
    pf, dec, full = _roundtrip(_mk())
    assert bool(jnp.all(jnp.isfinite(full[..., :300])))
    assert pf < 1e-4 and dec < 1e-4


def test_local_global_ring_buffer_roundtrip():
    cfg = _mk(n_layers=5, pattern=("local", "local", "attn"), window=12)
    pf, dec, _ = _roundtrip(cfg)
    assert pf < 1e-4 and dec < 1e-4


def test_qkv_bias_and_untied():
    cfg = _mk(qkv_bias=True, tie_embeddings=False)
    pf, dec, _ = _roundtrip(cfg)
    assert pf < 1e-4 and dec < 1e-4


def test_nonparam_ln():
    cfg = _mk(norm="nonparam_ln", ffn="swiglu")
    m = build_lm(cfg)
    # non-parametric LN has zero norm params
    assert "scale" not in m.spec["final_norm"]
    pf, dec, _ = _roundtrip(cfg)
    assert pf < 1e-4 and dec < 1e-4


def test_ssm_mamba2_roundtrip():
    cfg = _mk(family="ssm", pattern=("ssm",), n_layers=4, n_heads=1,
              n_kv_heads=1, d_ff=0, ssm_d_state=32, ssm_head_dim=32,
              ssm_chunk=8)
    pf, dec, _ = _roundtrip(cfg)
    # recurrent states round through fp32; tolerance slightly looser
    assert pf < 1e-3 and dec < 1e-3


def test_rglru_hybrid_roundtrip():
    cfg = _mk(family="hybrid", pattern=("rglru", "rglru", "local"), window=12,
              n_layers=5, rnn_width=64, ffn="geglu", embed_scale=True)
    pf, dec, _ = _roundtrip(cfg)
    assert pf < 1e-3 and dec < 1e-3


def test_moe_roundtrip_and_aux():
    cfg = _mk(family="moe", n_experts=4, moe_top_k=2, moe_d_ff=64,
              capacity_factor=2.0)
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 300)
    logits, aux = m.forward(params, toks, q_block=8, kv_block=8)
    assert float(aux["lb_loss"]) > 0.0
    # lb loss for uniform routing ~= n_layers (E * sum(me*ce)/k ~ 1 per layer)
    assert float(aux["lb_loss"]) < 3 * cfg.n_layers
    pf, dec, _ = _roundtrip(cfg, s_prompt=12, s_total=18)
    # token dropping differs between batched prefill and single decode only
    # if capacity binds; with cf=2 it should not
    assert pf < 1e-3 and dec < 1e-3


def test_moe_shared_experts():
    cfg = _mk(family="moe", n_experts=4, moe_top_k=2, moe_d_ff=64,
              n_shared_experts=1, capacity_factor=2.0)
    pf, dec, _ = _roundtrip(cfg, s_prompt=12, s_total=16)
    assert pf < 1e-3 and dec < 1e-3


def test_encdec_whisper_roundtrip():
    cfg = _mk(family="audio", encoder_decoder=True, n_enc_layers=2,
              n_layers=2, ffn="gelu", norm="layernorm", rope_theta=0.0)
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    b, s_enc, s_dec = 2, 24, 14
    frames = jax.random.normal(jax.random.PRNGKey(2), (b, s_enc, cfg.d_model))
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s_dec), 0, 300)
    full, _ = m.forward(params, toks, enc_embeds=frames, q_block=8, kv_block=8)
    assert bool(jnp.all(jnp.isfinite(full[..., :300])))
    lg, cache = m.prefill(params, toks[:, :8], max_len=s_dec + 4,
                          enc_embeds=frames, cache_dtype=jnp.float32,
                          q_block=8, kv_block=8)
    pf_err = float(jnp.max(jnp.abs(lg[:, :8, :300] - full[:, :8, :300])))
    errs = []
    for t in range(8, s_dec):
        lg_d, cache = m.decode_step(params, cache, toks[:, t:t + 1])
        errs.append(float(jnp.max(jnp.abs(lg_d[:, 0, :300] - full[:, t, :300]))))
    assert pf_err < 1e-3 and max(errs) < 1e-3


def test_vlm_prefix_forward_and_loss():
    cfg = _mk(family="vlm", prefix_len=8)
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    b, p, s = 2, 8, 12
    prefix = jax.random.normal(jax.random.PRNGKey(2), (b, p, cfg.d_model))
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, 300)
    logits, _ = m.forward(params, toks, prefix_embeds=prefix, q_block=8,
                          kv_block=8)
    assert logits.shape == (b, p + s, cfg.padded_vocab)
    loss, metrics = m.loss(
        params, {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                 "prefix_embeds": prefix}, q_block=8, kv_block=8)
    assert bool(jnp.isfinite(loss))


def test_qat_forward_close_to_float():
    cfg = _mk()
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 300)
    lf, _ = m.forward(params, toks, q_block=8, kv_block=8)
    lq, _ = m.forward(params, toks, qcfg=QuantConfig.on(), q_block=8, kv_block=8)
    lf = lf[..., :300]
    lq = lq[..., :300]
    rel = float(jnp.linalg.norm(lq - lf) / jnp.maximum(jnp.linalg.norm(lf), 1e-9))
    assert rel < 0.2


def test_remat_matches_no_remat():
    cfg = _mk()
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 300)

    def loss_fn(p, remat):
        return m.loss(p, {"tokens": toks[:, :-1], "labels": toks[:, 1:]},
                      remat=remat, q_block=8, kv_block=8)[0]

    l0, g0 = jax.value_and_grad(lambda p: loss_fn(p, False))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss_fn(p, True))(params)
    assert float(jnp.abs(l0 - l1)) < 1e-5
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), g0, g1)
    assert max(jax.tree.leaves(diffs)) < 1e-4


def test_window_equals_full_when_window_large():
    """Local attention with window >= seq must equal full attention."""
    cfg_full = _mk(pattern=("attn",))
    cfg_loc = _mk(pattern=("local",), window=4096)
    m_f, m_l = build_lm(cfg_full), build_lm(cfg_loc)
    params = init_params(jax.random.PRNGKey(0), m_f.spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 300)
    lf, _ = m_f.forward(params, toks, q_block=8, kv_block=8)
    ll, _ = m_l.forward(params, toks, q_block=8, kv_block=8)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ll), atol=1e-5)


# ------------------------------------------------- decode writes in place

_NEW_ATTENTION_DECODE = A.apply_attention_decode


def _old_attention_decode(params, x, cache, pos, dims, *, qcfg, comp, name,
                          cross_kv=None):
    """The decode write this repo used to have: the new token's K/V go into
    the whole cache by a select, and attention reads the written cache."""
    if cross_kv is not None:
        return _NEW_ATTENTION_DECODE(params, x, cache, pos, dims, qcfg=qcfg,
                                     comp=comp, name=name, cross_kv=cross_kv)
    b = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = A._project(params, x, qcfg, comp, name, "wq", "bq")
    k_new = A._project(params, x, qcfg, comp, name, "wk", "bk")
    v_new = A._project(params, x, qcfg, comp, name, "wv", "bv")
    if dims.rope_theta > 0:
        q = A.apply_rope(q, pos_b[:, None], dims.rope_theta)
        k_new = A.apply_rope(k_new, pos_b[:, None], dims.rope_theta)
    smax = cache["k"].shape[1]
    idx = jnp.arange(smax, dtype=jnp.int32)
    write = (idx[None, :] == jnp.mod(pos_b, smax)[:, None])[..., None, None]
    k_cache = jnp.where(write, k_new.astype(cache["k"].dtype), cache["k"])
    v_cache = jnp.where(write, v_new.astype(cache["v"].dtype), cache["v"])
    cache_positions = idx[None, :] + (
        (pos_b[:, None] - idx[None, :]) // smax) * smax
    out = A.decode_attention(q, k_cache, v_cache, dims, cur_pos=pos_b,
                             cache_positions=cache_positions)
    return (A._project(params, out, qcfg, comp, name, "wo"),
            {"k": k_cache, "v": v_cache})


def _old_decode_step(m, params, cache, tokens, active):
    """Reference decode: the whole cache through the layer scan's `ys`, then
    a masked select of every leaf for the inactive rows."""
    cfg = m.cfg
    pos = cache["pos"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.cdtype)
    if cfg.encoder_decoder:
        x = x + _sinusoid(pos[:, None], cfg.d_model).astype(x.dtype)

    def block(p, h, c, bt):
        h, out = T.apply_block_decode(p, h, c, pos, cfg, bt)
        return h, {**c, **out}

    def body(h, xs):
        p, c = xs
        new = {}
        for i, bt in enumerate(cfg.pattern):
            h, new[f"g{i}"] = block(p[f"g{i}"], h, c[f"g{i}"], bt)
        return h, new

    new = {"groups": cache["groups"], "tail": {}, "pos": pos + 1}
    if m.n_rep > 0:
        x, new["groups"] = jax.lax.scan(body, x,
                                        (params["blocks"], cache["groups"]))
    for j in range(m.n_tail):
        x, new["tail"][f"t{j}"] = block(params["tail"][f"t{j}"], x,
                                        cache["tail"][f"t{j}"],
                                        cfg.pattern[j])

    def merge(axis):
        def f(n, o):
            shape = [1] * n.ndim
            shape[axis] = active.shape[0]
            return jnp.where(active.reshape(shape), n, o)
        return f

    new = {"groups": jax.tree.map(merge(1), new["groups"], cache["groups"]),
           "tail": jax.tree.map(merge(0), new["tail"], cache["tail"]),
           "pos": jnp.where(active, new["pos"], pos)}
    x = T.apply_norm(params["final_norm"], x, cfg)
    return m._unembed(params, x), new


_WRITE_CASES = {
    "dense": (_mk(), 1e-4),
    "local_ring": (_mk(n_layers=5, pattern=("local", "local", "attn"),
                       window=6), 1e-4),
    "moe": (_mk(family="moe", n_experts=4, moe_top_k=2, moe_d_ff=64,
                capacity_factor=2.0), 1e-3),
    "rglru_hybrid": (_mk(family="hybrid", pattern=("rglru", "rglru", "local"),
                         window=6, n_layers=5, rnn_width=64, ffn="geglu",
                         embed_scale=True), 1e-3),
    "ssm_hybrid": (_mk(family="hybrid", pattern=("ssm", "attn"), n_layers=3,
                       ssm_d_state=32, ssm_head_dim=32, ssm_chunk=8), 1e-3),
    "encdec": (_mk(family="audio", encoder_decoder=True, n_enc_layers=2,
                   n_layers=2, ffn="gelu", norm="layernorm",
                   rope_theta=0.0), 1e-3),
}


@pytest.mark.parametrize("case", list(_WRITE_CASES))
def test_decode_row_write_matches_whole_cache_write(case, monkeypatch):
    """The decode step that writes one K/V slot per row equals the one that
    rewrote the whole cache: over several steps with mixed `active` masks
    and rows at different depths (one at Smax - 1, one wrapping a ring)."""
    cfg, tol = _WRITE_CASES[case]
    m = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), m.spec)
    b, smax = 4, 16
    spec = m.cache_spec(b, smax, jnp.float32, cross_len=8 if
                        cfg.encoder_decoder else 0)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))
    cache = {sect: jax.tree.map(lambda s: 0.5 * jax.random.normal(
        next(keys), s.shape, s.dtype), spec[sect]) for sect in ("groups",
                                                               "tail")}
    cache["pos"] = jnp.asarray([0, 5, 11, smax - 1], jnp.int32)
    masks = [[1, 1, 1, 1], [1, 0, 1, 0], [0, 1, 0, 1], [1, 1, 0, 1],
             [0, 0, 1, 0], [1, 0, 1, 1]]
    step = jax.jit(m.decode_step)

    @jax.jit
    def old_step(p, c, t, a):
        with monkeypatch.context() as mp:     # patched while tracing only
            mp.setattr(A, "apply_attention_decode", _old_attention_decode)
            return _old_decode_step(m, p, c, t, a)

    ref_cache = cache
    for t, mask in enumerate(masks):
        active = jnp.asarray(mask, bool)
        toks = jax.random.randint(jax.random.PRNGKey(10 + t), (b, 1), 0,
                                  cfg.vocab)
        lg, new = step(params, cache, toks, active=active)
        lg_ref, ref_cache = old_step(params, ref_cache, toks, active)
        on = np.asarray(mask, bool)
        err = np.max(np.abs(np.asarray(lg - lg_ref)[on, 0, :cfg.vocab]))
        assert err < tol, (t, err)
        for sect, axis in (("groups", 1), ("tail", 0)):
            for key in cache[sect]:
                for n in cache[sect][key]:
                    a = np.asarray(new[sect][key][n])
                    o = np.asarray(cache[sect][key][n])
                    np.testing.assert_array_equal(
                        np.take(a, np.flatnonzero(~on), axis=axis),
                        np.take(o, np.flatnonzero(~on), axis=axis))
                    np.testing.assert_allclose(
                        a, ref_cache[sect][key][n], atol=tol, rtol=0)
        np.testing.assert_array_equal(new["pos"],
                                      np.asarray(cache["pos"]) + on)
        cache = new
