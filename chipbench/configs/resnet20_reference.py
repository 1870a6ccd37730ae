"""Plain reference of the profiler's layer statistics on a 64x64 array.

Written from the definitions the configuration states (paper Sec. 3.1 and
its energy model); it imports nothing of the system under test. Input: the
integer weights and activations one compressible layer multiplies (the
profile's taps). Per layer:

1. the layer as a matmul: W (M, K) = conv kernel (kh, kw, cin, cout) as
   (cout, kh*kw*cin), or a dense (in, out) transposed; X (K, N) = im2col
   columns of the NHWC activations ("SAME" padding, row k = (i*kw + j)*cin
   + c), or the dense input rows transposed;
2. W and X zero-padded to multiples of 64; tiles (mi, ki, ni) enumerated
   mi-major; ``min(max_tiles, tiles)`` drawn without replacement by
   ``jax.random.choice`` under the key ``PRNGKey(crc32(layer) % 2**31)``;
3. per tile, stationary w[r, c] = W[mi*64 + c, ki*64 + r] and stream
   a[r, t] = X[ki*64 + r, ni*64 + t]; partial sums
   p[r, c, t] = sum_{r' <= r} w[r', c] a[r', t];
4. per transition t-1 -> t of every PE (r, c):
   - act_hist[a[r,t-1] + 128, a[r,t] + 128] += 1 once per row r;
   - group_hist[g(p_prev), g(p_cur)] += 1, g = 5 * min(10*m // 23, 9)
     + min(5*h // 23, 4), m = 1 + index of the highest set bit of the
     22-bit pattern (0 for zero), h = its set bits;
   - count[w + 128] += 1, and energy_sum[w + 128] += the MAC energy:
     c_prod HD16(w a_prev, w a_cur) + c_pp HD8(a_prev, a_cur) HW8(w)
     + c_acc HD22(p_prev, p_cur) + c_carry (1 + highest toggled bit), or
     c_zero HD22 for w = 0, plus c_base.

Bit counts are explicit sums over bit positions. The toggle counts are
summed as integers per weight value and combined with the coefficients in
float64 on the host, so the reference's energy sums carry no rounding.
``bf16=True`` is the control: each MAC's energy is formed and summed in
bfloat16, one step below the program's float32.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

TILE = 64


def _bits(x, n: int):
    """(..., n) bits of the n-bit two's-complement pattern of ``x``."""
    x = jnp.asarray(x, jnp.int32)
    return (x[..., None] >> jnp.arange(n, dtype=jnp.int32)) & 1


def popcount(x, n: int):
    return jnp.sum(_bits(x, n), axis=-1)


def top_bit_plus_one(x, n: int):
    """1 + index of the highest set bit of the n-bit pattern; 0 for none."""
    b = _bits(x, n)
    return jnp.max(b * jnp.arange(1, n + 1, dtype=jnp.int32), axis=-1)


def group(p):
    m = top_bit_plus_one(p, 22)
    h = popcount(p, 22)
    return 5 * jnp.minimum(10 * m // 23, 9) + jnp.minimum(5 * h // 23, 4)


def layer_matrices(a_int, w_int, kind: str, kernel: int, stride: int):
    """(W (M, K), X (K, N)) int32 of one layer."""
    a = jnp.asarray(a_int, jnp.int32)
    w = jnp.asarray(w_int, jnp.int32)
    if kind == "dense":
        return w.T, a.reshape(-1, a.shape[-1]).T
    n, h, wd, c = a.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    ph = max((ho - 1) * stride + kernel - h, 0)
    pw = max((wo - 1) * stride + kernel - wd, 0)
    a = jnp.pad(a, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2),
                    (0, 0)))
    rows = []
    for i in range(kernel):
        for j in range(kernel):
            patch = a[:, i:i + (ho - 1) * stride + 1:stride,
                      j:j + (wo - 1) * stride + 1:stride, :]
            rows.append(patch.reshape(n * ho * wo, c).T)       # (c, N)
    x = jnp.concatenate(rows, axis=0)                          # (k*k*c, N)
    wm = jnp.transpose(w, (3, 0, 1, 2)).reshape(w.shape[3], -1)
    return wm, x


def sample_tiles(wm, x, layer: str, max_tiles: int):
    """(w tiles (n, K_t, M_t), a blocks (n, K_t, T)) of the sampled tiles."""
    m, k = wm.shape
    n = x.shape[1]
    mp, kp, np_ = (-(-d // TILE) * TILE for d in (m, k, n))
    wp = jnp.zeros((mp, kp), jnp.int32).at[:m, :k].set(wm)
    xp = jnp.zeros((kp, np_), jnp.int32).at[:k, :n].set(x)
    mt, kt, nt = mp // TILE, kp // TILE, np_ // TILE
    total = mt * kt * nt
    key = jax.random.PRNGKey(zlib.crc32(layer.encode()) % (2 ** 31))
    idx = np.asarray(jax.random.choice(key, total, (min(max_tiles, total),),
                                       replace=False))
    mi, ki, ni = idx // (kt * nt), (idx % (kt * nt)) // nt, idx % nt
    w_t = jnp.stack([wp[a * TILE:(a + 1) * TILE, b * TILE:(b + 1) * TILE].T
                     for a, b in zip(mi, ki)])
    a_t = jnp.stack([xp[b * TILE:(b + 1) * TILE, c * TILE:(c + 1) * TILE]
                     for b, c in zip(ki, ni)])
    return w_t, a_t


@jax.jit
def _tile_counts(w, a):
    """Integer statistics of one tile; w (K, M), a (K, T)."""
    p = jnp.cumsum(w[:, :, None] * a[:, None, :], axis=0)      # (K, M, T)
    p0, p1 = p[..., :-1], p[..., 1:]
    a0, a1 = a[:, None, :-1], a[:, None, 1:]
    ww = w[:, :, None]
    t_prod = popcount((ww * a0) ^ (ww * a1), 16)
    t_pp = popcount(a0 ^ a1, 8) * popcount(ww, 8)
    t_acc = popcount(p0 ^ p1, 22)
    t_carry = top_bit_plus_one(p0 ^ p1, 22)
    nz = ww != 0
    wbin = jnp.broadcast_to(w + 128, w.shape).reshape(-1)
    per_mac = lambda v: jnp.sum(v, axis=-1).reshape(-1)        # noqa: E731
    seg = lambda v: jax.ops.segment_sum(per_mac(v), wbin, 256)  # noqa: E731
    counts = jnp.stack([seg(jnp.where(nz, t_prod, 0)),
                        seg(jnp.where(nz, t_pp, 0)),
                        seg(jnp.where(nz, t_acc, 0)),
                        seg(jnp.where(nz, t_carry, 0)),
                        seg(jnp.where(nz, 0, t_acc)),
                        seg(jnp.ones_like(t_acc))])           # (6, 256)
    codes = (group(p0) * 50 + group(p1)).reshape(-1)
    gh = jnp.zeros((2500,), jnp.int32).at[codes].add(1).reshape(50, 50)
    ah = jnp.zeros((65536,), jnp.int32).at[
        ((a[:, :-1] + 128) * 256 + a[:, 1:] + 128).reshape(-1)].add(1)
    return counts, gh, ah.reshape(256, 256)


@jax.jit
def _tile_energy_bf16(w, a, coeffs):
    """The control: (256,) energy sums formed and added in bfloat16."""
    bf = jnp.bfloat16
    p = jnp.cumsum(w[:, :, None] * a[:, None, :], axis=0)
    p0, p1 = p[..., :-1], p[..., 1:]
    a0, a1 = a[:, None, :-1], a[:, None, 1:]
    ww = w[:, :, None]
    c = [coeffs[i].astype(bf) for i in range(6)]
    t_acc = popcount(p0 ^ p1, 22).astype(bf)
    active = (c[0] * popcount((ww * a0) ^ (ww * a1), 16).astype(bf)
              + c[1] * (popcount(a0 ^ a1, 8) * popcount(ww, 8)).astype(bf)
              + c[2] * t_acc
              + c[3] * top_bit_plus_one(p0 ^ p1, 22).astype(bf))
    e = jnp.where(ww == 0, c[4] * t_acc, active) + c[5]
    wbin = jnp.broadcast_to(w + 128, w.shape).reshape(-1)
    return jax.ops.segment_sum(jnp.sum(e, axis=-1, dtype=bf).reshape(-1),
                               wbin, 256)


COEFF_ORDER = ("c_prod", "c_pp", "c_acc", "c_carry", "c_zero", "c_base")


def layer_stats(w_tiles, a_blocks, coeffs: dict, *, bf16: bool = False):
    """{energy_sum, count, group_hist, act_hist} of a tile batch, numpy."""
    c = np.array([coeffs[k] for k in COEFF_ORDER], np.float64)
    counts = np.zeros((6, 256), np.int64)
    gh = np.zeros((50, 50), np.int64)
    ah = np.zeros((256, 256), np.int64)
    e_low = np.zeros((256,), np.float64)
    for w, a in zip(w_tiles, a_blocks):
        k, g, h = _tile_counts(w, a)
        counts += np.asarray(k, np.int64)
        gh += np.asarray(g, np.int64)
        ah += np.asarray(h, np.int64)
        if bf16:
            e_low += np.asarray(_tile_energy_bf16(
                w, a, jnp.asarray(c, jnp.float32)), np.float64)
    energy = (c[0] * counts[0] + c[1] * counts[1] + c[2] * counts[2]
              + c[3] * counts[3] + c[4] * counts[4] + c[5] * counts[5])
    return {"energy_sum": e_low if bf16 else energy,
            "count": counts[5].astype(np.float64),
            "group_hist": gh.astype(np.float64),
            "act_hist": ah.astype(np.float64)}
