"""Operations and bytes of the kernels the benchmark reads, from shapes.

``lut_matmul`` (the packed 4-bit LUT GEMM of ``kernels/lut_matmul``) at one
call: X (M, K) in its dtype, packed indices (K/2, N) int8, a 16-entry
codebook, a (1, N) f32 scale, an optional (1, N) bias and (M, N) residual,
and an (M, N) f32 output. Each byte is counted once: the least traffic the
call needs. Operations: 2 M K N.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "s32": 4, "u32": 4,
          "pred": 1}


def nbytes(dtype: str, dims: Sequence[int]) -> int:
    n = _BYTES[dtype]
    for d in dims:
        n *= d
    return n


def lut_matmul(shapes: Sequence[Tuple[str, Tuple[int, ...]]]
               ) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one LUT GEMM call, from the HLO shapes of its
    custom call (output first, then operands); None if they are not a
    LUT GEMM's."""
    if len(shapes) < 5:
        return None
    (odt, out), (xdt, x), (pdt, packed) = shapes[0], shapes[1], shapes[2]
    if pdt != "s8" or len(out) != 2 or len(x) != 2 or len(packed) != 2:
        return None
    m, k = x
    n = out[1]
    if packed != (k // 2, n) or out[0] != m:
        return None
    flops = 2.0 * m * k * n
    moved = sum(nbytes(dt, dims) for dt, dims in shapes)
    return flops, float(moved)


def roofline_s(flops: float, moved: float, peaks: dict) -> Tuple[float, str]:
    """Least time of a call and the bound that sets it."""
    t_mxu = flops / peaks["bf16_flops"]
    t_hbm = moved / peaks["hbm_bytes_per_s"]
    return (t_hbm, "hbm") if t_hbm >= t_mxu else (t_mxu, "mxu")


def lm_matmul_params(model: dict) -> int:
    """Weights one token's forward multiplies by: every projection of every
    layer and the (tied) unembedding over the real vocabulary."""
    d, h, hd, f = (model["d_model"], model["n_heads"], model["head_dim"],
                   model["d_ff"])
    kv = model.get("n_kv_heads", h)
    per_layer = d * hd * (2 * h + 2 * kv) + 3 * d * f
    return model["n_layers"] * per_layer + model["vocab"] * d


def decode_flops(model: dict, spans) -> float:
    """Model operations of the output tokens in ``spans``: for each
    (padded prompt p, tokens before, tokens after), 2 x weights per token
    plus attention, 4 x layers x d_model x context, where token j attends
    over p + j positions."""
    params = lm_matmul_params(model)
    attn = 4.0 * model["n_layers"] * model["n_heads"] * model["head_dim"]
    total = 0.0
    for p, n0, n1 in spans:
        n = n1 - n0
        ctx = n * p + (n0 + n1 - 1) * n / 2.0
        total += 2.0 * params * n + attn * ctx
    return total
