"""Pallas TPU kernels for the compute hot-spots of the paper's pipeline.

  lut_matmul         4-bit codebook-index GEMM (deploys the restricted
                     weight sets of Section 4 on the MXU)
  transition_energy  systolic partial-sum transition statistics (replaces
                     the paper's gate-level MAC profiling loop)
  fake_quant         fused mask+quantize+codebook-project (QAT hot path)

Each kernel ships `<name>.py` (pl.pallas_call + BlockSpec), `ops.py` (jit'd
wrapper + custom VJP where applicable) and `ref.py` (pure-jnp oracle).
Kernels target TPU VMEM/MXU tiling and are validated with interpret=True on
CPU (per-kernel allclose tests sweep shapes and dtypes). On a TPU they compile
through Mosaic; `tests/test_tpu_compile.py` compiles them for a described
v5e without a chip.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(flag: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` (or ``use_ref``) argument.

    An explicit bool wins. ``None`` resolves per backend: False on a TPU,
    where the kernels compile through Mosaic, and True elsewhere, where the
    Pallas interpreter (or the jnp oracle) runs the same program.
    """
    if flag is not None:
        return flag
    return jax.default_backend() != "tpu"
