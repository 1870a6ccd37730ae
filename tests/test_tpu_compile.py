"""Compile the main path for a described TPU v5e, with no chip attached.

Nothing runs: each test lowers and compiles with the TPU compiler for a chip
that is described, not attached. Mosaic's refusals (block tiling, ops it
cannot lower, scoped VMEM) and programs too large for the chip's memory
fail here, where interpret mode and the CPU backend accept them.

The topology is described inside a module fixture, never while the file is
imported: only one process may hold the TPU library, and every test worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the persistent
    cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("m,k,n", [(8, 2048, 8192), (8, 8192, 2048)],
                         ids=["olmo1b-decode-up", "olmo1b-decode-down"])
def test_lut_matmul_compiles(one_chip, m, k, n):
    """The fused LUT GEMM at olmo-1b decode shapes, with the block shapes
    the autotuner picks for a TPU, bias and activation fused."""
    from repro.kernels.lut_matmul.autotune import BlockAutotuner
    from repro.kernels.lut_matmul.ops import lut_matmul_fused

    bm, bn, bk = BlockAutotuner().best(m, k, n, pack_block=128, backend="tpu")

    def fn(x, packed, cb, scale, bias):
        return lut_matmul_fused(x, packed, cb, scale, bias=bias,
                                activation="silu", block_m=bm, block_n=bn,
                                block_k=bk, interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((m, k), jnp.bfloat16), ((k // 2, n), jnp.int8), ((16,), jnp.int8),
        ((n,), jnp.float32), ((n,), jnp.float32))]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_transition_kernel_compiles(one_chip):
    """The batched transition-statistics kernel at a resnet20 profiling
    shape: 16 sampled tiles of 64 x 64, T = 64."""
    from repro.core.mac_model import DEFAULT_COEFFS
    from repro.kernels.transition_energy.ops import batched_transition_stats

    def fn(w, a, mask):
        return batched_transition_stats(w, a, DEFAULT_COEFFS, mask=mask,
                                        interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((16, 64, 64), jnp.int32), ((16, 64, 64), jnp.int32),
        ((16,), jnp.float32))]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_profiling_compiles_on_four_chips(topo):
    """The tile batch sharded over a 2x2 host's four chips: the compiled
    kernel inside `shard_map`, then one all-reduce of the statistics."""
    from repro.core.profiler import sharded_layer_stats
    from repro.distributed.sharding import TILE_AXIS

    mesh = Mesh(np.asarray(topo.devices), (TILE_AXIS,))
    sharded = NamedSharding(mesh, PartitionSpec(TILE_AXIS))

    def fn(w, a, mask):
        return sharded_layer_stats(w, a, mask=mask, mesh=mesh,
                                   use_kernel=True, interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=sharded) for s, d in (
        ((48, 64, 64), jnp.int32), ((48, 64, 64), jnp.int32),
        ((48,), jnp.float32))]
    text = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text


@pytest.mark.parametrize("plan", ["uncompressed", "fake_quant_k4"])
def test_olmo1b_decode_step_compiles(one_chip, plan):
    """olmo-1b at published widths (bf16 compute) decodes one token for a
    full slot group with the engine's default f32 cache, and fits a chip."""
    from repro.configs import get_config
    from repro.core.lm_compress import make_lm_comp_spec
    from repro.models.lm import build_lm
    from repro.nn.layers import QuantConfig
    from repro.nn.spec import init_params
    from repro.serving import EngineConfig

    cfg = get_config("olmo-1b")
    assert cfg.compute_dtype == "bfloat16"
    model = build_lm(cfg)
    ecfg = EngineConfig()
    batch, total_len = ecfg.max_batch, ecfg.group_total_len
    params = _shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), model.spec)), one_chip)
    cache = _shapes(model.cache_spec(batch, total_len,
                                     jnp.dtype(ecfg.cache_dtype)), one_chip)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=one_chip)
    if plan == "uncompressed":
        qcfg, comp = QuantConfig.off(), None
    else:
        qcfg = QuantConfig.on()
        comp = _shapes(jax.eval_shape(lambda: init_params(
            jax.random.PRNGKey(0), make_lm_comp_spec(model))), one_chip)

    def step(p, c, cache_, t, act):
        return model.decode_step(p, cache_, t, qcfg=qcfg, comp=c, active=act)

    compiled = _compile(step, params, comp, cache, tok, active)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_olmo1b_decode_writes_cache_in_place(one_chip):
    """The engine's decode (cache donated, `active`-masked) at olmo-1b
    widths writes the new token's K/V into the cache in place: its
    temporaries do not grow with the cache. A step that rewrites the
    whole cache holds one whole-cache temporary, which grows with it."""
    from repro.configs import get_config
    from repro.models.lm import build_lm
    from repro.nn.spec import init_params
    from repro.serving import EngineConfig

    model = build_lm(get_config("olmo-1b"))
    ecfg = EngineConfig()
    batch = ecfg.max_batch
    params = _shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), model.spec)), one_chip)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=one_chip)
    step = jax.jit(lambda p, c, t, a: model.decode_step(p, c, t, active=a),
                   donate_argnums=(1,))
    temp, cache_bytes = [], []
    for total_len in (96, 1024):
        cache = _shapes(model.cache_spec(batch, total_len,
                                         jnp.dtype(ecfg.cache_dtype)),
                        one_chip)
        mem = step.lower(params, cache, tok, active).compile() \
            .memory_analysis()
        temp.append(mem.temp_size_in_bytes)
        cache_bytes.append(sum(s.size * s.dtype.itemsize
                               for s in jax.tree.leaves(cache)))
    assert temp[1] - temp[0] < 0.05 * (cache_bytes[1] - cache_bytes[0]), \
        (temp, cache_bytes)
