"""Compile the planned matmul kernels for a described TPU v5e, no chip
attached: at zamba2-1.2b projection widths (K=2048, N=8192) for a decode
batch (M=4) and a prefill chunk (M=128), each op must lower to a compiled
Mosaic kernel (``tpu_custom_call``), not to interpret mode.  So must
``quant_matmul`` at the mamba2-1.3b chat cell's own projections, under the
blocks `ops.quant_matmul_blocks` chooses for them.

Interpret-mode parity tests cannot catch what only the TPU compiler
refuses (memory spaces, int8 vector arithmetic, tiling); these can.  The
topology is described inside a fixture so that only the worker that runs
this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

K, N = 2048, 8192


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be cached but never read back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _args(op, m, sds):
    f32, i8 = jnp.float32, jnp.int8
    scalar, steps = sds((), f32), sds((N,), f32)
    if op == "quant_matmul":
        return ops.quant_matmul_op, (sds((m, K), i8), sds((K, N), i8),
                                     scalar, steps), {}
    if op == "ternary_matmul":
        return ops.ternary_matmul_op, (sds((m, K), i8), sds((K, N), i8),
                                       scalar, steps), {}
    if op == "split_precision":
        return ops.split_precision_op, (
            sds((m, K), jnp.bfloat16), sds((m, K), i8), scalar,
            sds((K, N), jnp.bfloat16), sds((K, N), i8), steps), \
            {"boundary": N // 2}
    return ops.split_ternary_op, (
        sds((m, K), i8), sds((K, N), i8), sds((K // 4, N), jnp.uint8),
        scalar, steps), {"boundary": N // 2}


@pytest.mark.parametrize("m", [4, 128])
@pytest.mark.parametrize("op", ["quant_matmul", "ternary_matmul",
                                "split_precision", "split_ternary"])
def test_op_compiles_to_a_mosaic_kernel_for_v5e(one_chip, op, m):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn, args, kw = _args(op, m, sds)
    # the ops resolve interpret=None from the default backend, which is the
    # CPU here; the chip resolves it to False, so compile that explicitly
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **kw)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [16, 4096])
@pytest.mark.parametrize("k,n", [(2048, 8512), (4096, 2048)])
def test_quant_matmul_compiles_at_chosen_blocks(one_chip, m, k, n):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = (sds((m, k), jnp.int8), sds((k, n), jnp.int8),
            sds((), jnp.float32), sds((n,), jnp.float32))
    compiled = jax.jit(lambda *a: ops.quant_matmul_op(
        *a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
