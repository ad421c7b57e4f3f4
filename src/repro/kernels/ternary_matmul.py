"""Pallas TPU kernel: ternary-weight matmul (the AIMC-accelerator analogue).

Weights are codes in {-1, 0, +1} stored as int8.  On TPU the MXU's int8 path
executes this at 2x bf16 peak, and ternary codes make the weight stream
maximally compressible (the HBM->VMEM term of the roofline shrinks by 8x vs
bf16 at 2-bit packing; we stream int8 codes here and note 4x-packing as a
further step).  Structure mirrors quant_matmul with an int32 accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def _kernel(x_ref, w_ref, sw_ref, sx_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sx_ref[0] * sw_ref[...]


def ternary_matmul(x_q, w_t, sx, sw, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                   bk=DEFAULT_BK, interpret=False):
    """x_q (M,K) int8; w_t (K,N) int8 codes in {-1,0,1}; sw (N,) f32."""
    m, k = x_q.shape
    _, n = w_t.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_t, sw.reshape(1, n), sx.reshape(1))
