"""Pallas TPU kernel: fused ODiMO split-precision matmul — the paper's
deployment hot-spot (Fig. 3) adapted to TPU.

After the reorg pass, a layer's output channels are contiguous per precision
domain: columns [0, boundary) belong to the int8 domain, [boundary, N) to the
bf16 domain.  This kernel computes BOTH domains' output slices in one
pallas_call: each N-block selects its path by comparing its column range to
the boundary (block-aligned by construction — ops.py rounds the boundary up
to the block size, mirroring the paper's channel-group alignment).

This is the zero-data-marshaling claim of Fig. 3 made concrete on TPU: one
kernel, one output buffer, no gather/concat between domains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def _kernel(x_ref, xq_ref, wb_ref, wq_ref, sw_ref, sx_ref, o_ref,
            acc_i_ref, acc_f_ref, *, nk: int, bn: int, boundary: int):
    j = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_i_ref[...] = jnp.zeros_like(acc_i_ref)
        acc_f_ref[...] = jnp.zeros_like(acc_f_ref)

    col0 = j * bn
    is_int8_block = col0 < boundary

    @pl.when(is_int8_block)
    def _int8_path():
        acc_i_ref[...] += jax.lax.dot_general(
            xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(jnp.logical_not(is_int8_block))
    def _bf16_path():
        acc_f_ref[...] += jax.lax.dot_general(
            x_ref[...], wb_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        int8_out = acc_i_ref[...].astype(jnp.float32) * sx_ref[0] * sw_ref[...]
        o_ref[...] = jnp.where(is_int8_block, int8_out, acc_f_ref[...])


def split_precision_matmul(x, x_q, sx, w_bf16, w_q, sw, boundary, *,
                           bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                           interpret=False):
    """Fused two-domain matmul.

    x (M,K) bf16; x_q (M,K) int8; w_bf16/w_q (K,N); sw (N,) f32;
    boundary: int (static) — first bf16-domain column, multiple of bn.
    """
    m, k = x.shape
    _, n = w_bf16.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert boundary % bn == 0, "ops.py aligns the domain split to bn"
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bn=bn, boundary=boundary),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, x_q, w_bf16, w_q, sw.reshape(1, n), sx.reshape(1))
