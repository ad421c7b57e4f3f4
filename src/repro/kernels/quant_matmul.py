"""Pallas TPU kernel: w8a8 matmul with int32 accumulation + per-column scales.

Target: TPU v5e MXU int8 path (2x bf16 peak).  Grid (cdiv(M, bm), cdiv(N,
bn), K/bk) with the K dimension innermost ('arbitrary') accumulating into a
VMEM scratch, also when K is one block (as fast on a v5e).  M and N may
end in a ragged block: Pallas pads the reads and drops the writes past the
edge, and an output element reads only its own row of x and column of w, so
the padding never reaches a written element.  For the same reason w may
hold more columns than the output (N is the column steps' length).  K may
not: padding there would enter every sum (ops.py zero-pads K to a block
multiple).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512
#: the scoped VMEM a Mosaic kernel gets on v5e unless it asks for more
SCOPED_VMEM_DEFAULT = 16 * 2**20
VMEM_HEADROOM = 4 * 2**20


def vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """VMEM one grid step holds: double-buffered x, w, column-step and
    output tiles, the int32 dot result and its f32 product, and the int32
    accumulator."""
    tiles = bm * bk + bk * bn + 4 * bn + 4 * bm * bn
    return 2 * tiles + 12 * bm * bn


def vmem_limit_bytes(bm: int, bn: int, bk: int) -> int:
    """The scoped VMEM limit the kernel asks for: its footprint plus
    headroom for Mosaic's own scratch, never below the default."""
    return max(SCOPED_VMEM_DEFAULT, vmem_bytes(bm, bn, bk) + VMEM_HEADROOM)


def _kernel(x_ref, w_ref, sw_ref, sx_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        sx = sx_ref[0]
        sw = sw_ref[...]  # (1, bn)
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sx * sw


def quant_matmul(x_q, w_q, sx, sw, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                 bk=DEFAULT_BK, interpret=False):
    """x_q (M,K) int8, w_q (K,N') int8 with N' >= N, sx scalar f32, sw (N,)
    f32 -> (M,N) f32.

    K must be a multiple of ``bk``; M and N need not be multiples of
    ``bm`` / ``bn``.
    """
    m, k = x_q.shape
    (k2, n_w), n = w_q.shape, sw.shape[0]
    assert k == k2 and k % bk == 0 and n_w >= n, (x_q.shape, w_q.shape,
                                                   sw.shape, bk)
    nk = k // bk
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # sx scalar
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(bm, bn, bk)),
        interpret=interpret,
    )(x_q, w_q, sw.reshape(1, n), sx.reshape(1))
