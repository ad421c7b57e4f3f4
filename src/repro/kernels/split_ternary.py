"""Pallas TPU kernel: fused ternary+int8 split matmul — DIANA's exact
domain pairing (digital int8 accelerator + ternary AIMC array) in one
``pallas_call``.

After the Fig. 3 reorg a DIANA mixed layer's output channels are contiguous
per domain: columns [0, boundary) belong to the int8 (digital) domain,
[boundary, N) to the ternary (AIMC) domain.  Both domains contract the SAME
int8 activations on the MXU int8 path; they differ only in the weight
stream and the per-column dequant step:

  * int8 blocks read ``w_q`` — int8 codes, streamed as-is;
  * ternary blocks read ``w_packed`` — 2-bit-packed codes (4 per byte, the
    `ternary_packed` layout), unpacked in VMEM with VPU shifts.  The
    HBM->VMEM weight stream of the ternary side is 4x smaller than int8 —
    the analogue of DIANA's weights-resident-in-array term (LAT_aimc).

One int32 accumulator serves both paths because ternary codes ARE valid
int8 codes; the per-column ``sw`` step carries each domain's own dequant
scale, applied once at flush.  This closes the paper's zero-data-marshaling
claim for the headline platform: no gather/concat between domains, and no
fp fallback for ternary+int8 mixed layers.

Column layout contract (matching `runtime.lower` / `kernels.ops`): the
boundary is rounded UP to the N-block size, so a block straddling the raw
boundary executes on the int8 path — safe, because ``w_q`` holds every
column's codes (ternary columns included) and ``sw`` its per-domain step.
``w_packed`` only needs valid codes at columns >= the raw boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_packed import unpack_ternary

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def _kernel(xq_ref, wq_ref, wp_ref, sw_ref, sx_ref, o_ref, acc_ref, *,
            nk: int, bn: int, boundary: int):
    j = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col0 = j * bn
    is_int8_block = col0 < boundary

    @pl.when(is_int8_block)
    def _int8_path():
        acc_ref[...] += jax.lax.dot_general(
            xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(jnp.logical_not(is_int8_block))
    def _ternary_path():
        w = unpack_ternary(wp_ref[...])             # (bk//4, bn) -> (bk, bn)
        acc_ref[...] += jax.lax.dot_general(
            xq_ref[...], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sx_ref[0] * sw_ref[...]


def split_ternary_matmul(x_q, w_q, w_packed, sx, sw, boundary, *,
                         bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                         interpret=False):
    """Fused int8+ternary two-domain matmul.

    x_q (M,K) int8; w_q (K,N) int8 codes (every column — ternary columns
    hold their {-1,0,+1} codes); w_packed (K//4,N) uint8 2-bit-packed codes
    (read only at columns >= boundary); sw (N,) f32 per-column dequant step;
    boundary: int (static) — first ternary-domain column, multiple of bn.
    """
    m, k = x_q.shape
    _, n = w_q.shape
    kp = w_packed.shape[0]
    assert kp * 4 == k, (w_packed.shape, x_q.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % 4 == 0, "the 2-bit packing needs a K-block multiple of 4"
    assert boundary % bn == 0, "ops.py aligns the domain split to bn"
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bn=bn, boundary=boundary),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, w_packed, sw.reshape(1, n), sx.reshape(1))
