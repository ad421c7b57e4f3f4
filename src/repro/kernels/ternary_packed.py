"""Pallas TPU kernel: ternary matmul with 2-bit-PACKED weights.

The AIMC analogue taken to its conclusion: ternary codes {-1,0,+1} need 2
bits, so 4 codes pack into one uint8 — the HBM->VMEM weight stream is 4x
smaller than int8 (8x smaller than bf16), which is exactly the term DIANA's
AIMC array removes in the paper's Eq. for LAT_aimc (weights resident in the
array).  The kernel unpacks in VMEM (VPU shifts) and feeds the MXU int8 path.

Packing layout: w_packed[k, n] holds codes for K rows 4k..4k+3 of column n,
code c in bits (2c..2c+1), biased by +1 (00 -> -1, 01 -> 0, 10 -> +1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def pack_ternary(w_t: jax.Array) -> jax.Array:
    """(K, N) int8 codes in {-1,0,1} -> (K//4, N) uint8 packed."""
    K, N = w_t.shape
    assert K % 4 == 0
    biased = (w_t + 1).astype(jnp.uint8)           # {0,1,2}
    b = biased.reshape(K // 4, 4, N)
    return (b[:, 0] | (b[:, 1] << 2) | (b[:, 2] << 4) | (b[:, 3] << 6))


def unpack_ternary(w_p: jax.Array) -> jax.Array:
    """(K//4, N) uint8 -> (K, N) int8 codes; the kernels unpack with this."""
    Kp, N = w_p.shape
    # Shift, mask and bias in int32: the TPU vector unit has no int8
    # arithmetic, so the only int8 op is the final cast for the MXU.
    w = w_p.astype(jnp.int32)
    parts = [((w >> (2 * j)) & 3) - 1 for j in range(4)]
    return jnp.stack(parts, axis=1).reshape(Kp * 4, N).astype(jnp.int8)


def _kernel(x_ref, wp_ref, sw_ref, sx_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = unpack_ternary(wp_ref[...])                 # (bk//4, bn) -> (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sx_ref[0] * sw_ref[...]


def ternary_packed_matmul(x_q, w_packed, sx, sw, *, bm=DEFAULT_BM,
                          bn=DEFAULT_BN, bk=DEFAULT_BK, interpret=False):
    """x_q (M,K) int8; w_packed (K//4, N) uint8; sw (N,) f32 -> (M,N) f32."""
    m, k = x_q.shape
    kp, n = w_packed.shape
    assert kp * 4 == k and m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
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
    )(x_q, w_packed, sw.reshape(1, n), sx.reshape(1))
