"""Pallas TPU kernel: causal flash attention with online softmax + GQA.

Grid (B*H, Sq/bq, Sk/bk) with the KV dimension innermost ('arbitrary');
running max/denominator/accumulator live in VMEM scratch.  GQA is handled in
the BlockSpec index maps: the kv block for flat head h reads kv head h // G,
so KV is never repeated in memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ, DEFAULT_BK = 256, 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, nk: int, bq: int, bk: int, scale: float, causal: bool):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip fully-masked blocks (strictly above the causal diagonal)
    run = jnp.logical_or(not causal, ik * bk <= iq * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0]          # (bq, d)
        k = k_ref[0]          # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, bq=DEFAULT_BQ, bk=DEFAULT_BK,
                    interpret=False):
    """q (B,H,Sq,D); k,v (B,KVH,Sk,D), H = KVH*G. Returns (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    G = H // KVH
    bq = min(bq, Sq)
    bk_ = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk_ == 0
    nk = Sk // bk_
    scale = D ** -0.5

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * KVH, Sk, D)
    vf = v.reshape(B * KVH, Sk, D)

    # kv index map: flat q head (b*H + h) -> flat kv head (b*KVH + h // G)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bk=bk_, scale=scale,
                          causal=causal),
        grid=(B * H, Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk_, D),
                         lambda h, iq, ik: ((h // H) * KVH + (h % H) // G, ik, 0)),
            pl.BlockSpec((1, bk_, D),
                         lambda h, iq, ik: ((h // H) * KVH + (h % H) // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
