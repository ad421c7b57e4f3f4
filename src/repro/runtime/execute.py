"""Per-layer executors for `ExecutionPlan`s.

`prepare_layer` binds one `LayerPlan` to a concrete weight and hoists
EVERYTHING per-call work can be hoisted out of: the plan's channel
permutation and its inverse (as a device array), per-domain weight
quantization with the plan's scales (each active quantized domain's columns
carry that domain's own log-scale/step; max-abs fallback when the plan was
lowered without scales), the bf16 weight cast of the split kernel, the
2-bit-packed ternary stream of the split_ternary kernel, the static
activation-quant scale/step, the block-aligned split boundary, and the
resolved kernel block sizes (``LayerPlan.tuning`` overrides threaded down
to the Pallas calls; an untuned ``quant_matmul`` layer takes
`kernels.ops.quant_matmul_blocks` of each call's shape instead, and
records the blocks per row count in ``PreparedLayer.chosen``).
`execute_layer` itself only quantizes the activations and calls the
kernel — nothing about the weights is rebuilt per call.  Both 2-D dense
weights and 4-D HWIO conv weights bind — conv weights are flattened to
``(kh*kw*c_in, c_out)`` and executed through `execute_conv_layer`, which
im2cols the NHWC input so CNN artifacts run through the same fused Pallas
kernels as dense layers.

`execute_layer` runs an input through the matching Pallas kernel —
interpret mode on CPU — or through the pure-jnp reference oracle
(``reference=True``), always returning outputs in the ORIGINAL channel
order (the inverse permutation is applied, mirroring
`kernels.ops.odimo_deployed_dense`; the full Fig. 3 reorg removes it by
rewriting the next layer's input channels).

`PlanSet` binds a BANK of plans — N `ExecutionPlan` variants of the same
weights (e.g. a ternary-heavy "draft" and an int8-heavy "target" mapping)
— to one params pytree and implements the NAME-KEYED matmul-backend
protocol of `repro.models`
(``backend(name, p, x, conv=...) -> y | None``): plans are resolved by the
layer's pytree path — a static string — so planned execution traces cleanly
under ``jax.jit`` (weights may be tracers; the prepared arrays are baked
into the trace as constants).  The active variant is the trace-static key
published via ``repro.models._backend.plan_variant`` (default variant
outside any context), and prepared weight buffers are DEDUPLICATED across
variants wherever a layer's (plan, weight, domain-bits, block) tuple
coincides — ``prepared_bytes()``/``memory_report()`` account for the
sharing.  `PlannedBackend` is the single-variant special case (the
original API).  Scan-stacked plans (``base@r`` layer names) are GROUPED by
their static stack key: repeats whose kernels/boundaries/blocks agree
stack on a leading axis and execute as one gather indexed by the scan
index published by ``repro.models._backend.scan_slot``; a heterogeneous
stack dispatches ``jax.lax.switch`` over its GROUPS (G <= R branches)
rather than over every repeat — ``stack_mode="switch"`` restores the
one-branch-per-repeat dispatch as a benchmark baseline.  Install the
backend with ``repro.models.managed.matmul_backend(backend)`` and every
managed/LM dense or conv whose layer the plan covers executes through its
planned kernel, bias included — no model code forks.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels import ops, ref
from repro.kernels.ternary_packed import pack_ternary
from repro.models import _backend
from repro.runtime.lower import _layer_weight, _walk_path
from repro.runtime.plan import (KERNEL_FP, KERNEL_QUANT, KERNEL_SPLIT,
                                KERNEL_SPLIT_TERNARY, KERNEL_TERNARY,
                                ExecutionPlan, LayerPlan)

DEFAULT_BM, DEFAULT_BK = 128, 512


class ExecutionError(RuntimeError):
    """A planned layer cannot be executed as lowered."""


@dataclasses.dataclass
class PreparedLayer:
    """A `LayerPlan` bound to concrete arrays, ready to execute.

    Everything static or weight-derived is materialized here ONCE — per-call
    execution touches only the activations."""
    plan: LayerPlan
    inv: jax.Array                   # inverse channel permutation (device)
    w_perm: jax.Array | None         # permuted weights, original dtype (K, N)
                                     # (None for stacked quant/ternary slices
                                     # — those kernels never read it)
    b: jax.Array | None              # bias, ORIGINAL channel order
    w_q: jax.Array | None            # int8 codes, permuted (quantized paths)
    sw: jax.Array | None             # (N,) per-column dequant step, f32
    act_log_scale: float | None      # None -> dynamic max-abs per call
    block_n: int = 128               # N-block the plan was aligned with
    conv_shape: Tuple[int, ...] | None = None  # HWIO shape of a conv weight
    # ---- hoisted per-call state (derived; see prepare_layer) -------------
    w_bf16: jax.Array | None = None  # split kernel: bf16 cast of w_perm
    w_t_packed: jax.Array | None = None  # split_ternary: 2-bit-packed codes
    act_scale: jax.Array | None = None   # exp(act_log_scale), f32 scalar
    act_sx: jax.Array | None = None      # act dequant step, f32 scalar
    boundary: int = 0                # raw split boundary (static)
    # bm, bn, bk; None: chosen per call from its shape (untuned quant)
    blocks: Tuple[int, int, int] | None = (DEFAULT_BM, 128, DEFAULT_BK)
    # rows -> (bm, bn, bk) of each traced quant_matmul call
    chosen: Dict[int, Tuple[int, int, int]] = dataclasses.field(
        default_factory=dict)

    @property
    def kernel(self) -> str:
        return self.plan.kernel

    @property
    def conv_groups(self) -> int:
        return self.plan.groups


def _quant_domain(lp: LayerPlan, domain_bits: List[int]) -> int:
    """Index of the first active quantized domain (drives the codes of any
    identity-domain columns that execute in int8 through block padding)."""
    active = lp.active_domains()
    quantized = [i for i in active if domain_bits[i] < 16]
    if not quantized:
        raise ExecutionError(f"{lp.name}: no quantized domain for kernel "
                             f"{lp.kernel}")
    return quantized[0]


def _per_column_quant(lp: LayerPlan, wf: jax.Array,
                      domain_bits: List[int]) -> Tuple[jax.Array, jax.Array]:
    """(w_q int8 codes, sw (N,) f32 steps) in PERMUTED column order, built
    per domain: each active quantized domain's columns are quantized with
    that domain's own ``w_log_scales`` entry and bit-width, so multi-
    quantized-domain plans (e.g. 3-domain ``gap9_like``) dequantize every
    column with the right step.  Identity (>=16-bit) columns inherit the
    driving quantized domain's codes — conservative for the block-aligned
    extra columns the split kernel executes in int8."""
    drive = _quant_domain(lp, domain_bits)
    if lp.w_log_scales is not None:
        ls_of = lambda d: float(lp.w_log_scales[d])
    else:  # lowered without scales: max-abs of the bound weight
        ls = float(quant.init_log_scale(wf))
        ls_of = lambda d: ls
    bits_of = lambda d: (2 if lp.kernel == KERNEL_TERNARY
                         else min(int(domain_bits[d]), 8))
    col_ls = np.zeros(lp.c_out, np.float32)
    col_levels = np.ones(lp.c_out, np.float32)
    start = 0
    for d, c in enumerate(lp.counts):
        if c:
            src = d if domain_bits[d] < 16 else drive
            col_ls[start:start + c] = ls_of(src)
            col_levels[start:start + c] = quant.qlevels(bits_of(src))
        start += c
    scale = jnp.asarray(np.exp(col_ls))
    levels = jnp.asarray(col_levels)
    w_q = jnp.round(jnp.clip(wf / scale[None, :], -1.0, 1.0) *
                    levels[None, :]).astype(jnp.int8)
    sw = (scale / levels).astype(jnp.float32)
    return w_q, sw


def _resolve_blocks(lp: LayerPlan,
                    block_n: int) -> Tuple[int, int, int] | None:
    """(bm, bn, bk) for the layer's kernel calls: plan-level ``block_n``
    with `LayerPlan.tuning` overrides.  None for an untuned ``quant_matmul``
    layer, whose calls take blocks from their shapes: its ``bn`` bounds no
    domain, where the split kernels' aligns the boundary (numerics)."""
    if lp.kernel == KERNEL_QUANT and not lp.tuning:
        return None
    tun = lp.tuning or {}
    bm = int(tun.get("bm", DEFAULT_BM))
    bn = int(tun.get("bn", block_n))
    bk = int(tun.get("bk", DEFAULT_BK))
    if min(bm, bn, bk) < 1:
        raise ExecutionError(f"{lp.name}: invalid kernel tuning {tun}")
    if lp.kernel == KERNEL_SPLIT_TERNARY and bk % 4 != 0:
        raise ExecutionError(f"{lp.name}: split_ternary needs bk % 4 == 0 "
                             f"(2-bit packing), got bk={bk}")
    return bm, bn, bk


def _pack_ternary_stream(lp: LayerPlan, w_q: jax.Array) -> jax.Array:
    """The split_ternary kernel's compressed weight side: 2-bit-pack the
    ternary-domain columns of the per-domain codes (int8 columns zeroed —
    the kernel never reads them from the packed stream), K padded up to a
    multiple of 4 with code 0."""
    K, N = w_q.shape
    boundary = lp.split_boundary()
    cols = jnp.arange(N)[None, :]
    w_t = jnp.where(cols >= boundary, w_q, 0).astype(jnp.int8)
    k4 = -(-K // 4) * 4
    if k4 != K:
        w_t = jnp.pad(w_t, ((0, k4 - K), (0, 0)))
    return pack_ternary(w_t)


def _expand_grouped(w, groups: int) -> jax.Array:
    """Zero-embed a grouped conv weight ``(kh, kw, C_in/G, C_out)`` into the
    block-diagonal full matrix ``(kh, kw, C_in, C_out)``: input-channel
    block g only reaches output-channel block g (XLA's
    ``feature_group_count`` semantics), every other entry is exactly zero.
    Zeros quantize to code 0 in every domain, so the expanded weight runs
    through the SAME im2col'd dense kernels as an ungrouped conv — trading
    G-fold redundant MACs for kernel coverage (the cost model still prices
    the true grouped geometry via ``LayerGeometry.groups``)."""
    kh, kw, cpg, c_out = (int(s) for s in w.shape)
    if c_out % groups:
        raise ExecutionError(f"{c_out} output channels do not divide into "
                             f"{groups} conv groups")
    opg = c_out // groups
    eye = jnp.eye(groups, dtype=w.dtype)
    w5 = jnp.asarray(w).reshape(kh, kw, cpg, groups, opg)
    full = jnp.einsum("hwcgo,gG->hwGcgo", w5, eye)
    return full.reshape(kh, kw, groups * cpg, c_out)


def prepare_layer(lp: LayerPlan, w, b=None,
                  domain_bits: List[int] | None = None,
                  block_n: int = 128) -> PreparedLayer:
    """Bind ``lp`` to a concrete weight (+ optional bias): a 2-D
    (C_in, C_out) dense matrix or a 4-D (kh, kw, C_in, C_out) HWIO conv
    kernel (flattened to ``(kh*kw*C_in, C_out)``; run conv layers through
    `execute_conv_layer`).  A plan with ``groups > 1`` binds a grouped/
    depthwise conv weight ``(kh, kw, C_in/G, C_out)`` — zero-embedded into
    its block-diagonal dense form (`_expand_grouped`) so it executes
    through the same kernels."""
    ndim = getattr(w, "ndim", 0)
    if ndim not in (2, 4):
        raise ExecutionError(f"{lp.name}: planned execution covers 2-D "
                             f"(dense) and 4-D (HWIO conv) weights, got "
                             f"shape {tuple(getattr(w, 'shape', ()))}")
    if int(w.shape[-1]) != lp.c_out:
        raise ExecutionError(f"{lp.name}: weight has {int(w.shape[-1])} "
                             f"output channels, plan expects {lp.c_out}")
    if lp.groups > 1:
        if ndim != 4:
            raise ExecutionError(f"{lp.name}: groups={lp.groups} needs a "
                                 f"4-D HWIO conv weight, got shape "
                                 f"{tuple(w.shape)}")
        w = _expand_grouped(w, lp.groups)
    conv_shape = tuple(int(s) for s in w.shape) if ndim == 4 else None
    w2 = jnp.asarray(w).reshape(-1, int(w.shape[-1]))
    if domain_bits is None:
        domain_bits = [8] * len(lp.counts)
    w_perm = jnp.take(w2, jnp.asarray(lp.perm), axis=-1)
    w_q = sw = w_bf16 = w_t_packed = act_scale = act_sx = None
    if lp.kernel in (KERNEL_QUANT, KERNEL_TERNARY, KERNEL_SPLIT,
                     KERNEL_SPLIT_TERNARY):
        w_q, sw = _per_column_quant(lp, w_perm.astype(jnp.float32),
                                    domain_bits)
    if lp.kernel == KERNEL_SPLIT:
        w_bf16 = w_perm.astype(jnp.bfloat16)
    if lp.kernel == KERNEL_SPLIT_TERNARY:
        w_t_packed = _pack_ternary_stream(lp, w_q)
    if lp.act_log_scale is not None:
        act_scale = jnp.asarray(np.exp(lp.act_log_scale), jnp.float32)
        act_sx = (act_scale / quant.qlevels(8)).astype(jnp.float32)
    return PreparedLayer(plan=lp, inv=jnp.asarray(lp.inv_perm()),
                         w_perm=w_perm,
                         b=(jnp.asarray(b) if b is not None else None),
                         w_q=w_q, sw=sw, act_log_scale=lp.act_log_scale,
                         block_n=block_n, conv_shape=conv_shape,
                         w_bf16=w_bf16, w_t_packed=w_t_packed,
                         act_scale=act_scale, act_sx=act_sx,
                         boundary=lp.split_boundary(),
                         blocks=_resolve_blocks(lp, block_n))


def _act_quant(xf: jax.Array, prep: PreparedLayer):
    """(x_q int8, sx step): the prepared static scale when one was lowered
    (exp/step hoisted into `prepare_layer`), else dynamic max-abs (the
    v1-artifact migration path)."""
    if prep.act_scale is not None:
        scale, sx = prep.act_scale, prep.act_sx
    else:
        scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-8)
        sx = (scale / quant.qlevels(8)).astype(jnp.float32)
    x_q = jnp.round(jnp.clip(xf / scale, -1.0, 1.0) *
                    quant.qlevels(8)).astype(jnp.int8)
    return x_q, sx


def execute_layer(prep: PreparedLayer, x, *, interpret=None,
                  reference: bool = False) -> jax.Array:
    """Run ``x (..., C_in)`` through the prepared layer's kernel; returns
    ``(..., C_out)`` in the original channel order, bias applied, in
    ``x.dtype``.  ``reference=True`` routes through the pure-jnp oracles
    (`kernels.ref`) instead of the Pallas kernels — the bit-tolerance
    reference path.  Jit-safe: ``x`` (and the prepared arrays, for stacked
    repeats) may be tracers."""
    lp = prep.plan
    wk = prep.w_perm if prep.w_perm is not None else prep.w_q
    if int(x.shape[-1]) != int(wk.shape[-2]):
        raise ExecutionError(f"{lp.name}: input has {int(x.shape[-1])} "
                             f"features, weight expects "
                             f"{int(wk.shape[-2])}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xf = x2.astype(jnp.float32)
    m, k = x2.shape
    bm, bn, bk = prep.blocks or ops.quant_matmul_blocks(m, k, lp.c_out)
    # the ops clamp the N-block to min(bn, max(128, n)) and round the
    # boundary up to it; the oracles must split at the same column
    bn_eff = min(bn, max(128, lp.c_out))

    if lp.kernel == KERNEL_FP:
        y = xf @ prep.w_perm.astype(jnp.float32)
    elif lp.kernel in (KERNEL_QUANT, KERNEL_TERNARY):
        x_q, sx = _act_quant(xf, prep)
        if reference:
            fn = (ref.ternary_matmul_ref if lp.kernel == KERNEL_TERNARY
                  else ref.quant_matmul_ref)
            y = fn(x_q, prep.w_q, sx, prep.sw)
        else:
            fn = (ops.ternary_matmul_op if lp.kernel == KERNEL_TERNARY
                  else ops.quant_matmul_op)
            if lp.kernel == KERNEL_QUANT:
                prep.chosen[m] = (bm, bn, bk)
            y = fn(x_q, prep.w_q, sx, prep.sw, bm=bm, bn=bn, bk=bk,
                   interpret=interpret)
    elif lp.kernel == KERNEL_SPLIT_TERNARY:
        x_q, sx = _act_quant(xf, prep)
        if reference:
            y = ref.split_ternary_matmul_ref(
                x_q, prep.w_q, prep.w_q, sx, prep.sw,
                ops.align_boundary(prep.boundary, bn_eff))
        else:
            y = ops.split_ternary_op(x_q, prep.w_q, prep.w_t_packed, sx,
                                     prep.sw, prep.boundary, bm=bm, bn=bn,
                                     bk=bk, interpret=interpret)
    elif lp.kernel == KERNEL_SPLIT:
        x_q, sx = _act_quant(xf, prep)
        xb = x2.astype(jnp.bfloat16)
        if reference:
            y = ref.split_precision_matmul_ref(
                xb, x_q, sx, prep.w_bf16, prep.w_q, prep.sw,
                ops.align_boundary(prep.boundary, bn_eff))
        else:
            y = ops.split_precision_op(xb, x_q, sx, prep.w_bf16, prep.w_q,
                                       prep.sw, prep.boundary, bm=bm, bn=bn,
                                       bk=bk, interpret=interpret)
    else:  # pragma: no cover - __post_init__ rejects unknown kernels
        raise ExecutionError(f"{lp.name}: unknown kernel {lp.kernel}")

    y = jnp.take(y, prep.inv, axis=-1)
    if prep.b is not None:
        y = y + prep.b.astype(y.dtype)
    return y.reshape(*lead, lp.c_out).astype(x.dtype)


# --------------------------------------------------------------------------
# Conv execution: im2col onto the dense kernels
# --------------------------------------------------------------------------

def _same_pads(size: int, k: int, stride: int) -> Tuple[int, int, int]:
    out = -(-size // stride)
    pad = max((out - 1) * stride + k - size, 0)
    return out, pad // 2, pad - pad // 2


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1,
           padding: str = "SAME") -> jax.Array:
    """NHWC input -> (B, OH, OW, kh*kw*C) patches whose last axis matches a
    flattened HWIO conv weight ``w.reshape(kh*kw*C, C_out)`` (row-major
    (kh, kw, C) order), with XLA's SAME/VALID padding semantics — so
    ``im2col(x) @ w_flat == lax.conv_general_dilated(x, w)``."""
    B, H, W, C = x.shape
    if padding == "SAME":
        oh, pt, pb = _same_pads(H, kh, stride)
        ow, pl, pr = _same_pads(W, kw, stride)
        x = jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
    elif padding == "VALID":
        oh = (H - kh) // stride + 1
        ow = (W - kw) // stride + 1
    else:
        raise ExecutionError(f"unsupported conv padding {padding!r}")
    if oh < 1 or ow < 1:
        raise ExecutionError(f"conv kernel ({kh}x{kw}) exceeds input "
                             f"({H}x{W}) under {padding} padding")
    cols = [x[:, i:i + (oh - 1) * stride + 1:stride,
              j:j + (ow - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(cols, axis=-1)


def execute_conv_layer(prep: PreparedLayer, x, stride: int = 1,
                       padding: str = "SAME", *, interpret=None,
                       reference: bool = False) -> jax.Array:
    """Run an NHWC input through a prepared CONV layer: im2col the input to
    ``(B, OH, OW, kh*kw*C_in)`` patches and execute them through the layer's
    planned dense kernel (groups == 1 only)."""
    if prep.conv_shape is None:
        raise ExecutionError(f"{prep.plan.name}: not a conv layer (bound "
                             f"weight was 2-D)")
    kh, kw, ci, _ = prep.conv_shape
    if int(x.shape[-1]) != ci:
        raise ExecutionError(f"{prep.plan.name}: input has "
                             f"{int(x.shape[-1])} channels, conv weight "
                             f"expects {ci}")
    patches = im2col(x, kh, kw, stride=stride, padding=padding)
    return execute_layer(prep, patches, interpret=interpret,
                         reference=reference)


def reference_layer(prep: PreparedLayer, x) -> jax.Array:
    """Full-precision oracle: ``x @ w + b`` on the ORIGINAL weight order
    (the parity target planned execution is pinned against)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    w = jnp.take(prep.w_perm, prep.inv, axis=-1)
    y = x2 @ w.astype(jnp.float32)
    if prep.b is not None:
        y = y + prep.b.astype(y.dtype)
    return y.reshape(*lead, prep.plan.c_out).astype(x.dtype)


# --------------------------------------------------------------------------
# Scan-stacked prepared layers
# --------------------------------------------------------------------------

def _stack_key(prep: PreparedLayer):
    """Repeats can share one stacked execution only when everything STATIC
    about their kernels agrees — arrays may differ, trace structure may
    not."""
    lp = prep.plan
    return (lp.kernel, lp.c_in, lp.c_out, tuple(lp.counts),
            tuple(lp.aligned_boundaries), prep.boundary, prep.blocks,
            prep.block_n, prep.conv_shape, prep.b is None,
            prep.act_log_scale is None)


#: kernels whose execute paths never read the fp32 weight copy (split reads
#: the hoisted bf16 cast instead) — stacking w_perm would hold R
#: full-precision matrices that only the eager `reference_layer` oracle
#: could use, and stacked entries never route there
_DROPS_FP_STACK = (KERNEL_QUANT, KERNEL_TERNARY, KERNEL_SPLIT_TERNARY,
                   KERNEL_SPLIT)


class _StackedPrepared:
    """Homogeneous per-repeat `PreparedLayer`s stacked on a leading R axis;
    ``at(r)`` slices repeat ``r`` (r may be a traced scan index — this is
    what executes scan-stacked LM layers inside the jitted layer scan)."""

    def __init__(self, preps: List[PreparedLayer]):
        p0 = preps[0]
        self.plan, self.block_n = p0.plan, p0.block_n
        self.conv_shape = p0.conv_shape
        self.conv_groups = p0.plan.groups
        self.boundary, self.blocks = p0.boundary, p0.blocks
        self.chosen = p0.chosen
        self.n_repeats = len(preps)
        st = lambda get: (None if get(p0) is None
                          else jnp.stack([jnp.asarray(get(p)) for p in preps]))
        self._inv = st(lambda p: p.inv)
        self._w_perm = (st(lambda p: p.w_perm)
                        if p0.plan.kernel not in _DROPS_FP_STACK else None)
        self._w_bf16 = st(lambda p: p.w_bf16)
        self._w_t_packed = st(lambda p: p.w_t_packed)
        self._b = st(lambda p: p.b)
        self._w_q = st(lambda p: p.w_q)
        self._sw = st(lambda p: p.sw)
        self._act_scale = st(lambda p: p.act_scale)
        self._act_sx = st(lambda p: p.act_sx)

    def at(self, r) -> PreparedLayer:
        take = lambda a: None if a is None else jnp.take(a, r, axis=0)
        return PreparedLayer(
            plan=self.plan, inv=take(self._inv), w_perm=take(self._w_perm),
            b=take(self._b), w_q=take(self._w_q), sw=take(self._sw),
            act_log_scale=self.plan.act_log_scale,
            block_n=self.block_n, conv_shape=self.conv_shape,
            w_bf16=take(self._w_bf16), w_t_packed=take(self._w_t_packed),
            act_scale=take(self._act_scale), act_sx=take(self._act_sx),
            boundary=self.boundary, blocks=self.blocks, chosen=self.chosen)

    def execute(self, x, r, conv=None, *, interpret=None, reference=False):
        prep = self.at(r)
        if conv is not None:
            return execute_conv_layer(prep, x, conv["stride"],
                                      conv["padding"], interpret=interpret,
                                      reference=reference)
        return execute_layer(prep, x, interpret=interpret,
                             reference=reference)


class _SingleRepeat:
    """A one-repeat stack (R=1, e.g. every reduced-config layer stack): the
    scan index is necessarily 0, so the prepared arrays execute DIRECTLY —
    no leading stack axis, no per-iteration dynamic gather."""

    def __init__(self, prep: PreparedLayer):
        # same fp32-copy drop as the other stack containers: stacked
        # entries never route to reference_layer, so w_perm is dead weight
        if prep.plan.kernel in _DROPS_FP_STACK:
            prep = dataclasses.replace(prep, w_perm=None)
        self.prep = prep
        self.conv_shape = prep.conv_shape
        self.conv_groups = prep.plan.groups

    def execute(self, x, r, conv=None, *, interpret=None, reference=False):
        if conv is not None:
            return execute_conv_layer(self.prep, x, conv["stride"],
                                      conv["padding"], interpret=interpret,
                                      reference=reference)
        return execute_layer(self.prep, x, interpret=interpret,
                             reference=reference)


def _stack_group(preps: List[PreparedLayer]):
    """One homogeneous group: direct execution for a single repeat, a
    stacked gather otherwise."""
    return (_SingleRepeat(preps[0]) if len(preps) == 1
            else _StackedPrepared(preps))


class _GroupedPrepared:
    """Per-repeat `PreparedLayer`s grouped by static stack key: every group
    is a `_StackedPrepared` over the repeats that share its trace structure,
    and a (possibly traced) scan index dispatches ``jax.lax.switch`` over
    the G GROUPS — not over all R repeats — selecting the repeat inside the
    group with a stacked gather.  Heterogeneous stacks with recurring layer
    patterns (the common case: a few distinct mappings tiled across the
    depth) trace G kernels instead of R."""

    def __init__(self, preps: List[PreparedLayer]):
        buckets: Dict[Any, List[int]] = {}
        for r, p in enumerate(preps):
            buckets.setdefault(_stack_key(p), []).append(r)
        order = list(buckets.values())
        self.groups = [_stack_group([preps[r] for r in idxs])
                       for idxs in order]
        self.group_of = np.zeros(len(preps), np.int32)
        self.pos_of = np.zeros(len(preps), np.int32)
        for g, idxs in enumerate(order):
            for pos, r in enumerate(idxs):
                self.group_of[r] = g
                self.pos_of[r] = pos
        self.conv_shape = preps[0].conv_shape
        self.conv_groups = preps[0].plan.groups

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    def execute(self, x, r, conv=None, *, interpret=None, reference=False):
        run = lambda grp, pos: grp.execute(x, pos, conv=conv,
                                           interpret=interpret,
                                           reference=reference)
        if not isinstance(r, jax.core.Tracer):
            ri = int(r)
            return run(self.groups[self.group_of[ri]], int(self.pos_of[ri]))
        # homogeneous stacks never construct _GroupedPrepared (they route
        # through _stack_group), so there are always >= 2 groups here
        pos = jnp.take(jnp.asarray(self.pos_of), r)
        branches = [lambda xx, grp=grp: grp.execute(
            xx, pos, conv=conv, interpret=interpret, reference=reference)
            for grp in self.groups]
        g = jnp.take(jnp.asarray(self.group_of), r)
        return jax.lax.switch(g, branches, x)


class _SwitchPrepared:
    """One ``jax.lax.switch`` branch PER REPEAT — the pre-grouping dispatch,
    kept as the benchmark baseline (``PlannedBackend(stack_mode="switch")``):
    every repeat's kernel is traced once even when repeats share their
    structure."""

    def __init__(self, preps: List[PreparedLayer]):
        # stacked repeats never read the fp32 weights (split reads its bf16
        # cast), so don't keep their (K, N) float copies alive
        self.preps = [dataclasses.replace(p, w_perm=None)
                      if p.plan.kernel in _DROPS_FP_STACK else p
                      for p in preps]
        self.conv_shape = preps[0].conv_shape
        self.conv_groups = preps[0].plan.groups

    def execute(self, x, r, conv=None, *, interpret=None, reference=False):
        def run(prep, xx):
            if conv is not None:
                return execute_conv_layer(prep, xx, conv["stride"],
                                          conv["padding"],
                                          interpret=interpret,
                                          reference=reference)
            return execute_layer(prep, xx, interpret=interpret,
                                 reference=reference)
        if not isinstance(r, jax.core.Tracer):
            return run(self.preps[int(r)], x)
        branches = [lambda xx, p=p: run(p, xx) for p in self.preps]
        return jax.lax.switch(jnp.asarray(r, jnp.int32), branches, x)


_STACKED_TYPES = (_SingleRepeat, _StackedPrepared, _GroupedPrepared,
                  _SwitchPrepared)


def _members(entry):
    """The `PreparedLayer`s (or homogeneous stacks) a bound entry runs."""
    if isinstance(entry, _SingleRepeat):
        yield entry.prep
    elif isinstance(entry, _GroupedPrepared):
        for g in entry.groups:
            yield from _members(g)
    elif isinstance(entry, _SwitchPrepared):
        yield from entry.preps
    else:
        yield entry


# --------------------------------------------------------------------------
# Prepared-buffer accounting
# --------------------------------------------------------------------------

#: PreparedLayer fields that hold device arrays (the bind-time weight
#: memory a plan keeps alive)
_PREP_ARRAY_FIELDS = ("inv", "w_perm", "b", "w_q", "sw", "w_bf16",
                      "w_t_packed", "act_scale", "act_sx")


def _entry_arrays(entry):
    """Every device array a bound entry (plain or stacked) keeps alive."""
    if isinstance(entry, PreparedLayer):
        for f in _PREP_ARRAY_FIELDS:
            a = getattr(entry, f)
            if a is not None:
                yield a
    elif isinstance(entry, _SingleRepeat):
        yield from _entry_arrays(entry.prep)
    elif isinstance(entry, _StackedPrepared):
        for a in (entry._inv, entry._w_perm, entry._w_bf16,
                  entry._w_t_packed, entry._b, entry._w_q, entry._sw,
                  entry._act_scale, entry._act_sx):
            if a is not None:
                yield a
    elif isinstance(entry, _GroupedPrepared):
        for g in entry.groups:
            yield from _entry_arrays(g)
    elif isinstance(entry, _SwitchPrepared):
        for p in entry.preps:
            yield from _entry_arrays(p)


def prepared_nbytes(entries) -> int:
    """Total bytes of the UNIQUE arrays held by ``entries`` — arrays shared
    between entries (the PlanSet dedup) are counted once."""
    seen, total = set(), 0
    for e in entries:
        for a in _entry_arrays(e):
            if id(a) not in seen:
                seen.add(id(a))
                total += int(a.nbytes)
    return total


# --------------------------------------------------------------------------
# Pluggable matmul backend over a bank of plans
# --------------------------------------------------------------------------

def _node_weight_ok(node):
    w = _layer_weight(node)
    return (isinstance(node, dict) and getattr(w, "ndim", 0) in (2, 4)
            and not isinstance(w, jax.ShapeDtypeStruct))


class _BoundPlan:
    """One `ExecutionPlan` variant bound to the owning `PlanSet`'s params:
    resolves layers exactly like the single-plan `PlannedBackend` always
    did (handle plan order, or artifact layer names as params paths) but
    routes every prepare through the owner's shared prep cache, so
    identical (layer plan, weight, domain-bits, block) tuples across
    variants bind to ONE set of prepared arrays."""

    def __init__(self, variant: str, plan: ExecutionPlan, params, handle,
                 owner: "PlanSet"):
        self.variant = variant
        self.plan = plan
        domain_bits = [int(d["weight_bits"]) for d in plan.domains]
        dsig = tuple(domain_bits)
        if handle is not None:
            dicts = handle.layers(params)
            if len(dicts) != len(plan.layers):
                raise ExecutionError(
                    f"handle resolves {len(dicts)} managed layers but the "
                    f"plan has {len(plan.layers)}")
            # node identity for the shared prep cache: handle position
            resolved = [(lp, node, ("h", i))
                        for i, (lp, node) in enumerate(zip(plan.layers,
                                                           dicts))]
        else:
            resolved = [(lp, _walk_path(params, lp.name), ("p", lp.name))
                        for lp in plan.layers]
        self.by_name: Dict[str, Any] = {}
        self.bound: List[str] = []
        self.unbound: List[str] = []
        stacked: Dict[str, List[Tuple[int, LayerPlan, Any, Any]]] = {}
        for lp, node, nkey in resolved:
            base, _, rep = lp.name.partition("@")
            if rep:
                stacked.setdefault(base, []).append((int(rep), lp, node,
                                                     nkey))
                continue
            if not _node_weight_ok(node):
                self.unbound.append(lp.name)
                continue
            key = ("layer", nkey, owner._plan_sig(lp), dsig,
                   int(plan.block_n))
            prep = owner._memo(
                key, variant, lp.name,
                lambda: prepare_layer(lp, _layer_weight(node),
                                      b=node.get("b"),
                                      domain_bits=domain_bits,
                                      block_n=plan.block_n))
            self.by_name[lp.name] = prep
            self.bound.append(lp.name)
        for base, entries in sorted(stacked.items()):
            entries.sort(key=lambda e: e[0])
            reps = [r for r, _, _, _ in entries]
            if reps != list(range(len(reps))):
                raise ExecutionError(
                    f"{base}: stacked plan repeats {reps} are not the "
                    f"contiguous range 0..{len(reps) - 1}")
            if handle is None:
                # a plan covering FEWER repeats than the model's stack would
                # index out of range inside the scan (NaN fill) — reject at
                # bind time instead
                stack_w = _layer_weight(_walk_path(params, base))
                if getattr(stack_w, "ndim", 0) in (3, 5) and \
                        int(stack_w.shape[0]) != len(reps):
                    raise ExecutionError(
                        f"{base}: plan covers {len(reps)} repeats but the "
                        f"stacked weight carries {int(stack_w.shape[0])} — "
                        f"the artifact does not match this model's layer "
                        f"stack")
            if not all(_node_weight_ok(node) for _, _, node, _ in entries):
                self.unbound.extend(lp.name for _, lp, _, _ in entries)
                continue
            # stack entries dedup at WHOLE-STACK granularity: the stacked
            # containers jnp.stack fresh arrays, so per-repeat sharing
            # cannot alias device buffers — one divergent repeat forks the
            # whole stack for that base
            key = ("stack", entries[0][3][0], base,
                   tuple(owner._plan_sig(lp) for _, lp, _, _ in entries),
                   dsig, int(plan.block_n), owner.stack_mode)
            entry = owner._memo(
                key, variant, base,
                lambda: owner._stack_entry(
                    [prepare_layer(lp, _layer_weight(node),
                                   b=node.get("b"),
                                   domain_bits=domain_bits,
                                   block_n=plan.block_n)
                     for _, lp, node, _ in entries]))
            self.by_name[base] = entry
            self.bound.extend(lp.name for _, lp, _, _ in entries)


class PlanSet:
    """A precision bank: N `ExecutionPlan` variants of the SAME weights
    bound against one params pytree, serving the NAME-KEYED `repro.models`
    matmul-backend protocol (``backend(name, p, x, conv=...)``).

    The active variant is selected by the trace-static key published via
    ``repro.models._backend.plan_variant`` (threaded through the
    transformer/façade ``variant=`` kwargs); calls outside any variant
    context execute ``default``.  Because the key is static, each variant
    traces its own kernels — jitted callers must make it a static argument
    (``static_argnames=("variant",)``).

    Prepared weight buffers DEDUPLICATE across variants: wherever a
    layer's (layer plan, resolved weight, domain bit-widths, block size)
    tuple coincides — same kernel, same domain boundary, same scales — the
    variants share one set of prepared arrays (per plain layer; per whole
    stack for scan-stacked ``base@r`` entries, whose containers stack
    fresh arrays).  ``prepared_bytes()`` / ``memory_report()`` measure the
    dedup: a two-variant bank stays strictly below two independent binds
    whenever any layer coincides.

    Layer resolution, scan-stack grouping (``stack_mode``), coverage
    bookkeeping and the fail-loud `ExecutionError` semantics are exactly
    the single-plan `PlannedBackend`'s — which is now the one-variant
    special case of this class.
    """

    def __init__(self, variants: Dict[str, ExecutionPlan], params,
                 handle=None, *, default: str | None = None,
                 interpret=None, reference: bool = False,
                 stack_mode: str = "grouped"):
        if stack_mode not in ("grouped", "switch"):
            raise ValueError(f"stack_mode must be 'grouped' or 'switch', "
                             f"got {stack_mode!r}")
        if not variants:
            raise ValueError("PlanSet needs at least one plan variant")
        for v in variants:
            if not isinstance(v, str) or not v:
                raise ValueError(f"variant names must be non-empty strings, "
                                 f"got {v!r}")
        self.interpret = interpret
        self.reference = reference
        self.stack_mode = stack_mode
        self.variant_names: Tuple[str, ...] = tuple(variants)
        self.default = self.variant_names[0] if default is None else default
        if self.default not in variants:
            raise ValueError(f"default variant {self.default!r} is not one "
                             f"of {list(self.variant_names)}")
        self.runtime_declines: Dict[str, str] = {}
        self._prep_cache: Dict[Any, Any] = {}
        self._share: Dict[Any, List[Tuple[str, str]]] = {}
        self._sig_cache: Dict[int, str] = {}
        self._variants: Dict[str, _BoundPlan] = {}
        for vname, plan in variants.items():
            self._variants[vname] = _BoundPlan(vname, plan, params, handle,
                                               self)

    # ---- shared prepare cache -------------------------------------------

    def _plan_sig(self, lp: LayerPlan) -> str:
        sig = self._sig_cache.get(id(lp))
        if sig is None:
            sig = json.dumps(lp.to_dict(), sort_keys=True)
            self._sig_cache[id(lp)] = sig
        return sig

    def _memo(self, key, variant: str, display_name: str, build):
        if key not in self._prep_cache:
            self._prep_cache[key] = build()
        self._share.setdefault(key, []).append((variant, display_name))
        return self._prep_cache[key]

    def _stack_entry(self, preps: List[PreparedLayer]):
        if self.stack_mode == "switch":
            return _SwitchPrepared(preps)
        if len({_stack_key(p) for p in preps}) == 1:
            return _stack_group(preps)
        return _GroupedPrepared(preps)

    # ---- backend protocol -----------------------------------------------

    def _resolve_variant(self) -> _BoundPlan:
        v = _backend.current_plan_variant()
        if v is None:
            v = self.default
        bp = self._variants.get(v)
        if bp is None:
            raise ExecutionError(
                f"unknown plan variant {v!r}: this PlanSet binds "
                f"{list(self.variant_names)}")
        return bp

    def __call__(self, name, p, x, *, conv=None):
        """Matmul-backend hook: resolve ``name`` against the ACTIVE variant
        (``_backend.current_plan_variant()`` or ``default``); returns the
        planned output (bias applied) or None to decline (unknown /
        unnamed layer, or an unsupported conv).  ``conv`` carries the call
        site's ``{"stride", "padding", "groups"}`` for conv layers."""
        if name is None:
            return None
        bp = self._resolve_variant()
        entry = bp.by_name.get(name)
        if entry is None:
            return None
        conv_shape = entry.conv_shape
        if conv is not None and conv_shape is None:
            raise ExecutionError(
                f"{name}: conv call site but the plan bound a 2-D dense "
                f"weight — the artifact does not match this model")
        if conv is None and conv_shape is not None:
            raise ExecutionError(
                f"{name}: dense call site but the plan bound a conv weight "
                f"— the artifact does not match this model")
        if conv is not None:
            cg = int(conv.get("groups", 1))
            pg = entry.conv_groups
            if cg != pg:
                if pg == 1:
                    # plan lowered without a groups record (pre-groups
                    # artifact): loud trace-time decline, surfaced via
                    # runtime_declines — re-emit the artifact to get the
                    # block-diagonal grouped lowering
                    self.runtime_declines[self._decline_key(bp, name)] = (
                        f"grouped conv (groups={cg}) but the plan was "
                        f"lowered without groups; executed on the default "
                        f"path")
                    return None
                raise ExecutionError(
                    f"{name}: call site has groups={cg} but the plan was "
                    f"lowered with groups={pg} — the artifact does not "
                    f"match this model")
        if isinstance(entry, _STACKED_TYPES):
            r = _backend.current_scan_index()
            if r is None:
                raise ExecutionError(
                    f"{name}: scan-stacked plan executed outside a "
                    f"scan_slot context (no repeat index to select the "
                    f"prepared kernels)")
            return entry.execute(x, r, conv=conv, interpret=self.interpret,
                                 reference=self.reference)
        if conv is not None:
            return execute_conv_layer(entry, x, conv["stride"],
                                      conv["padding"],
                                      interpret=self.interpret,
                                      reference=self.reference)
        return execute_layer(entry, x, interpret=self.interpret,
                             reference=self.reference)

    def _decline_key(self, bp: _BoundPlan, name: str) -> str:
        # single-variant banks keep the bare-name key (the PlannedBackend
        # contract); multi-variant banks qualify it so variants don't alias
        return name if len(self._variants) == 1 else f"{bp.variant}:{name}"

    # ---- coverage -------------------------------------------------------

    def variant(self, name: str) -> _BoundPlan:
        """The bound state of one variant (plan / bound / unbound)."""
        return self._variants[name]

    @property
    def fully_covered(self) -> bool:
        """True when EVERY variant bound every planned layer."""
        return all(not bp.unbound for bp in self._variants.values())

    def coverage(self) -> str:
        parts = []
        for v, bp in self._variants.items():
            s = (f"{len(bp.bound)}/{len(bp.plan.layers)} planned layers "
                 f"bound to weights, {len(bp.unbound)} unbound")
            parts.append(s if len(self._variants) == 1 else f"{v}: {s}")
        return "; ".join(parts)

    def coverage_diff(self) -> Dict[str, List[str]]:
        """Per-variant UNBOUND layer names (only variants with gaps): the
        actionable diff when one variant binds fewer layers than another —
        names, not counts."""
        return {v: list(bp.unbound) for v, bp in self._variants.items()
                if bp.unbound}

    def kernel_blocks(self) -> Dict[str, Dict[int, Tuple[int, int, int]]]:
        """Layer name -> {rows: (bm, bn, bk)} of every ``quant_matmul``
        call traced so far, per row count; a stacked layer reads under the
        name of its first repeat in each group of repeats."""
        out: Dict[str, Dict[int, Tuple[int, int, int]]] = {}
        for bp in self._variants.values():
            for entry in bp.by_name.values():
                for member in _members(entry):
                    if member.chosen:
                        out.setdefault(member.plan.name, {}).update(
                            member.chosen)
        return out

    # ---- memory accounting ----------------------------------------------

    def prepared_bytes(self, variant: str | None = None) -> int:
        """Bytes of unique prepared device arrays held by ``variant`` (or
        by the whole bank when None) — buffers shared across variants count
        once, which is the point of the bank."""
        if variant is None:
            entries = [e for bp in self._variants.values()
                       for e in bp.by_name.values()]
        else:
            entries = list(self._variants[variant].by_name.values())
        return prepared_nbytes(entries)

    def shared_layers(self) -> Dict[str, Tuple[str, ...]]:
        """Display name -> variants whose prepared buffers coincide (>= 2
        variants sharing one prep-cache entry)."""
        out: Dict[str, Tuple[str, ...]] = {}
        for users in self._share.values():
            vs = tuple(dict.fromkeys(v for v, _ in users))
            if len(vs) > 1:
                out[users[0][1]] = vs
        return out

    def memory_report(self) -> Dict[str, Any]:
        per_variant = {v: self.prepared_bytes(v) for v in self.variant_names}
        total = self.prepared_bytes()
        return {
            "variants": per_variant,
            "prepared_bytes": total,
            "sum_variant_bytes": sum(per_variant.values()),
            "dedup_saved_bytes": sum(per_variant.values()) - total,
            "shared_layers": self.shared_layers(),
        }


class PlannedBackend(PlanSet):
    """A one-plan `PlanSet` — the original single-mapping binding, kept as
    the common case and the backward-compatible API: ``plan`` / ``bound`` /
    ``unbound`` / ``coverage()`` address the single variant directly, and
    ``runtime_declines`` keys stay bare layer names."""

    def __init__(self, plan: ExecutionPlan, params, handle=None, *,
                 interpret=None, reference: bool = False,
                 stack_mode: str = "grouped"):
        super().__init__({"default": plan}, params, handle=handle,
                         interpret=interpret, reference=reference,
                         stack_mode=stack_mode)

    @property
    def plan(self) -> ExecutionPlan:
        return self._variants["default"].plan

    @property
    def bound(self) -> List[str]:
        return self._variants["default"].bound

    @property
    def unbound(self) -> List[str]:
        return self._variants["default"].unbound

    @property
    def _by_name(self) -> Dict[str, Any]:
        return self._variants["default"].by_name
