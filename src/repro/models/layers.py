"""Shared transformer building blocks (pure JAX, pytree params).

Weight layout: every projection is (in_features, out_features) so the ODiMO
output-channel convention (out axis last) holds framework-wide.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import _backend


def init_dense(key, d_in, d_out, dtype=jnp.bfloat16, scale=None, bias=False):
    s = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * s).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, name=None):
    """``name`` is the layer's pytree path, forwarded to the pluggable
    matmul backend (`repro.models._backend`); None skips backend dispatch."""
    be = _backend.current()
    if be is not None:
        y = be(name, p, x)
        if y is not None:
            return y  # planned kernel output, bias applied by the backend
    if "w_q" in p:
        # int8-domain weights: HBM stream is int8; dequant fuses into the
        # matmul operand load (per-output-channel scale)
        w = p["w_q"].astype(x.dtype) * p["w_s"].astype(x.dtype)[..., None, :]
        y = x @ w
    else:
        y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(d, dtype=jnp.bfloat16, kind="rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm(p, x, kind="rmsnorm", eps=1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rope(x, positions, theta=10000.0, rotary_dim=None):
    """Rotary embedding. x: (..., S, H, D); positions: (..., S)."""
    d = rotary_dim or x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]   # (..., S, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:d]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    out = jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)
    if d < x.shape[-1]:
        out = jnp.concatenate([out, x[..., d:]], axis=-1)
    return out


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "relu2":  # nemotron squared-ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    if name == "gelu_exact":  # erf form (torch's nn.GELU default)
        return lambda x: jax.nn.gelu(x, approximate=False)
    raise ValueError(name)


def init_ffn(key, d_model, d_ff, gated: bool, dtype=jnp.bfloat16, bias=False):
    ks = jax.random.split(key, 3)
    p = {"up": init_dense(ks[0], d_model, d_ff, dtype, bias=bias),
         "down": init_dense(ks[1], d_ff, d_model, dtype,
                            scale=d_ff ** -0.5, bias=bias)}
    if gated:
        p["gate"] = init_dense(ks[2], d_model, d_ff, dtype, bias=bias)
    return p


def ffn(p, x, act_name="silu", name=None):
    a = act_fn(act_name)
    j = _backend.join
    if "gate" in p:
        h = a(dense(p["gate"], x, j(name, "gate"))) * \
            dense(p["up"], x, j(name, "up"))
    else:
        h = a(dense(p["up"], x, j(name, "up")))
    return dense(p["down"], h, j(name, "down"))
