"""Plain float32 reference of the served language models.

It imports nothing of the program.  It reads the benchmark's own weights
(the same pytree the program serves, by leaf name) and the configuration
file's ``sizes``, and computes the whole forward pass of one sequence in
float32 at ``Precision.HIGHEST``: no kernels, no cache, no batching.

Block kinds, as the configuration's ``pattern`` names them, repeated
``n_layers // len(pattern)`` times with the first ``n_layers % len(pattern)``
kinds once more at the end:

  ``attn``         x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))   (llama)
  ``mamba``        x += Mamba2(RMSNorm(x))
  ``shared_attn``  as ``attn``, with one shared copy of the attention and
                   FFN weights and a per-use input norm         (zamba2)

Attention is grouped-query with rotary embeddings on both halves of each
head (``rope_theta``), causal, softmax scaled by head_dim^-0.5.  The FFN is
act(x Wgate) * (x Wup) Wdown with ``act`` ``silu`` or ``gelu`` (tanh form).
Mamba2 is the selective state space of arXiv:2405.21060 with one group
(B and C shared by all heads): in_proj to (z, x, B, C, dt), a depthwise
causal convolution of ``mamba_d_conv`` taps over (x, B, C), SiLU, per-head
decay exp(softplus(dt + dt_bias) * -exp(A_log)), the recurrence run step by
step, the skip ``D``, a SiLU(z) gate, an RMSNorm and out_proj.  RMSNorm has
eps 1e-5.  Logits are RMSNorm(x) Whead, or RMSNorm(x) Wemb^T where the
embeddings are tied (no ``head`` leaf).

`param_shapes` declares the shape of every weight it reads, for the
harness's check at set-up.  It computes Mamba2 with one group only, and
refuses ``mamba_ngroups`` other than 1.

``bits`` turns the reference into the precision control: every projection's
weight is rounded to that many bits on one max-abs scale per matrix, and its
input to as many bits on the static scale exp(act_log_scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
EPS = 1e-5
Q_BLOCK = 512          # query rows per attention block


def _rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return x * scale.astype(F32)


def _fake_quant(x, scale, bits):
    levels = 2 ** (bits - 1) - 1
    return jnp.round(jnp.clip(x / scale, -1.0, 1.0) * levels) * scale / levels


def _proj(x, w, q):
    """x @ w in float32; under the control, w and x rounded as stated."""
    w = w.astype(F32)
    if q is not None:
        bits, act_scale = q
        w = _fake_quant(w, jnp.max(jnp.abs(w)), bits)
        x = _fake_quant(x, act_scale, bits)
    return jnp.matmul(x, w, precision=HI)


def _act(name):
    return {"silu": jax.nn.silu,
            "gelu": functools.partial(jax.nn.gelu, approximate=True)}[name]


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(pa, h, sz, q):
    L = h.shape[0]
    H, KVH, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    G = H // KVH
    pos = jnp.arange(L)
    qh = _rope(_proj(h, pa["wq"]["w"], q).reshape(L, H, hd), pos,
               sz["rope_theta"]).reshape(L, KVH, G, hd)
    k = _rope(_proj(h, pa["wk"]["w"], q).reshape(L, KVH, hd), pos,
              sz["rope_theta"])
    v = _proj(h, pa["wv"]["w"], q).reshape(L, KVH, hd)
    outs = []
    for s0 in range(0, L, Q_BLOCK):
        qb = qh[s0:s0 + Q_BLOCK]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * hd ** -0.5
        qi = s0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(L)[None, :] <= qi, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(L, H * hd)
    return _proj(o, pa["wo"]["w"], q)


def _ffn(pf, h, sz, q):
    a = _act(sz["act"])(_proj(h, pf["gate"]["w"], q)) * \
        _proj(h, pf["up"]["w"], q)
    return _proj(a, pf["down"]["w"], q)


def _mamba(pm, h, sz, q):
    L, D = h.shape
    di = sz["mamba_expand"] * D
    N, P = sz["ssm_state"], sz["mamba_headdim"]
    H, K = di // P, sz["mamba_d_conv"]
    zxbcdt = _proj(h, pm["in_proj"]["w"], q)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * N]
    dt_raw = zxbcdt[:, 2 * di + 2 * N:]
    w = pm["conv_w"].astype(F32)
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(xp[i:i + L] * w[i] for i in range(K))
                      + pm["conv_b"].astype(F32))
    xs = xbc[:, :di].reshape(L, H, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt_raw + pm["dt_bias"].astype(F32))
    A = -jnp.exp(pm["A_log"].astype(F32))

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, Bm, Cm, dt))
    y = y + pm["D"].astype(F32)[:, None] * xs
    y = y.reshape(L, di) * jax.nn.silu(z)
    y = _rms(y, pm["norm"]["scale"])
    return _proj(y, pm["out_proj"]["w"], q)


@functools.partial(jax.jit, static_argnames=("kind", "sz", "q"))
def _block(blk, shared, x, *, kind, sz, q):
    sz = dict(sz)
    if kind == "mamba":
        return x + _mamba(blk["mamba"], _rms(x, blk["norm1"]["scale"]), sz, q)
    body = shared if kind == "shared_attn" else blk
    x = x + _attention(body["attn"], _rms(x, blk["norm1"]["scale"]), sz, q)
    return x + _ffn(body["ffn"], _rms(x, body["norm2"]["scale"]), sz, q)


@functools.partial(jax.jit, static_argnames=("q",))
def _head(final_scale, w, x, rows, *, q):
    return _proj(_rms(x[rows], final_scale), w, q)


def _blocks(params, sz):
    """(kind, block params) in execution order."""
    pattern = list(sz["pattern"])
    reps = sz["n_layers"] // len(pattern)
    out = []
    for r in range(reps):
        for i, kind in enumerate(pattern):
            out.append((kind, jax.tree.map(lambda a: a[r],
                                           params["units"][i])))
    for i, blk in enumerate(params.get("rem") or []):
        out.append((pattern[i % len(pattern)], blk))
    return out


def logits(params, sizes: dict, tokens, rows, *, bits=None,
           act_log_scale=None):
    """float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens`` (later positions do not change earlier ones)."""
    sz = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                      for k, v in sizes.items()))
    q = None if bits is None else (int(bits), float(np.exp(act_log_scale)))
    x = params["emb"][jnp.asarray(tokens)].astype(F32)
    shared = params.get("shared")
    for kind, blk in _blocks(params, sizes):
        x = _block(blk, shared, x, kind=kind, sz=sz, q=q)
    head = params["head"]["w"] if "head" in params else params["emb"].T
    return _head(params["final_norm"]["scale"], head, x,
                 jnp.asarray(rows), q=q)


def _block_shapes(kind: str, sizes: dict, lead: tuple = ()) -> dict:
    """Shapes of the weights one block of ``kind`` reads, each with the
    leading axes ``lead`` (the repeat axis of a scan-stacked block)."""
    d = sizes["d_model"]
    norm = lambda n: {"scale": (*lead, n)}
    dense = lambda i, o: {"w": (*lead, i, o)}
    if kind == "mamba":
        di = sizes["mamba_expand"] * d
        N, K = sizes["ssm_state"], sizes["mamba_d_conv"]
        H = di // sizes["mamba_headdim"]
        return {"norm1": norm(d), "mamba": {
            "in_proj": dense(d, 2 * di + 2 * N + H),
            "conv_w": (*lead, K, di + 2 * N), "conv_b": (*lead, di + 2 * N),
            "dt_bias": (*lead, H), "A_log": (*lead, H), "D": (*lead, H),
            "norm": norm(di), "out_proj": dense(di, d)}}
    if kind == "shared_attn":
        return {"norm1": norm(d)}
    if kind == "attn":
        H, KVH, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
        f = sizes["d_ff"]
        return {"norm1": norm(d), "norm2": norm(d),
                "attn": {"wq": dense(d, H * hd), "wk": dense(d, KVH * hd),
                         "wv": dense(d, KVH * hd), "wo": dense(H * hd, d)},
                "ffn": {"gate": dense(d, f), "up": dense(d, f),
                        "down": dense(f, d)}}
    raise ValueError(f"block kind {kind!r}: this reference computes "
                     f"mamba, attn and shared_attn")


def param_shapes(sizes: dict) -> dict:
    """Shapes of every weight `logits` reads, in the program's tree: the
    ``pattern``'s blocks under ``units``, each stacked over the pattern's
    repeats, the remainder's under ``rem``, the shared block's attention
    and FFN under ``shared``.  Leaves are tuples of ints."""
    if sizes.get("mamba_ngroups", 1) != 1:
        raise ValueError(f"mamba_ngroups {sizes['mamba_ngroups']}: this "
                         f"reference computes Mamba2 with one group")
    d, V = sizes["d_model"], sizes["vocab"]
    pattern = list(sizes["pattern"])
    reps, rem = divmod(sizes["n_layers"], len(pattern))
    out = {"emb": (V, d), "final_norm": {"scale": (d,)},
           "units": tuple(_block_shapes(k, sizes, (reps,)) for k in pattern)}
    if not sizes["tie_embeddings"]:
        out["head"] = {"w": (d, V)}
    if rem:
        out["rem"] = [_block_shapes(k, sizes) for k in pattern[:rem]]
    if "shared_attn" in pattern:
        blk = _block_shapes("attn", sizes)
        out["shared"] = {k: blk[k] for k in ("attn", "ffn", "norm2")}
    return out


def _kinds(sizes: dict):
    pattern = list(sizes["pattern"])
    return pattern * (sizes["n_layers"] // len(pattern)) + \
        pattern[:sizes["n_layers"] % len(pattern)]


def layer_uses(sizes: dict, name: str) -> int:
    """How many times per token the planned layer ``name`` runs: the
    shared block's projections once per ``shared_attn`` use."""
    if name.startswith("shared/"):
        return _kinds(sizes).count("shared_attn")
    return 1


def mixer_flops(sizes: dict, position: int) -> float:
    """Floating-point operations per token outside the projections, at
    context ``position``: QK^T and PV over position + 1 keys per attention
    use; the Mamba2 state update and readout, and its convolution."""
    total = 0.0
    for kind in _kinds(sizes):
        if kind in ("attn", "shared_attn"):
            total += 4.0 * sizes["n_heads"] * sizes["head_dim"] * (position + 1)
        elif kind == "mamba":
            di = sizes["mamba_expand"] * sizes["d_model"]
            N = sizes["ssm_state"]
            total += 6.0 * di * N + \
                2.0 * sizes["mamba_d_conv"] * (di + 2 * N)
    return total
