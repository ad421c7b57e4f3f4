"""Plain float32 reference of Zamba2 (arXiv:2411.15242; the Zamba2-7B
equations of transformers' ``modeling_zamba2``): grouped Mamba2 layers and
shared attention blocks used in turn.

It imports nothing of the program.  It reads the benchmark's own weights
(the same pytree the program serves, by leaf name) and the configuration
file's ``sizes``, and computes the whole forward pass of one sequence in
float32 at ``Precision.HIGHEST``: no kernels, no cache, no batching.

Layers, as the configuration's ``pattern`` names them (``n_layers`` of
them, the pattern repeated and its first kinds once more at the end); ``e``
is the token embeddings, and the u-th hybrid layer uses shared block
u mod ``mem_blocks``:

  ``mamba``   x += Mamba2(RMSNorm(x))
  ``hybrid``  h = RMSNorm(concat(x, e))                      (2 d_model)
              a = RMSNorm(Attn(h))                           (d_model)
              g, u = split(a Wgate_up + (a A_u) B_u)         (LoRA of use u)
              y = (GELU(g) * u) Wdown Wlinear_u
              x += Mamba2(RMSNorm(x + y))

Attention is multi-head (``n_kv_heads`` of ``n_heads``) with rotary
embeddings over all ``head_dim`` dims (both halves, ``rope_theta``),
causal, the softmax scaled by (head_dim / 2) ** -0.5.  GELU is the exact
(erf) form.  Mamba2 has ``mamba_ngroups`` groups of B and C, head h reading
group h // (heads / groups): in_proj to (z, x, B, C, dt), a depthwise causal
convolution of ``mamba_d_conv`` taps with bias over (x, B, C), SiLU, per-head
decay exp(softplus(dt + dt_bias) * -exp(A_log)), the recurrence run step by
step, the skip ``D``, then y * SiLU(z) normalised over each group's
d_inner / groups channels, and out_proj.  RMSNorm has eps 1e-5.  Logits
are RMSNorm(x) Wemb^T (tied embeddings).

Departures, each from transformers' torch path, none from the published
kernels' path: dt is not clamped below at ``time_step_min`` (the mamba_ssm
kernels take ``time_step_limit``, None in the published configuration, so
no limit); attention adapters and ``use_long_context`` are not computed
(both off in Zamba2-7B; the program refuses them).

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


def _rms(x, scale, groups=1):
    shape = x.shape
    x = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return x.reshape(shape) * scale.astype(F32)


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
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * \
            (hd / 2) ** -0.5
        qi = s0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(L)[None, :] <= qi, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(L, H * hd)
    return _proj(o, pa["wo"]["w"], q)


def _mamba(pm, h, sz, q):
    L, D = h.shape
    di = sz["mamba_expand"] * D
    N, P, G = sz["ssm_state"], sz["mamba_headdim"], sz["mamba_ngroups"]
    H, K = di // P, sz["mamba_d_conv"]
    zxbcdt = _proj(h, pm["in_proj"]["w"], q)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * G * N]
    dt_raw = zxbcdt[:, 2 * di + 2 * G * N:]
    w = pm["conv_w"].astype(F32)
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(xp[i:i + L] * w[i] for i in range(K))
                      + pm["conv_b"].astype(F32))
    xs = xbc[:, :di].reshape(L, H, P)
    per_head = lambda t: jnp.repeat(t.reshape(L, G, N), H // G, axis=1)
    Bm = per_head(xbc[:, di:di + G * N])                   # (L, H, N)
    Cm = per_head(xbc[:, di + G * N:])
    dt = jax.nn.softplus(dt_raw + pm["dt_bias"].astype(F32))
    A = -jnp.exp(pm["A_log"].astype(F32))

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, Bm, Cm, dt))
    y = y + pm["D"].astype(F32)[:, None] * xs
    y = y.reshape(L, di) * jax.nn.silu(z)
    y = _rms(y, pm["norm"]["scale"], G)
    return _proj(y, pm["out_proj"]["w"], q)


def _shared_block(sb, lp, h, sz, q):
    """Shared block ``sb`` over h = concat(x, e), with use ``lp``'s LoRA."""
    a = _attention(sb["attn"], _rms(h, sb["norm1"]["scale"]), sz, q)
    a = _rms(a, sb["norm2"]["scale"])
    gu = _proj(a, sb["ffn"]["gate_up"]["w"], q) + \
        _proj(_proj(a, lp["lora_a"]["w"], q), lp["lora_b"]["w"], q)
    gate, up = jnp.split(gu, 2, axis=-1)
    return _proj(jax.nn.gelu(gate, approximate=False) * up,
                 sb["ffn"]["down"]["w"], q)


@functools.partial(jax.jit, static_argnames=("kind", "sz", "q"))
def _layer(lp, sb, x, e, *, kind, sz, q):
    sz = dict(sz)
    h = x
    if kind == "hybrid":
        y = _shared_block(sb, lp, jnp.concatenate([x, e], -1), sz, q)
        h = x + _proj(y, lp["linear"]["w"], q)
    return x + _mamba(lp["mamba"], _rms(h, lp["norm1"]["scale"]), sz, q)


@functools.partial(jax.jit, static_argnames=("q",))
def _head(final_scale, w, x, rows, *, q):
    return _proj(_rms(x[rows], final_scale), w, q)


def _kinds(sizes: dict):
    pattern = list(sizes["pattern"])
    return pattern * (sizes["n_layers"] // len(pattern)) + \
        pattern[:sizes["n_layers"] % len(pattern)]


def _layers(params, sz):
    """(kind, layer params, shared block or None) in execution order."""
    pattern = list(sz["pattern"])
    reps = sz["n_layers"] // len(pattern)
    out = []
    for r in range(reps):
        for i, kind in enumerate(pattern):
            out.append((kind, jax.tree.map(lambda a: a[r],
                                           params["units"][i])))
    for i, lp in enumerate(params.get("rem") or []):
        out.append((pattern[i % len(pattern)], lp))
    uses = 0
    for i, (kind, lp) in enumerate(out):
        sb = None
        if kind == "hybrid":
            sb = params["shared"][uses % sz["mem_blocks"]]
            uses += 1
        out[i] = (kind, lp, sb)
    return out


def logits(params, sizes: dict, tokens, rows, *, bits=None,
           act_log_scale=None):
    """float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens`` (later positions do not change earlier ones)."""
    sz = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                      for k, v in sizes.items()))
    q = None if bits is None else (int(bits), float(np.exp(act_log_scale)))
    e = params["emb"][jnp.asarray(tokens)].astype(F32)
    x = e
    for kind, lp, sb in _layers(params, sizes):
        x = _layer(lp, sb, x, e, kind=kind, sz=sz, q=q)
    return _head(params["final_norm"]["scale"], params["emb"].T, x,
                 jnp.asarray(rows), q=q)


def _layer_shapes(kind: str, sizes: dict, lead: tuple = ()) -> dict:
    """Shapes of the weights one layer of ``kind`` holds, each with the
    leading axes ``lead`` (the repeat axis of a scan-stacked layer)."""
    d = sizes["d_model"]
    di = sizes["mamba_expand"] * d
    N, K, G = sizes["ssm_state"], sizes["mamba_d_conv"], sizes["mamba_ngroups"]
    H = di // sizes["mamba_headdim"]
    dense = lambda i, o: {"w": (*lead, i, o)}
    out = {"norm1": {"scale": (*lead, d)}, "mamba": {
        "in_proj": dense(d, 2 * di + 2 * G * N + H),
        "conv_w": (*lead, K, di + 2 * G * N),
        "conv_b": (*lead, di + 2 * G * N),
        "dt_bias": (*lead, H), "A_log": (*lead, H), "D": (*lead, H),
        "norm": {"scale": (*lead, di)}, "out_proj": dense(di, d)}}
    if kind == "hybrid":
        r, f = sizes["adapter_rank"], sizes["d_ff"]
        out.update(lora_a=dense(d, r), lora_b=dense(r, 2 * f),
                   linear=dense(d, d))
    elif kind != "mamba":
        raise ValueError(f"layer kind {kind!r}: this reference computes "
                         f"mamba and hybrid")
    return out


def _shared_shapes(sizes: dict) -> dict:
    d, f = sizes["d_model"], sizes["d_ff"]
    H, KVH, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    return {"norm1": {"scale": (2 * d,)}, "norm2": {"scale": (d,)},
            "attn": {"wq": {"w": (2 * d, H * hd)},
                     "wk": {"w": (2 * d, KVH * hd)},
                     "wv": {"w": (2 * d, KVH * hd)},
                     "wo": {"w": (H * hd, d)}},
            "ffn": {"gate_up": {"w": (d, 2 * f)}, "down": {"w": (f, d)}}}


def param_shapes(sizes: dict) -> dict:
    """Shapes of every weight `logits` reads, in the program's tree: the
    ``pattern``'s layers under ``units``, each stacked over the pattern's
    repeats, the remainder's under ``rem``, the ``mem_blocks`` shared
    blocks under ``shared``.  Leaves are tuples of ints."""
    if not sizes["tie_embeddings"]:
        raise ValueError("this reference computes tied embeddings only")
    d, V = sizes["d_model"], sizes["vocab"]
    pattern = list(sizes["pattern"])
    reps, rem = divmod(sizes["n_layers"], len(pattern))
    out = {"emb": (V, d), "final_norm": {"scale": (d,)},
           "units": tuple(_layer_shapes(k, sizes, (reps,)) for k in pattern)}
    if rem:
        out["rem"] = [_layer_shapes(k, sizes) for k in pattern[:rem]]
    if "hybrid" in pattern:
        out["shared"] = [_shared_shapes(sizes)
                         for _ in range(sizes["mem_blocks"])]
    return out


def layer_uses(sizes: dict, name: str) -> int:
    """How many times per token the planned layer ``name`` runs: a shared
    block's projections once per hybrid layer that uses the block."""
    if name.startswith("shared/"):
        b = int(name.split("/")[1])
        uses = _kinds(sizes).count("hybrid")
        return len(range(b, uses, sizes["mem_blocks"]))
    return 1


def mixer_flops(sizes: dict, position: int) -> float:
    """Floating-point operations per token outside the projections, at
    context ``position``: QK^T and PV over position + 1 keys per hybrid
    layer; every layer's Mamba2 state update and readout, and its
    convolution."""
    di = sizes["mamba_expand"] * sizes["d_model"]
    N, G = sizes["ssm_state"], sizes["mamba_ngroups"]
    kinds = _kinds(sizes)
    attn = 4.0 * sizes["n_heads"] * sizes["head_dim"] * (position + 1)
    mamba = 6.0 * di * N + 2.0 * sizes["mamba_d_conv"] * (di + 2 * G * N)
    return len(kinds) * mamba + kinds.count("hybrid") * attn


def kv_bytes_per_token(sizes: dict) -> int:
    """Bytes of K and V that one attention layer keeps for one token in
    the served cache (int8 codes)."""
    return 2 * sizes["n_kv_heads"] * sizes["head_dim"]


def state_bytes_per_slot(sizes: dict) -> int:
    """Bytes of recurrent state one slot holds over all layers: each
    Mamba2's state (heads x headdim x d_state, float32) and convolution
    window (d_conv - 1 rows of x, B and C, bfloat16)."""
    di = sizes["mamba_expand"] * sizes["d_model"]
    N, G = sizes["ssm_state"], sizes["mamba_ngroups"]
    ssm = di * N * 4
    conv = (sizes["mamba_d_conv"] - 1) * (di + 2 * G * N) * 2
    return sizes["n_layers"] * (ssm + conv)
