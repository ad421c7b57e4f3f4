"""State-space / recurrent blocks: Mamba2 (zamba2) and xLSTM (mLSTM, sLSTM).

All recurrences are O(T) scans with O(1) per-token state, which is what makes
these archs eligible for the long_500k decode shape (DESIGN.md §4).

State conventions (decode caches):
  mamba2 : {"ssm": (B, H, hd, N), "conv": (B, K-1, conv_dim)};
           conv_dim = d_inner + 2 * ngroups * N
  mlstm  : {"C": (B, H, hd, hd), "n": (B, H, hd), "m": (B, H)}
  slstm  : {"c","n","h": (B, H, hd), "m": (B, H)}
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models._backend import join as _j


# ===================================================================== Mamba2

@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    d_model: int
    d_state: int = 64
    expand: int = 2
    head_dim: int = 64
    conv_kernel: int = 4
    ngroups: int = 1       # groups of B and C; head h reads h // (H / G)

    @property
    def d_inner(self):
        return self.expand * self.d_model

    @property
    def n_heads(self):
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.ngroups * self.d_state


def init_mamba2(key, cfg: Mamba2Config, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.n_heads
    proj_out = 2 * di + 2 * cfg.ngroups * N + H  # z, x, B, C, dt
    return {
        "in_proj": L.init_dense(ks[0], d, proj_out, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_kernel, cfg.conv_dim))
                   * 0.2).astype(dtype),
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),       # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": L.init_norm(di, dtype),
        "out_proj": L.init_dense(ks[2], di, d, dtype, scale=di ** -0.5),
    }


def _causal_conv(x, w, b, state=None, lengths=None):
    """Depthwise causal conv. x: (B,S,C), w: (K,C). state: (B,K-1,C)|None.

    ``lengths`` (B,) makes the NEW state ragged-aware: with right-padded
    inputs the carried window must hold the last K-1 VALID positions of
    each slot, i.e. ``xp[b, lengths[b] : lengths[b]+K-1]`` (``xp`` prepends
    the K-1 carry rows, so index ``lengths`` is exactly that window; a slot
    with lengths == 0 keeps its state bit-identical)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    if lengths is None:
        new_state = xp[:, -(K - 1):, :]
    else:
        new_state = jax.vmap(
            lambda xb, l: jax.lax.dynamic_slice(
                xb, (l, 0), (K - 1, xb.shape[1])))(xp, lengths)
    return out + b, new_state



SSD_CHUNK = 256


def _ssd_chunked(xs, Bt, Ct, dt, la, h0, chunk=None):
    """Chunkwise-parallel SSD (Mamba2).  xs (B,S,H,P); Bt/Ct (B,S,N);
    dt/la (B,S,H) with la = dt*A <= 0; h0 (B,H,P,N) f32.
    Returns (h_final, y (B,S,H,P) f32).

    Padding steps use dt=0 (=> la=0): exact identity on the state.
    """
    B, S, H, P = xs.shape
    N = Bt.shape[-1]
    Q = min(chunk or SSD_CHUNK, S)
    pad = (-S) % Q
    if pad:
        zf = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        xs, Bt, Ct, dt, la = map(zf, (xs, Bt, Ct, dt, la))
    Sp = S + pad
    nc = Sp // Q

    def r(a):  # (B,Sp,...) -> (nc, B, Q, ...)
        return a.reshape(B, nc, Q, *a.shape[2:]).swapaxes(0, 1)

    xs_c, B_c, C_c, dt_c, la_c = map(r, (xs, Bt, Ct, dt, la))

    @jax.checkpoint
    def chunk_fn(h, inp):
        xc, bc, cc, dtc, lac = inp
        xc = xs_f = xc.astype(jnp.float32)
        bc = bc.astype(jnp.float32)
        cc = cc.astype(jnp.float32)
        ca = jnp.cumsum(lac, axis=1)                       # (B,Q,H) inclusive
        # intra-chunk: y_t += sum_{s<=t} exp(ca_t - ca_s) dt_s (C_t.B_s) x_s
        cb = constrain(jnp.einsum("bqn,bsn->bqs", cc, bc), "act")  # (B,Q,Q)
        L = jnp.exp(ca[:, :, None, :] - ca[:, None, :, :])  # (B,Q,S=Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
        W = constrain(cb[..., None] * jnp.where(tri, L, 0.0), "act")
        y = jnp.einsum("bqsh,bsh,bshp->bqhp", W, dtc, xs_f)
        # inter-chunk: y_t += exp(ca_t) C_t . h0
        y = y + jnp.einsum("bqn,bhpn->bqhp", cc, h) *             jnp.exp(ca)[..., None]
        # state update: h' = exp(ca_Q) h0 + sum_s exp(ca_Q - ca_s) dt_s B_s x_s
        dlast = jnp.exp(ca[:, -1:, :] - ca)                # (B,Q,H)
        h = jnp.exp(ca[:, -1, :])[:, :, None, None] * h +             jnp.einsum("bsh,bshp,bsn->bhpn", dlast * dtc, xs_f, bc)
        return h, y

    hT, ys = jax.lax.scan(chunk_fn, h0, (xs_c, B_c, C_c, dt_c, la_c))
    y = ys.swapaxes(0, 1).reshape(B, Sp, H, P)[:, :S]
    return hT, y


def _ssm(xs, Bt, Ct, dt, la, h0):
    """The selective state space of one group: xs (B,S,H,P), Bt/Ct (B,S,N)
    shared by all H heads, dt/la (B,S,H), h0 (B,H,P,N) f32.  Returns
    (h_final, y (B,S,H,P) f32)."""
    if xs.shape[1] > 1:
        # chunkwise SSD (parallel within chunks, O(S*Q) not O(S) scan steps;
        # backward stores only per-chunk states -> bounded memory)
        return _ssd_chunked(xs, Bt, Ct, dt, la, h0)
    dA = jnp.exp(la)

    def step(h, inp):
        xs_t, B_t, C_t, dA_t, dt_t = inp
        dBx = jnp.einsum("bhp,bn,bh->bhpn", xs_t.astype(jnp.float32),
                         B_t.astype(jnp.float32), dt_t)
        h = h * dA_t[..., None, None] + dBx
        yt = jnp.einsum("bhpn,bn->bhp", h, C_t.astype(jnp.float32))
        return h, yt
    inps = (xs.transpose(1, 0, 2, 3), Bt.transpose(1, 0, 2),
            Ct.transpose(1, 0, 2), dA.transpose(1, 0, 2),
            dt.transpose(1, 0, 2))
    hT, ys = jax.lax.scan(step, h0, inps)
    return hT, ys.transpose(1, 0, 2, 3)                         # (B,S,H,hd)


def _grouped_ssm(G, xs, Bt, Ct, dt, la, h0):
    """G groups of B and C: the state space of each group over its H/G
    heads (head h reads group h // (H/G)), mapped over the groups."""
    B, S, H, P = xs.shape
    N = Bt.shape[-1] // G
    heads = lambda a, ax: a.reshape(a.shape[:ax] + (G, H // G) +
                                    a.shape[ax + 1:])
    hT, y = jax.vmap(_ssm, in_axes=(2, 2, 2, 2, 2, 1), out_axes=(1, 2))(
        heads(xs, 2), Bt.reshape(B, S, G, N), Ct.reshape(B, S, G, N),
        heads(dt, 2), heads(la, 2), heads(h0, 1))
    return hT.reshape(B, H, P, N), y.reshape(B, S, H, P)


def _group_norm(p, y, groups):
    """RMSNorm over each of ``groups`` equal slices of the channels (the
    gated norm of a grouped Mamba2); one group is the plain RMSNorm."""
    if groups == 1:
        return L.norm(p, y)
    yg = y.reshape(*y.shape[:-1], groups, -1)
    scale = p["scale"].reshape(groups, -1)
    return L.norm({"scale": scale}, yg).reshape(y.shape)


def mamba2(p, x, cfg: Mamba2Config, state=None, name=None, length_mask=None):
    """x: (B,S,D). Returns (y, new_state). Recurrent selective-state scan.

    ``length_mask`` (B,S) bool marks the VALID positions of right-padded
    ragged inputs (continuous-batching prefill / per-slot decode): padded
    steps run with dt = 0, which is an exact identity on the SSM state
    (see `_ssd_chunked`), and the conv carry keeps each slot's last valid
    window.  Outputs at masked positions are garbage by contract."""
    B, S, D = x.shape
    di, H, hd = cfg.d_inner, cfg.n_heads, cfg.head_dim
    GN = cfg.ngroups * cfg.d_state
    zxbcdt = L.dense(p["in_proj"], x, _j(name, "in_proj"))
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + cfg.conv_dim]
    dt_raw = zxbcdt[..., di + cfg.conv_dim:]                    # (B,S,H)

    lengths = (jnp.sum(length_mask.astype(jnp.int32), axis=-1)
               if length_mask is not None else None)
    conv_state = state["conv"] if state is not None else None
    xbc = constrain(xbc, "act")
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state,
                                 lengths=lengths)
    xbc = jax.nn.silu(xbc)
    xs = constrain(xbc[..., :di].reshape(B, S, H, hd), "act")
    Bt = xbc[..., di:di + GN]                                   # (B,S,G*N)
    Ct = xbc[..., di + GN:]                                     # (B,S,G*N)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    if length_mask is not None:
        dt = dt * length_mask[..., None].astype(dt.dtype)
    A = -jnp.exp(p["A_log"])                                    # (H,)

    h0 = (state["ssm"] if state is not None
          else jnp.zeros((B, H, hd, cfg.d_state), jnp.float32))

    la = dt * A                                                 # (B,S,H) <= 0
    if cfg.ngroups == 1:
        hT, y = _ssm(xs, Bt, Ct, dt, la, h0)
    else:
        hT, y = _grouped_ssm(cfg.ngroups, xs, Bt, Ct, dt, la, h0)
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(x.dtype) * jax.nn.silu(z)
    y = _group_norm(p["norm"], y, cfg.ngroups)
    out = L.dense(p["out_proj"], y, _j(name, "out_proj"))
    new_state = {"ssm": hT, "conv": new_conv}
    return out, new_state


def mamba2_init_state(B, cfg: Mamba2Config, dtype=jnp.bfloat16):
    return {"ssm": jnp.zeros((B, cfg.n_heads, cfg.head_dim, cfg.d_state),
                             jnp.float32),
            "conv": jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_dim), dtype)}


# ===================================================================== mLSTM

@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    d_model: int
    n_heads: int = 4
    expand: int = 2
    conv_kernel: int = 4

    @property
    def d_inner(self):
        return self.expand * self.d_model

    @property
    def head_dim(self):
        return self.d_inner // self.n_heads


def init_mlstm(key, cfg: XLSTMConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 7)
    d, di, H = cfg.d_model, cfg.d_inner, cfg.n_heads
    hd = cfg.head_dim
    bd = lambda k: (jax.random.normal(k, (H, hd, hd)) * hd ** -0.5).astype(dtype)
    return {
        "up": L.init_dense(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_kernel, di)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        # block-diagonal per-head q/k/v (xLSTM: di^2/H params each, not di^2)
        "wq_bd": bd(ks[2]),
        "wk_bd": bd(ks[3]),
        "wv_bd": bd(ks[4]),
        "w_if": L.init_dense(ks[5], di, 2 * H, dtype),   # input+forget gates
        "norm": L.init_norm(di, dtype),
        "down": L.init_dense(ks[6], di, d, dtype, scale=di ** -0.5),
    }



MLSTM_CHUNK = 256


def _mlstm_chunked(q, k, v, ig, fg, state, chunk=None):
    """Chunkwise-parallel mLSTM with exact exponential-gating stabilization.

    q/k/v (B,S,H,hd); ig/fg (B,S,H) raw gate pre-activations; state
    (C0 (B,H,hd,hd), n0 (B,H,hd), m0 (B,H)) in the same scaled convention as
    the recurrent step (stored C == true C / exp(m)).
    Returns ((C,n,m), h (B,S,H,hd) f32).

    Derivation (matches the recurrent form exactly): with a = cumsum(logf)
    inclusive and u_s = i_s - a_s,
      m_t     = a_t + M_t,  M_t = max(cummax_{s<=t} u_s, m0)
      h_t     = [ sum_{s<=t} exp(u_s - M_t) (q_t.k_s) v_s
                  + exp(m0 - M_t) q_t.C0 ] / max(|den|, exp(-m_t))
      den     = sum_{s<=t} exp(u_s - M_t) (q_t.k_s) + exp(m0 - M_t) q_t.n0
    Padding steps use f=+inf (logf=0) and i=-inf: exact identity.
    """
    B, S, H, hd = q.shape
    Q = min(chunk or MLSTM_CHUNK, S)
    pad = (-S) % Q
    if pad:
        pf = lambda a, val: jnp.pad(
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2),
            constant_values=val)
        q, k, v = pf(q, 0), pf(k, 0), pf(v, 0)
        ig = pf(ig, -1e30)     # i = -inf: no input
        fg = pf(fg, 80.0)      # sigmoid(80) ~ 1: no decay
    Sp = S + pad
    nc = Sp // Q

    def r(a):
        return a.reshape(B, nc, Q, *a.shape[2:]).swapaxes(0, 1)

    qc, kc, vc, igc, fgc = map(r, (q, k, v, ig, fg))

    @jax.checkpoint
    def chunk_fn(carry, inp):
        C0, n0, m0 = carry
        qt, kt, vt, it, ft = inp
        qt = qt.astype(jnp.float32)
        kt = kt.astype(jnp.float32)
        vt = vt.astype(jnp.float32)
        logf = jax.nn.log_sigmoid(ft)                    # (B,Q,H)
        a = jnp.cumsum(logf, axis=1)
        u = it - a                                       # (B,Q,H)
        Mt = jnp.maximum(jax.lax.cummax(u, axis=1), m0[:, None, :])
        # intra-chunk scores, gated:  g(t,s) = exp(a_t - a_s + i_s - m_t)
        #                                    = exp(u_s - M_t)  (a_t cancels)
        # NOTE: k arrives pre-scaled by hd**-0.5 (see mlstm()).
        qk = constrain(jnp.einsum("bqhd,bshd->bhqs", qt, kt), "act")
        g = jnp.exp(u.transpose(0, 2, 1)[:, :, None, :] -
                    Mt.transpose(0, 2, 1)[:, :, :, None])  # (B,H,t,s)
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None]
        w = jnp.where(tri, qk * g, 0.0)                  # (B,H,t,s)
        num = jnp.einsum("bhts,bshd->bthd", w, vt)
        den = jnp.sum(w, axis=-1).transpose(0, 2, 1)     # (B,t,H)
        # inter-chunk from carried state
        inter_scale = jnp.exp(m0[:, None, :] - Mt)       # (B,t,H)
        qC = jnp.einsum("bqhk,bhvk->bqhv", qt, C0)
        num = num + inter_scale[..., None] * qC
        den = den + inter_scale * jnp.einsum("bqhk,bhk->bqh", qt, n0)
        m_t = a + Mt
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # end-of-chunk state (scaled convention)
        MQ = Mt[:, -1, :]
        dec = jnp.exp(u - MQ[:, None, :])                # (B,s,H)
        Cn = jnp.einsum("bsh,bshv,bshk->bhvk", dec, vt, kt) + \
            jnp.exp(m0 - MQ)[..., None, None] * C0
        nn = jnp.einsum("bsh,bshk->bhk", dec, kt) + \
            jnp.exp(m0 - MQ)[..., None] * n0
        mn = a[:, -1, :] + MQ
        return (Cn, nn, mn), h

    (CT, nT, mT), hs = jax.lax.scan(chunk_fn, state, (qc, kc, vc, igc, fgc))
    h = hs.swapaxes(0, 1).reshape(B, Sp, H, hd)[:, :S]
    return (CT, nT, mT), h


def mlstm(p, x, cfg: XLSTMConfig, state=None, name=None, length_mask=None):
    """Matrix-memory LSTM with exponential gating (xLSTM), recurrent form.

    ``length_mask`` (B,S) marks valid positions of ragged inputs: masked
    steps reuse the chunked path's padding convention (i = -inf: no input,
    f ~ +inf: no decay) so they are an identity on (C, n, m), and the conv
    carry keeps each slot's last valid window."""
    B, S, D = x.shape
    di, H, hd = cfg.d_inner, cfg.n_heads, cfg.head_dim
    uz = L.dense(p["up"], x, _j(name, "up"))
    u, z = uz[..., :di], uz[..., di:]
    lengths = (jnp.sum(length_mask.astype(jnp.int32), axis=-1)
               if length_mask is not None else None)
    conv_state = state["conv"] if state is not None else None
    uc, new_conv = _causal_conv(u, p["conv_w"], p["conv_b"], conv_state,
                                lengths=lengths)
    uc = constrain(jax.nn.silu(uc), "act")
    uh = uc.reshape(B, S, H, hd)
    q = constrain(jnp.einsum("bshd,hdk->bshk", uh, p["wq_bd"]), "act")
    k = constrain(jnp.einsum("bshd,hdk->bshk", uh, p["wk_bd"]), "act") * hd ** -0.5
    v = constrain(jnp.einsum("bshd,hdk->bshk", uh, p["wv_bd"]), "act")
    gates = L.dense(p["w_if"], uc, _j(name, "w_if")).astype(jnp.float32)  # (B,S,2H)
    ig, fg = gates[..., :H], gates[..., H:]
    if length_mask is not None:
        ig = jnp.where(length_mask[..., None], ig, -1e30)
        fg = jnp.where(length_mask[..., None], fg, 80.0)

    if state is None:
        C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
        m0 = jnp.zeros((B, H), jnp.float32)
    else:
        C0, n0, m0 = state["C"], state["n"], state["m"]

    if S > 1:
        (CT, nT, mT), hs = _mlstm_chunked(q, k, v, ig, fg, (C0, n0, m0))
        h = hs.reshape(B, S, di).astype(x.dtype)
    else:
        def step(carry, inp):
            C, n, m = carry
            q_t, k_t, v_t, i_t, f_t = inp
            # stabilized exponential gating (xLSTM eq. 15-19)
            logf = jax.nn.log_sigmoid(f_t)
            m_new = jnp.maximum(logf + m, i_t)
            fs = jnp.exp(logf + m - m_new)
            is_ = jnp.exp(i_t - m_new)
            kf, vf = k_t.astype(jnp.float32), v_t.astype(jnp.float32)
            C = C * fs[..., None, None] + is_[..., None, None] * \
                jnp.einsum("bhv,bhk->bhvk", vf, kf)
            n = n * fs[..., None] + is_[..., None] * kf
            qf = q_t.astype(jnp.float32)
            num = jnp.einsum("bhvk,bhk->bhv", C, qf)
            den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qf)),
                              jnp.exp(-m_new))[..., None]
            return (C, n, m_new), num / den

        inps = (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
                v.transpose(1, 0, 2, 3), ig.transpose(1, 0, 2),
                fg.transpose(1, 0, 2))
        (CT, nT, mT), hs = jax.lax.scan(step, (C0, n0, m0), inps)
        h = hs.transpose(1, 0, 2, 3).reshape(B, S, di).astype(x.dtype)
    h = L.norm(p["norm"], h) * jax.nn.silu(z)
    out = L.dense(p["down"], h, _j(name, "down"))
    return out, {"C": CT, "n": nT, "m": mT, "conv": new_conv}


def mlstm_init_state(B, cfg: XLSTMConfig, dtype=jnp.bfloat16):
    H, hd = cfg.n_heads, cfg.head_dim
    return {"C": jnp.zeros((B, H, hd, hd), jnp.float32),
            "n": jnp.zeros((B, H, hd), jnp.float32),
            "m": jnp.zeros((B, H), jnp.float32),
            "conv": jnp.zeros((B, cfg.conv_kernel - 1, cfg.d_inner), dtype)}


# ===================================================================== sLSTM

def init_slstm(key, cfg: XLSTMConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    d, di, H, hd = cfg.d_model, cfg.d_inner, cfg.n_heads, cfg.head_dim
    return {
        "w_in": L.init_dense(ks[0], d, 4 * di, dtype),          # i,f,z,o pre-acts
        "r": (jax.random.normal(ks[1], (H, hd, 4 * hd)) * hd ** -0.5).astype(dtype),
        "norm": L.init_norm(di, dtype),
        "down": L.init_dense(ks[2], di, d, dtype, scale=di ** -0.5),
    }


def slstm(p, x, cfg: XLSTMConfig, state=None, name=None, length_mask=None):
    """Scalar-memory LSTM with exponential gating + recurrent head mixing.

    ``length_mask`` (B,S) marks valid positions of ragged inputs; masked
    steps carry (c, n, h, m) through unchanged (the recurrent h-mixing
    makes a gate-level identity impossible, so the step SELECTS the old
    carry instead)."""
    B, S, D = x.shape
    di, H, hd = cfg.d_inner, cfg.n_heads, cfg.head_dim
    pre = L.dense(p["w_in"], x, _j(name, "w_in")).reshape(B, S, H, 4 * hd)

    if state is None:
        c0 = jnp.zeros((B, H, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
        h0 = jnp.zeros((B, H, hd), jnp.float32)
        m0 = jnp.zeros((B, H), jnp.float32)
    else:
        c0, n0, h0, m0 = state["c"], state["n"], state["h"], state["m"]

    r = p["r"].astype(jnp.float32)
    mask = (jnp.ones((B, S), bool) if length_mask is None
            else length_mask.astype(bool))

    def step(carry, inp):
        pre_t, m_t_ = inp
        c, n, h, m = carry
        rec = jnp.einsum("bhk,hkj->bhj", h, r)                  # (B,H,4hd)
        g = pre_t.astype(jnp.float32) + rec
        i_t, f_t, z_t, o_t = jnp.split(g, 4, axis=-1)
        i_t, f_t = i_t.mean(-1), f_t.mean(-1)                   # scalar/head gates
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        fs = jnp.exp(logf + m - m_new)[..., None]
        is_ = jnp.exp(i_t - m_new)[..., None]
        c_new = c * fs + is_ * jnp.tanh(z_t)
        n_new = n * fs + is_
        h_new = jax.nn.sigmoid(o_t) * c_new / jnp.maximum(n_new, 1e-6)
        sel2, sel3 = m_t_[:, None], m_t_[:, None, None]
        carry = (jnp.where(sel3, c_new, c), jnp.where(sel3, n_new, n),
                 jnp.where(sel3, h_new, h), jnp.where(sel2, m_new, m))
        return carry, h_new

    (cT, nT, hT, mT), hs = jax.lax.scan(step, (c0, n0, h0, m0),
                                        (pre.transpose(1, 0, 2, 3),
                                         mask.transpose(1, 0)))
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, di).astype(x.dtype)
    out = L.dense(p["down"], L.norm(p["norm"], h), _j(name, "down"))
    return out, {"c": cT, "n": nT, "h": hT, "m": mT}


def slstm_init_state(B, cfg: XLSTMConfig):
    H, hd = cfg.n_heads, cfg.head_dim
    z = lambda *s: jnp.zeros(s, jnp.float32)
    return {"c": z(B, H, hd), "n": z(B, H, hd), "h": z(B, H, hd), "m": z(B, H)}
